"""Pallas TPU kernel: fused last-microstep SGD + D2D consensus mixing.

One consensus block of the TT-HF interval ends with an SGD update
followed by the block-diagonal mixing ``z_c <- W_c z_c`` (the
``fused_power`` backend's precomputed ``W = V^Gamma``). Run separately
those are two full parameter-stream HBM passes: read w / read g /
write w, then read w / write w. This kernel fuses them into ONE pass —
read w, read g, write mixed w, in place — over the ``(R, rows, LANE)``
replica buffer of the fused-interval step
(:func:`repro.core.distributed.make_tthf_train_step` with
``fused_interval=True``), viewed per cluster as ``(N, s, rows, LANE)``.

Math (the same f32 operations as the XLA path,
:func:`repro.core.mixing.mix_blocks`):

    w' = w - eta * (g + wd * w)          (per replica, f32)
    z_ci <- sum_j W_cij w'_cj            (per cluster, s terms in order)

The mixing is an explicit sum with W read as scalars from SMEM, not an
MXU matmul: exact f32 (a DEFAULT-precision f32 matmul is one bf16
pass) and free of the (s, M) tile a matmul would need.

Grid: (N, rows / blk_rows). An (s, blk_rows, LANE) tile of w and g is
pinned in VMEM; each entry mixes independently, so the zero padding
between pytree leaves stays zero.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

LANE = 128
SUBLANE = 8


def _kernel(w_ref, g_ref, mix_ref, eta_ref, o_ref, *,
            weight_decay: float):
    n = pl.program_id(0)
    s = w_ref.shape[1]
    w = w_ref[0].astype(jnp.float32)          # (s, blk_rows, LANE)
    g = g_ref[0].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * w
    wp = w - eta_ref[0] * g
    for i in range(s):
        acc = mix_ref[n, i, 0] * wp[0]
        for j in range(1, s):
            acc = acc + mix_ref[n, i, j] * wp[j]
        o_ref[0, i] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("weight_decay", "blk_rows", "interpret"))
def fused_consensus_sgd(w: jax.Array, g: jax.Array, W: jax.Array,
                        eta: jax.Array, weight_decay: float = 0.0,
                        blk_rows: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """w, g: (N, s, rows, LANE); W: (N, s, s); returns
    ``W @ (w - eta*g)`` per cluster, written over w (donate it).

    ``interpret=None`` auto-detects (interpret only off-TPU).
    ``blk_rows=None`` picks 512 rows compiled (a 256 KiB tile per
    member at s=2) and 8192 interpreted (fewer unrolled grid cells).
    """
    interpret = resolve_interpret(interpret)
    if blk_rows is None:
        blk_rows = 8_192 if interpret else 512
    N, s, rows, lane = w.shape
    assert lane == LANE and g.shape == w.shape and W.shape == (N, s, s)

    # a SUBLANE multiple, or all rows; a ragged last block needs no pad
    # copy of the parameter-sized streams (its out-of-range rows are
    # never written back, and entries mix independently)
    blk = rows if rows <= blk_rows else max(SUBLANE,
                                            blk_rows // SUBLANE * SUBLANE)
    eta_arr = jnp.asarray(eta, jnp.float32).reshape(1)
    tile = pl.BlockSpec((1, s, blk, LANE), lambda n, r: (n, 0, r, 0))

    return pl.pallas_call(
        functools.partial(_kernel, weight_decay=weight_decay),
        grid=(N, pl.cdiv(rows, blk)),
        in_specs=[
            tile, tile,
            pl.BlockSpec(memory_space=pltpu.SMEM),   # W, read as scalars
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scalar eta
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        # each tile is read before its mixed value is written over it:
        # the update runs in place (no third parameter-sized buffer)
        input_output_aliases={0: 0},
        interpret=interpret,
        name="fused_consensus_sgd",
    )(w, g, W.astype(jnp.float32), eta_arr)
