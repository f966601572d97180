"""Pallas TPU kernel: fused SGD update  w <- w - eta * (g + wd * w).

Trivial arithmetic, but fusing the schedule multiply + weight decay +
subtract into one pass halves parameter-stream HBM traffic inside the
tau-step TT-HF local scan (read w, read g, write w — vs an extra
round-trip for the scaled gradient).

Grid: 1-D over flattened, lane-padded parameter tiles. The flat size is
padded up to a lane multiple (128) ONCE so every block is lane-aligned —
a small leaf (n < 128) used to produce a non-lane-multiple block that
Mosaic would have to re-tile.
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


def _kernel(w_ref, g_ref, eta_ref, o_ref, *, weight_decay: float):
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * w
    o_ref[...] = (w - eta_ref[0] * g).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("weight_decay", "blk", "interpret"))
def fused_sgd(w: jax.Array, g: jax.Array, eta: jax.Array,
              weight_decay: float = 0.0, blk: int = 65_536,
              interpret: Optional[bool] = None) -> jax.Array:
    """Flat or shaped arrays; returns updated w with the same shape.

    ``interpret=None`` auto-detects (interpret only off-TPU)."""
    interpret = resolve_interpret(interpret)
    shape, dtype = w.shape, w.dtype
    wf, gf = w.reshape(-1), g.reshape(-1)
    n = wf.size
    # lane-align once: blk is always a multiple of LANE, and the single
    # pad (on both streamed operands) rounds n up to a blk multiple
    blk = max(LANE, min(blk, -(-n // LANE) * LANE))
    assert blk % LANE == 0
    pad = (-n) % blk
    if pad:
        wf = jnp.pad(wf, (0, pad))
        gf = jnp.pad(gf, (0, pad))
    eta_arr = jnp.asarray(eta, jnp.float32).reshape(1)

    out = pl.pallas_call(
        functools.partial(_kernel, weight_decay=weight_decay),
        grid=(wf.size // blk,),
        in_specs=[
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scalar eta
        ],
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((wf.size,), dtype),
        interpret=interpret,
        name="fused_sgd",
    )(wf, gf, eta_arr)
    return out[:n].reshape(shape)
