"""Pallas TPU kernel: fused Gamma-round D2D consensus mixing.

Computes ``z_c <- V_c^{gamma_c} z_c`` for N stacked clusters without
round-tripping intermediates through HBM: the (s, s) mixing matrix and an
(s, blk_m) parameter tile are pinned in VMEM and the Gamma-round loop of
small MXU matmuls runs in registers/VMEM. HBM traffic drops from
``2 * Gamma * s * M`` words (the naive per-round einsum) to ``2 * s * M``
— a Gamma-fold cut, and Remark 1 routinely asks for Gamma in the tens.

Grid: (N, M / blk_m); gamma is a scalar-prefetch operand so each cluster
can run a *different* (aperiodic, Remark-1) round count.

TPU notes: blk_m defaults to 512 lanes (4 x 128); s is the cluster size
(tiny, e.g. 5) — Mosaic pads the sublane dim to 8. The matmul chain
accumulates in fp32 via preferred_element_type regardless of z dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret


def _kernel(gamma_ref, z_ref, v_ref, o_ref):
    n = pl.program_id(0)
    gamma_n = gamma_ref[n]
    v = v_ref[0].astype(jnp.float32)          # (s, s)
    z0 = z_ref[0].astype(jnp.float32)         # (s, blk_m)

    def body(_, z):
        return jnp.dot(v, z, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    z = jax.lax.fori_loop(0, gamma_n, body, z0)
    o_ref[0] = z.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_m", "interpret"))
def consensus_mix(z: jax.Array, V: jax.Array, gamma: jax.Array,
                  blk_m: int = 512,
                  interpret: Optional[bool] = None) -> jax.Array:
    """z: (N, s, M), V: (N, s, s), gamma: (N,) int32.

    ``interpret=None`` auto-detects (interpret only off-TPU)."""
    interpret = resolve_interpret(interpret)
    N, s, M = z.shape
    gamma = jnp.asarray(gamma, jnp.int32)
    if gamma.ndim == 0:
        gamma = jnp.full((N,), gamma)

    blk = min(blk_m, max(M, 1))
    pad = (-M) % blk
    zp = jnp.pad(z, ((0, 0), (0, 0), (0, pad))) if pad else z
    Mp = M + pad

    grid = (N, Mp // blk)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, s, blk), lambda n, m, g: (n, 0, m)),
                pl.BlockSpec((1, s, s), lambda n, m, g: (n, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, s, blk), lambda n, m, g: (n, 0, m)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, s, Mp), z.dtype),
        interpret=interpret,
        name="consensus_mix",
    )(gamma, zp, V)
    return out[:, :, :M] if pad else out
