"""Pallas TPU kernel: Mamba-2 SSD chunked scan (state-space duality).

The SSD recurrence  h_t = a_t h_{t-1} + dt_t B_t (x) x_t ;  y_t = C_t h_t
is evaluated in chunks of Q tokens (arXiv:2405.21060):

  intra-chunk:  Y += (L o (C B^T) o dt) X        -- quadratic in Q, MXU
  inter-chunk:  Y += (C o exp(l)) H_prev         -- state broadcast
  state carry:  H  = exp(l_Q) H_prev + (B o exp(l_Q - l) o dt)^T X

where l is the in-chunk cumulative log decay. The running state H lives
in a VMEM scratch buffer that persists across the chunk axis of the grid
(minor-most => sequential), so HBM sees each token exactly once in and
once out — the memory-optimal schedule for a recurrent scan on TPU.

Grid: (BH, T/Q). Block shapes: X (Q, P), B/C (Q, S), and the decay
rows dt/loga as (1, Q) blocks of a (BH, 1, T) view (tile-legal: the
unit dim equals the array's, Q is a lane multiple);
defaults Q=256, S=128, P=64 keep the working set ~0.6 MB << 16 MB VMEM
and all matmul dims MXU-aligned (Q, S multiples of 128; P=64 packs the
lane dim at half utilization, the native Mamba-2 head size).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, loga_ref, b_ref, c_ref, y_ref, hfin_ref, h_scr):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (1, Q) row
    la = loga_ref[0].astype(jnp.float32)      # (1, Q) row
    b = b_ref[0].astype(jnp.float32)          # (Q, S)
    c = c_ref[0].astype(jnp.float32)          # (Q, S)

    q = x.shape[0]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    u_idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = t_idx >= u_idx
    diag = t_idx == u_idx

    # Mosaic lowers neither cumsum nor a (1, Q) -> (Q, 1) relayout, so
    # both come from masked (Q, Q) reductions (adding zeros is exact):
    # the inclusive cumulative log decay l_t = sum_{u <= t} la_u as a
    # column, then its row twin and dt's column twin off the diagonal
    l_col = jnp.sum(jnp.where(causal, la, 0.0), axis=1, keepdims=True)
    l_row = jnp.sum(jnp.where(diag, l_col, 0.0), axis=0, keepdims=True)
    dt_col = jnp.sum(jnp.where(diag, dt, 0.0), axis=1, keepdims=True)
    total = jnp.sum(la, axis=1, keepdims=True)                # (1, 1)

    # intra-chunk: M[t,u] = exp(l_t - l_u) * dt_u  for u <= t
    g = jnp.dot(c, b.T, preferred_element_type=jnp.float32)   # (Q, Q)
    # clamp to <= 0: exact on causal entries (l is non-increasing) and
    # keeps the masked half from overflowing exp (inf * 0 = nan in the
    # backward pass)
    decay = jnp.exp(jnp.minimum(l_col - l_row, 0.0))
    m = jnp.where(causal, g * decay * dt, 0.0)
    y = jnp.dot(m, x, preferred_element_type=jnp.float32)     # (Q, P)

    # inter-chunk: contribution of the carried state
    h = h_scr[...]                                            # (S, P)
    c_decayed = c * jnp.exp(l_col)
    y = y + jnp.dot(c_decayed, h, preferred_element_type=jnp.float32)

    # state update
    b_decayed = b * (jnp.exp(total - l_col) * dt_col)         # (Q, S)
    h_new = jnp.exp(total) * h + jnp.dot(
        b_decayed.T, x, preferred_element_type=jnp.float32)
    h_scr[...] = h_new

    y_ref[0] = y.astype(y_ref.dtype)
    hfin_ref[0] = h_new.astype(hfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, loga: jax.Array, B: jax.Array,
             C: jax.Array, chunk: int = 256,
             interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """x: (BH, T, P), dt/loga: (BH, T), B/C: (BH, T, S).

    Returns (y: (BH, T, P), h_final: (BH, S, P)). T must be a multiple
    of ``chunk`` (ops.py pads).
    """
    from repro.kernels.runtime import resolve_interpret
    interpret = resolve_interpret(interpret)
    BH, T, P = x.shape
    S = B.shape[-1]
    assert T % chunk == 0, f"T={T} not a multiple of chunk={chunk}"
    nc = T // chunk

    y, hfin = pl.pallas_call(
        _kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, c: (bh, 0, c)),
            pl.BlockSpec((1, 1, chunk), lambda bh, c: (bh, 0, c)),
            pl.BlockSpec((1, chunk, S), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, S), lambda bh, c: (bh, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, S, P), lambda bh, c: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, P), x.dtype),
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((S, P), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, dt[:, None], loga[:, None], B, C)
    return y, hfin
