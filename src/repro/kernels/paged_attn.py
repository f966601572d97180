"""Pallas TPU kernel: paged decode attention (one query token per slot).

The jnp reference path in ``models.attention.paged_decode_attention``
gathers every slot's pages into a contiguous (B, S, K, hd) buffer and
runs a masked softmax — an HBM round-trip of the whole working set per
step. This kernel instead walks the page list with a scalar-prefetched
page map: grid = (slot, page_index), the BlockSpec index_map reads
``page_map[b, j]`` to DMA exactly one (page_size, K, hd) page per step,
and an online-softmax accumulator in VMEM scratch carries the partial
attention across a slot's pages (same flash-decode recurrence as
``models.attention.flash_attention``).

Masking is positional: page ``j`` holds absolute positions
``[j*page_size, (j+1)*page_size)``; entries beyond ``pos[b]`` (or
outside the sliding band) are NEG_INF'd, so dummy-page garbage never
contributes. Runs in ``interpret=True`` off-TPU via
``runtime.resolve_interpret`` like every kernel in this package.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _paged_decode_kernel(pm_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, page_size: int,
                         pages_per_slot: int, window: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                 # (K, G, hd)
    k = k_ref[0].astype(jnp.float32)                 # (ps, K, hd)
    v = v_ref[0].astype(jnp.float32)
    hd = q.shape[-1]

    s = jnp.einsum("kgh,skh->kgs", q * hd ** -0.5, k,
                   preferred_element_type=jnp.float32)   # (K, G, ps)
    pos = pos_ref[b]
    k_pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (page_size,), 0)
    valid = k_pos <= pos
    if window:
        valid = valid & (k_pos > pos - window)
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_new = acc_ref[...] * alpha[..., None] + jnp.einsum(
        "kgs,skh->kgh", p, v, preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(j == pages_per_slot - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[..., None]).astype(o_ref.dtype)


def paged_decode(q, k_pages, v_pages, page_map, pos, *, window: int = 0,
                 interpret: Optional[bool] = None):
    """Paged single-token attention.

    q: (B, K, G, hd); k_pages/v_pages: (num_pages, page_size, K, hd);
    page_map: (B, pages_per_slot) int32; pos: (B,) int32. Returns the
    softmax-weighted values (B, K, G, hd) in fp32 (caller projects).
    """
    interpret = resolve_interpret(interpret)
    B, K, G, hd = q.shape
    _, ps = k_pages.shape[:2]
    P = page_map.shape[1]
    kern = functools.partial(_paged_decode_kernel, page_size=ps,
                             pages_per_slot=P, window=int(window))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # page_map, pos
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, K, G, hd), lambda b, j, pm, pos: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, K, hd),
                         lambda b, j, pm, pos: (pm[b, j], 0, 0, 0)),
            pl.BlockSpec((1, ps, K, hd),
                         lambda b, j, pm, pos: (pm[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd),
                               lambda b, j, pm, pos: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G, hd), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), jnp.float32),
        interpret=interpret, name="paged_decode")
    return fn(page_map.astype(jnp.int32), pos.astype(jnp.int32),
              q.astype(jnp.float32), k_pages, v_pages)


__all__ = ["paged_decode"]
