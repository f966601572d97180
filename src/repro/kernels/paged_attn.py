"""Pallas TPU kernel: paged decode attention (one query token per slot).

The jnp reference path in ``models.attention.paged_decode_attention``
gathers every slot's pages into a contiguous (B, S, K, hd) buffer and
runs a masked softmax — an HBM round-trip of the whole working set per
step. This kernel instead walks each slot's page list, read from a
scalar-prefetched page map, in **blocks** of ``pages_per_block`` pages
(about 512 tokens, :func:`block_pages`), and only the blocks that
hold the slot's band of live positions
``[max(0, pos - window + 1), pos]`` (:func:`block_band`). A lane that is
not live (mid-prefill or retired) walks nothing.

The pools stay in HBM (``memory_space=pl.ANY``): the whole stack
``(layers, num_pages, page_size, K, hd)`` that the serving step carries
through its layer loop, with the layer index scalar-prefetched beside
the page map, so no layer's pool is sliced out of the stack (one
layer's pool is read as a stack of one). The kernel copies a
block's pages by hand, one async copy per page, into a VMEM buffer, and
double-buffers: the copies of the next live block (which may be the
next live slot's first) start before the current block is computed. So
a layer's decode step is one kernel invocation, a few scalar loops and
one copy of K and one of V per page of the walked blocks: the fixed
cost of a grid step per page, and not the bytes, bounded the page grid
below.

Compute per block: q is folded to ``(K*G, hd)`` and the block read as
``(block_tokens*K, hd)``, one dot scores every row against every
(token, head) column, and pairs that cross heads are masked with the
positions outside the band. An online-softmax accumulator (the
flash-decode recurrence of ``models.attention.flash_attention``)
carries the partial attention across a slot's blocks. Pages past
``pages_per_slot`` in a slot's last block re-read its last page and are
masked like any position past ``pos``.

On a TPU, a head dim that is not a multiple of 128 lanes leaves the
pool's rows lane-padded, and Mosaic cannot slice one page out of such
a pool: those shapes keep the page grid (:func:`page_grid`), grid =
(slot, page) with the BlockSpec index_map reading ``page_map[b, j]``,
which walks every page of every slot. Both run in ``interpret=True``
off-TPU via ``runtime.resolve_interpret`` like every kernel in this
package (where the block walk serves every head dim).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30
BLOCK_TOKENS = 512                  # tokens a block aims at
BLOCK_VMEM_BYTES = 4 << 20          # K and V buffers, double-buffered


def block_pages(page_size: int, pages_per_slot: int, kv_heads: int,
                head_dim: int, itemsize: int, window: int = 0) -> int:
    """Pages the kernel fetches per block: about ``BLOCK_TOKENS``
    tokens, no more than a slot holds, than the band of a sliding
    ``window`` needs, or than ``BLOCK_VMEM_BYTES`` of K and V buffers
    (two of each, lanes padded to 128) allow."""
    page_bytes = page_size * kv_heads * -(-head_dim // 128) * 128 * itemsize
    n = min(pages_per_slot, max(1, BLOCK_TOKENS // page_size),
            max(1, BLOCK_VMEM_BYTES // (4 * page_bytes)))
    if window:
        n = min(n, -(-window // page_size))
    return max(1, n)


def page_grid(head_dim: int, interpret: bool) -> bool:
    """Whether :func:`paged_decode` walks a page per grid step, every
    page of every slot, instead of blocks: on a TPU, for head dims that
    are not a multiple of 128 lanes, whose pool rows are lane-padded and
    from which Mosaic cannot slice one page."""
    return bool(head_dim % 128) and not interpret


def block_band(pos, live, *, window: int, block_tokens: int,
               num_blocks: int, xp=np):
    """``(first, count)``: the blocks of a slot at position ``pos`` that
    hold its band ``[max(0, pos - window + 1), pos]`` (``window`` 0: no
    band), clipped to the slot's ``num_blocks``; ``count`` is 0 where
    the slot is not ``live``. Works on host arrays (``xp=np``) and on
    the kernel's scalars (``xp=jnp``)."""
    last = xp.minimum(pos // block_tokens, num_blocks - 1)
    first = (xp.maximum(pos - window + 1, 0) // block_tokens if window
             else xp.zeros_like(pos))
    count = xp.where(live != 0, xp.maximum(last - first + 1, 0), 0)
    return first, count


def _paged_decode_kernel(pm_ref, pos_ref, live_ref, layer_ref, q_ref, k_hbm,
                         v_hbm, o_ref, k_buf, v_buf, sem, band_ref, m_ref,
                         l_ref, acc_ref, *, page_size: int,
                         pages_per_slot: int, pages_per_block: int,
                         window: int, group: int):
    B, rows, hd = q_ref.shape                        # rows = K * G
    K = rows // group
    P, ppb = pages_per_slot, pages_per_block
    bt = ppb * page_size                             # tokens per block
    nblk = -(-P // ppb)

    # per slot: first block, block count, next slot with a band (B: none)
    def plan(j, nxt):
        b = B - 1 - j
        first, count = block_band(pos_ref[b], live_ref[b], window=window,
                                  block_tokens=bt, num_blocks=nblk,
                                  xp=jnp)
        band_ref[0, b] = first
        band_ref[1, b] = count
        band_ref[2, b] = nxt
        return jnp.where(count > 0, b, nxt)

    b0 = jax.lax.fori_loop(0, B, plan, B)

    def copy(kv, page, half, j):
        """Page ``page`` of K (``kv`` 0) or V into page ``j`` of a block
        buffer; ``half`` picks one of the two buffers."""
        hbm, buf = (k_hbm, k_buf) if kv == 0 else (v_hbm, v_buf)
        return pltpu.make_async_copy(hbm.at[layer_ref[0], page],
                                     buf.at[half, j], sem.at[kv, half])

    def start(b, i, half):
        """One async copy per page of block ``i`` of slot ``b``."""
        for j in range(ppb):
            page = pm_ref[b, jnp.minimum(i * ppb + j, P - 1)]
            copy(0, page, half, j).start()
            copy(1, page, half, j).start()

    def wait(kv, half):
        # a wait needs only the semaphore and one copy's size: any page
        for j in range(ppb):
            copy(kv, 0, half, j).wait()

    @pl.when(b0 < B)
    def _prime():
        start(b0, band_ref[0, b0], 0)

    row_head = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bt * K), 1)
    same_head = row_head == col % K                  # (rows, bt*K)
    end = P * page_size                              # slot capacity

    def slot_body(b, half):
        first, count, nb = band_ref[0, b], band_ref[1, b], band_ref[2, b]
        pos = pos_ref[b]
        q = q_ref[b].astype(jnp.float32) * hd ** -0.5   # (rows, hd)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def block_body(t, half):
            i = first + t
            last = t + 1 >= count
            # the next live block: this slot's next, or the next band's
            # first; its copies overlap this block's compute
            nb_t = jnp.where(last, nb, b)
            ni = jnp.where(last, band_ref[0, jnp.minimum(nb, B - 1)], i + 1)

            @pl.when(nb_t < B)
            def _prefetch():
                start(nb_t, ni, 1 - half)

            wait(0, half)
            k = k_buf.at[half].reshape(bt * K, hd)[...].astype(jnp.float32)
            s = jnp.einsum("rh,ch->rc", q, k,
                           preferred_element_type=jnp.float32)
            # column c is token c // K of the block, head c % K: positions
            # as bounds on c, so no integer division per element
            base = i * bt
            hi = (jnp.minimum(pos, end - 1) - base + 1) * K
            valid = same_head & (col < hi)
            if window:
                valid = valid & (col >= (pos - window + 1 - base) * K)
            s = jnp.where(valid, s, NEG_INF)

            m_prev, l_prev = m_ref[...], l_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            wait(1, half)
            v = v_buf.at[half].reshape(bt * K, hd)[...].astype(jnp.float32)
            l_ref[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
                "rc,ch->rh", p, v, preferred_element_type=jnp.float32)
            m_ref[...] = m_new
            return 1 - half

        half = jax.lax.fori_loop(0, count, block_body, half)
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[b] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        return half

    jax.lax.fori_loop(0, B, slot_body, 0)


def _page_grid_kernel(pm_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                      acc_ref, m_ref, l_ref, *, page_size: int,
                      pages_per_slot: int, window: int):
    """One page of one slot per grid step (no band, no ``live``)."""
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


def _page_grid_decode(q, k_pages, v_pages, page_map, pos, window: int,
                      interpret: bool, layer=None):
    """Grid (slot, page): the pipeline fetches one page of layer
    ``layer`` per step."""
    k_pages, v_pages, layer = _as_stack(k_pages, v_pages, layer)
    B, K, G, hd = q.shape
    ps = k_pages.shape[2]
    P = page_map.shape[1]
    kern = functools.partial(_page_grid_kernel, page_size=ps,
                             pages_per_slot=P, window=window)
    page = pl.BlockSpec((None, 1, ps, K, hd), lambda b, j, pm, pos, ly:
                        (ly[0], pm[b, j], 0, 0, 0))
    slot = lambda b, j, pm, pos, ly: (b, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                       # page_map, pos, layer
        grid=(B, P),
        in_specs=[pl.BlockSpec((1, K, G, hd), slot), page, page],
        out_specs=pl.BlockSpec((1, K, G, hd), slot),
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
    return fn(page_map.astype(jnp.int32), pos.astype(jnp.int32), layer,
              q.astype(jnp.float32), k_pages, v_pages)


def _as_stack(k_pages, v_pages, layer):
    """The pools as a stack, and the scalar-prefetched layer index: one
    layer's pool is a stack of one (a leading unit axis is a bitcast)."""
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    assert layer is not None, "stacked pools need their layer index"
    return k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1)


def paged_decode(q, k_pages, v_pages, page_map, pos, *, window: int = 0,
                 live=None, layer=None, pages_per_block: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Paged single-token attention.

    q: (B, K, G, hd); k_pages/v_pages: stacked (layers, num_pages,
    page_size, K, hd) pools read at layer ``layer`` (an int or traced
    int32 scalar), or one layer's (num_pages, page_size, K, hd) pool,
    read as a stack of one (a leading unit axis is a bitcast);
    page_map: (B, pages_per_slot) int32; pos: (B,) int32; live: (B,)
    bool or None (every lane live) — in the block walk a lane that is
    not live walks no page and returns zeros; its output is meant to be
    discarded (the page grid ignores ``live``). ``pages_per_block``
    overrides the block
    size :func:`block_pages` derives from the shapes (tests force
    small blocks). Returns the softmax-weighted values (B, K, G, hd) in
    fp32 (caller projects).
    """
    interpret = resolve_interpret(interpret)
    window = int(window)
    if page_grid(q.shape[-1], interpret):
        return _page_grid_decode(q, k_pages, v_pages, page_map, pos,
                                 window, interpret, layer)
    k_pages, v_pages, layer = _as_stack(k_pages, v_pages, layer)
    B, K, G, hd = q.shape
    ps = k_pages.shape[2]
    P = page_map.shape[1]
    ppb = pages_per_block or block_pages(
        ps, P, K, hd, jnp.dtype(k_pages.dtype).itemsize, window)
    live = (jnp.ones((B,), jnp.int32) if live is None
            else jnp.asarray(live).astype(jnp.int32).reshape(B))
    kern = functools.partial(_paged_decode_kernel, page_size=ps,
                             pages_per_slot=P, pages_per_block=ppb,
                             window=window, group=G)
    whole = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,             # page_map, pos, live, layer
        grid=(),
        in_specs=[whole(), pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole(),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, K, hd), k_pages.dtype),
            pltpu.VMEM((2, ppb, ps, K, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),         # (K|V, buffer)
            pltpu.SMEM((3, B), jnp.int32),           # first, count, next
            pltpu.VMEM((K * G, 1), jnp.float32),
            pltpu.VMEM((K * G, 1), jnp.float32),
            pltpu.VMEM((K * G, hd), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K * G, hd), jnp.float32),
        interpret=interpret, name="paged_decode")
    out = fn(page_map.astype(jnp.int32), pos.astype(jnp.int32), live,
             layer, q.astype(jnp.float32).reshape(B, K * G, hd), k_pages,
             v_pages)
    return out.reshape(B, K, G, hd)


__all__ = ["block_band", "block_pages", "page_grid", "paged_decode"]
