"""Attention for the model zoo: GQA/MQA/MHA with RoPE, QKV bias,
causal / sliding-window / prefix-LM masks, cross-attention, KV caches.

Two execution paths, chosen by sequence length:

* ``simple``: materialize (B, H, Tq, Tk) scores — tests & short seqs.
* ``flash``: scan over query/key chunks with online softmax — compiles
  to compact HLO (scan) and keeps live memory at (B, H, qc, kc) per
  step, which is what lets 4k-32k contexts lower on the 256-chip mesh
  without a T^2 buffer. This is the jnp reference of a TPU flash
  kernel; FLOPs are identical.

Masks are expressed by (mode, window, prefix_len) so the flash path can
apply them per chunk without building a (Tq, Tk) bool tensor.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.common import Px, dense_init, zeros_init, rope
from repro.models.layout import read, read_pages, write_rows

NEG_INF = -1e30


class AttnParams(NamedTuple):
    pass  # params are plain dicts; kept for documentation


def init_attention(key, cfg, d_model: int | None = None,
                   cross: bool = False) -> dict:
    d = d_model or cfg.d_model
    hd, H, K = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 5)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), ("embed", "q_proj")),
        "wk": dense_init(ks[1], (d, K * hd), ("embed", "kv_proj")),
        "wv": dense_init(ks[2], (d, K * hd), ("embed", "kv_proj")),
        "wo": dense_init(ks[3], (H * hd, d), ("q_proj", "embed"),
                         scale=1.0, fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((H * hd,), ("q_proj",))
        p["bk"] = zeros_init((K * hd,), ("kv_proj",))
        p["bv"] = zeros_init((K * hd,), ("kv_proj",))
    return p


# ---------------------------------------------------------------------------
# mask logic (chunk-local evaluation)
# ---------------------------------------------------------------------------

def _mask_block(q_pos, k_pos, mode: str, window: int, prefix_len):
    """Boolean keep-mask for a (qc, kc) tile given absolute positions.

    mode: 'causal' | 'sliding' | 'prefix' | 'full'
    """
    q = q_pos[:, None]
    k = k_pos[None, :]
    if mode == "full":
        return jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    causal = k <= q
    if mode == "causal":
        return causal
    if mode == "sliding":
        return causal & (k > q - window)
    if mode == "prefix":
        # bidirectional inside the prefix, causal after
        both_prefix = (q < prefix_len) & (k < prefix_len)
        return causal | both_prefix
    raise ValueError(mode)


# ---------------------------------------------------------------------------
# core attention computations
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q: (B, Tq, K, G, hd), k: (B, Tk, K, hd) -> (B, K, G, Tq, Tk)."""
    return jnp.einsum("btkgh,bskh->bkgts", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(w, v):
    """w: (B, K, G, Tq, Tk), v: (B, Tk, K, hd) -> (B, Tq, K, G, hd)."""
    return jnp.einsum("bkgts,bskh->btkgh", w, v,
                      preferred_element_type=jnp.float32)


def simple_attention(q, k, v, *, mode="causal", window=0, prefix_len=None,
                     q_offset=0, k_len: jax.Array | None = None):
    """Materialized attention. q: (B,Tq,K,G,hd), k/v: (B,Tk,K,hd)."""
    B, Tq = q.shape[0], q.shape[1]
    Tk = k.shape[1]
    scale = q.shape[-1] ** -0.5
    scores = _gqa_scores(q * scale, k)              # (B,K,G,Tq,Tk) f32
    q_pos = q_offset + jnp.arange(Tq)
    k_pos = jnp.arange(Tk)
    keep = _mask_block(q_pos, k_pos, mode, window,
                       prefix_len if prefix_len is not None else 0)
    if k_len is not None:                            # cache validity limit
        keep = keep & (k_pos[None, :] < k_len)
    scores = jnp.where(keep[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(w, v)
    return out.astype(q.dtype)


def flash_attention(q, k, v, *, mode="causal", window=0, prefix_len=None,
                    q_offset=0, q_chunk=512, k_chunk=1024, k_len=None):
    """Chunked online-softmax attention with a flash-style custom VJP.

    q: (B, Tq, K, G, hd); k, v: (B, Tk, K, hd). Tq % q_chunk == 0 and
    Tk % k_chunk == 0 (caller pads; ``k_len`` masks the key padding).

    The backward pass recomputes score blocks (never materializing more
    than a (q_chunk, k_chunk) tile per step) — residuals are O(T), which
    is what lets 4k-32k training contexts fit the dry-run memory budget.
    """
    return _flash(q, k, v, mode, window, prefix_len, q_offset, q_chunk,
                  k_chunk, k_len)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, mode, window, prefix_len, q_offset, q_chunk, k_chunk,
           k_len):
    out, _ = _flash_fwd(q, k, v, mode, window, prefix_len, q_offset,
                        q_chunk, k_chunk, k_len)
    return out


def _flash_fwd(q, k, v, mode, window, prefix_len, q_offset, q_chunk,
               k_chunk, k_len):
    B, Tq, K, G, hd = q.shape
    Tk = k.shape[1]
    assert Tq % q_chunk == 0 and Tk % k_chunk == 0, (Tq, Tk)
    nq, nk = Tq // q_chunk, Tk // k_chunk
    scale = hd ** -0.5
    pl_ = prefix_len if prefix_len is not None else 0

    qc = q.reshape(B, nq, q_chunk, K, G, hd)
    kc = k.reshape(B, nk, k_chunk, K, hd)
    vc = v.reshape(B, nk, k_chunk, K, hd)

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx
        q_pos = q_offset + iq * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kv_and_idx):
            acc, m, l = carry
            (ki, vi), ik = kv_and_idx
            k_pos = ik * k_chunk + jnp.arange(k_chunk)
            s = _gqa_scores(qi * scale, ki)          # (B,K,G,qc,kc) f32
            keep = _mask_block(q_pos, k_pos, mode, window, pl_)
            if k_len is not None:
                keep = keep & (k_pos[None, :] < k_len)
            s = jnp.where(keep[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgts,bskh->bkgth", p, vi,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, K, G, q_chunk, hd), jnp.float32)
        m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            ((jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)),
             jnp.arange(nk)))
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe[..., None]
        lse = m + jnp.log(l_safe)                    # (B,K,G,qc)
        return None, (out.astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_step, None,
                                   (jnp.moveaxis(qc, 1, 0), jnp.arange(nq)))
    # outs: (nq, B, K, G, qc, hd) -> (B, Tq, K, G, hd)
    out = jnp.transpose(outs, (1, 0, 4, 2, 3, 5)).reshape(B, Tq, K, G, hd)
    lse = jnp.transpose(lses, (1, 0, 4, 2, 3)).reshape(B, Tq, K, G)
    return out, lse


def _flash_fwd_vjp(q, k, v, mode, window, prefix_len, q_offset, q_chunk,
                   k_chunk, k_len):
    out, lse = _flash_fwd(q, k, v, mode, window, prefix_len, q_offset,
                          q_chunk, k_chunk, k_len)
    return out, (q, k, v, out, lse)


def _flash_bwd(mode, window, prefix_len, q_offset, q_chunk, k_chunk, k_len,
               res, dout):
    q, k, v, out, lse = res
    B, Tq, K, G, hd = q.shape
    Tk = k.shape[1]
    nq, nk = Tq // q_chunk, Tk // k_chunk
    scale = hd ** -0.5
    pl_ = prefix_len if prefix_len is not None else 0

    # delta = rowsum(dout * out)  (B, Tq, K, G)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    qc = jnp.moveaxis(q.reshape(B, nq, q_chunk, K, G, hd), 1, 0)
    doc = jnp.moveaxis(dout.reshape(B, nq, q_chunk, K, G, hd), 1, 0)
    lsec = jnp.moveaxis(lse.reshape(B, nq, q_chunk, K, G), 1, 0)
    deltac = jnp.moveaxis(delta.reshape(B, nq, q_chunk, K, G), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nk, k_chunk, K, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, k_chunk, K, hd), 1, 0)

    def kv_step(dq_acc, kv_and_idx):
        (ki, vi), ik = kv_and_idx
        k_pos = ik * k_chunk + jnp.arange(k_chunk)

        def q_step(carry_q, q_and_idx):
            dki, dvi = carry_q
            (qi, doi, lsei, deli), iq = q_and_idx
            # qi/doi: (B, qc, K, G, hd); lsei/deli: (B, qc, K, G)
            q_pos = q_offset + iq * q_chunk + jnp.arange(q_chunk)
            s = _gqa_scores(qi * scale, ki)            # (B,K,G,qc,kc)
            keep = _mask_block(q_pos, k_pos, mode, window, pl_)
            if k_len is not None:
                keep = keep & (k_pos[None, :] < k_len)
            s = jnp.where(keep[None, None, None], s, NEG_INF)
            lse_a = jnp.transpose(lsei, (0, 2, 3, 1))   # (B,K,G,qc)
            del_a = jnp.transpose(deli, (0, 2, 3, 1))
            p = jnp.exp(s - lse_a[..., None])
            do_b = jnp.transpose(doi, (0, 2, 3, 1, 4)
                                 ).astype(jnp.float32)  # (B,K,G,qc,hd)
            dv_blk = jnp.einsum("bkgts,bkgth->bskh", p, do_b)
            dp = jnp.einsum("bkgth,bskh->bkgts", do_b,
                            vi.astype(jnp.float32))
            ds = p * (dp - del_a[..., None]) * scale
            dq_blk = jnp.einsum("bkgts,bskh->bkgth", ds,
                                ki.astype(jnp.float32))
            q_b = jnp.transpose(qi, (0, 2, 3, 1, 4)).astype(jnp.float32)
            dk_blk = jnp.einsum("bkgts,bkgth->bskh", ds, q_b)
            # -> dq tile back to (B, qc, K, G, hd)
            dq_tile = jnp.transpose(dq_blk, (0, 3, 1, 2, 4))
            return (dki + dk_blk, dvi + dv_blk), dq_tile

        (dk_i, dv_i), dq_tiles = jax.lax.scan(
            q_step,
            (jnp.zeros((B, k_chunk, K, hd), jnp.float32),
             jnp.zeros((B, k_chunk, K, hd), jnp.float32)),
            ((qc, doc, lsec, deltac), jnp.arange(nq)))
        # dq_tiles: (nq, B, qc, K, G, hd) -> (B, Tq, K, G, hd)
        dq_full = jnp.moveaxis(dq_tiles, 0, 1).reshape(B, Tq, K, G, hd)
        return dq_acc + dq_full, (dk_i, dv_i)

    dq0 = jnp.zeros((B, Tq, K, G, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, ((kc, vc), jnp.arange(nk)))
    # dks: (nk, B, kc, K, hd) -> (B, Tk, K, hd)
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Tk, K, hd).astype(k.dtype)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Tk, K, hd).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


_flash.defvjp(_flash_fwd_vjp, _flash_bwd)


# ---------------------------------------------------------------------------
# pair-scheduled flash attention (beyond-paper §Perf optimization):
# only the (q-chunk, k-chunk) pairs that can contain unmasked entries are
# computed — ~2x fewer FLOPs for causal, window/T for sliding windows —
# instead of masking a full rectangular sweep.
# ---------------------------------------------------------------------------

def _block_pairs(nq, nk, q_chunk, k_chunk, mode, window, prefix_len,
                 q_offset):
    """Static list of (iq, ik) chunk pairs with any visible entries."""
    pairs = []
    for iq in range(nq):
        q_lo = q_offset + iq * q_chunk
        q_hi = q_lo + q_chunk - 1
        for ik in range(nk):
            k_lo = ik * k_chunk
            k_hi = k_lo + k_chunk - 1
            if mode == "full":
                vis = True
            elif mode == "causal":
                vis = k_lo <= q_hi
            elif mode == "sliding":
                vis = (k_lo <= q_hi) and (k_hi > q_lo - window)
            elif mode == "prefix":
                vis = (k_lo <= q_hi) or (k_lo < (prefix_len or 0))
            else:
                raise ValueError(mode)
            if vis:
                pairs.append((iq, ik))
    return pairs


def flash_attention_pairs(q, k, v, *, mode="causal", window=0,
                          prefix_len=None, q_offset=0, q_chunk=512,
                          k_chunk=512, k_len=None):
    """Same math as :func:`flash_attention`, triangular/banded schedule.

    Scans over the static visible-pair list; accumulators for ALL query
    chunks are carried (O(Tq) memory, fp32) and renormalized once at the
    end. Custom VJP with the same pair schedule backward.
    """
    return _flash_pairs(q, k, v, mode, window, prefix_len, q_offset,
                        q_chunk, k_chunk, k_len)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_pairs(q, k, v, mode, window, prefix_len, q_offset, q_chunk,
                 k_chunk, k_len):
    out, _ = _flash_pairs_fwd(q, k, v, mode, window, prefix_len, q_offset,
                              q_chunk, k_chunk, k_len)
    return out


def _pairs_arrays(nq, nk, q_chunk, k_chunk, mode, window, prefix_len,
                  q_offset):
    import numpy as _np
    pairs = _block_pairs(nq, nk, q_chunk, k_chunk, mode, window,
                         prefix_len, q_offset)
    arr = _np.asarray(pairs, _np.int32)
    return jnp.asarray(arr[:, 0]), jnp.asarray(arr[:, 1])


def _flash_pairs_fwd(q, k, v, mode, window, prefix_len, q_offset, q_chunk,
                     k_chunk, k_len):
    B, Tq, K, G, hd = q.shape
    Tk = k.shape[1]
    assert Tq % q_chunk == 0 and Tk % k_chunk == 0, (Tq, Tk)
    nq, nk = Tq // q_chunk, Tk // k_chunk
    scale = hd ** -0.5
    pl_ = prefix_len if prefix_len is not None else 0
    iqs, iks = _pairs_arrays(nq, nk, q_chunk, k_chunk, mode, window,
                             prefix_len, q_offset)

    qb = q.reshape(B, nq, q_chunk, K, G, hd)
    kb = k.reshape(B, nk, k_chunk, K, hd)
    vb = v.reshape(B, nk, k_chunk, K, hd)

    def step(carry, pair):
        acc, m, l = carry                     # acc (B,nq,qc,K,G,hd) f32
        iq, ik = pair
        qi = jax.lax.dynamic_index_in_dim(qb, iq, 1, keepdims=False)
        ki = jax.lax.dynamic_index_in_dim(kb, ik, 1, keepdims=False)
        vi = jax.lax.dynamic_index_in_dim(vb, ik, 1, keepdims=False)
        q_pos = q_offset + iq * q_chunk + jnp.arange(q_chunk)
        k_pos = ik * k_chunk + jnp.arange(k_chunk)
        s = _gqa_scores(qi * scale, ki)       # (B,K,G,qc,kc)
        keep = _mask_block(q_pos, k_pos, mode, window, pl_)
        if k_len is not None:
            keep = keep & (k_pos[None, :] < k_len)
        s = jnp.where(keep[None, None, None], s, NEG_INF)
        m_i = jax.lax.dynamic_index_in_dim(m, iq, 1, keepdims=False)
        l_i = jax.lax.dynamic_index_in_dim(l, iq, 1, keepdims=False)
        a_i = jax.lax.dynamic_index_in_dim(acc, iq, 1, keepdims=False)
        s_t = jnp.transpose(s, (0, 3, 1, 2, 4))   # (B,qc,K,G,kc)
        m_new = jnp.maximum(m_i, s_t.max(axis=-1))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s_t - m_new[..., None])
        l_new = l_i * alpha + p.sum(axis=-1)
        upd = jnp.einsum("btkgs,bskh->btkgh", p,
                         vi.astype(jnp.float32))
        a_new = a_i * alpha[..., None] + upd
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, iq, 1)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, iq, 1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, iq, 1)
        return (acc, m, l), None

    acc0 = jnp.zeros((B, nq, q_chunk, K, G, hd), jnp.float32)
    m0 = jnp.full((B, nq, q_chunk, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nq, q_chunk, K, G), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (iqs, iks))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).reshape(B, Tq, K, G, hd).astype(q.dtype)
    lse = (m + jnp.log(l_safe)).reshape(B, Tq, K, G)
    return out, lse


def _flash_pairs_fwd_vjp(q, k, v, mode, window, prefix_len, q_offset,
                         q_chunk, k_chunk, k_len):
    out, lse = _flash_pairs_fwd(q, k, v, mode, window, prefix_len,
                                q_offset, q_chunk, k_chunk, k_len)
    return out, (q, k, v, out, lse)


def _flash_pairs_bwd(mode, window, prefix_len, q_offset, q_chunk, k_chunk,
                     k_len, res, dout):
    q, k, v, out, lse = res
    B, Tq, K, G, hd = q.shape
    Tk = k.shape[1]
    nq, nk = Tq // q_chunk, Tk // k_chunk
    scale = hd ** -0.5
    pl_ = prefix_len if prefix_len is not None else 0
    iqs, iks = _pairs_arrays(nq, nk, q_chunk, k_chunk, mode, window,
                             prefix_len, q_offset)

    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                         # (B,Tq,K,G)
    qb = q.reshape(B, nq, q_chunk, K, G, hd)
    kb = k.reshape(B, nk, k_chunk, K, hd)
    vb = v.reshape(B, nk, k_chunk, K, hd)
    dob = dout.reshape(B, nq, q_chunk, K, G, hd)
    lseb = lse.reshape(B, nq, q_chunk, K, G)
    delb = delta.reshape(B, nq, q_chunk, K, G)

    def step(carry, pair):
        dq, dk, dv = carry
        iq, ik = pair
        qi = jax.lax.dynamic_index_in_dim(qb, iq, 1, keepdims=False)
        ki = jax.lax.dynamic_index_in_dim(kb, ik, 1, keepdims=False)
        vi = jax.lax.dynamic_index_in_dim(vb, ik, 1, keepdims=False)
        doi = jax.lax.dynamic_index_in_dim(dob, iq, 1, keepdims=False)
        lsei = jax.lax.dynamic_index_in_dim(lseb, iq, 1, keepdims=False)
        deli = jax.lax.dynamic_index_in_dim(delb, iq, 1, keepdims=False)
        q_pos = q_offset + iq * q_chunk + jnp.arange(q_chunk)
        k_pos = ik * k_chunk + jnp.arange(k_chunk)
        s = _gqa_scores(qi * scale, ki)              # (B,K,G,qc,kc)
        keep = _mask_block(q_pos, k_pos, mode, window, pl_)
        if k_len is not None:
            keep = keep & (k_pos[None, :] < k_len)
        s = jnp.where(keep[None, None, None], s, NEG_INF)
        lse_a = jnp.transpose(lsei, (0, 2, 3, 1))
        del_a = jnp.transpose(deli, (0, 2, 3, 1))
        p = jnp.exp(s - lse_a[..., None])
        do_b = jnp.transpose(doi, (0, 2, 3, 1, 4)).astype(jnp.float32)
        dv_blk = jnp.einsum("bkgts,bkgth->bskh", p, do_b)
        dp = jnp.einsum("bkgth,bskh->bkgts", do_b, vi.astype(jnp.float32))
        ds = p * (dp - del_a[..., None]) * scale
        dq_blk = jnp.einsum("bkgts,bskh->bkgth", ds, ki.astype(jnp.float32))
        q_b = jnp.transpose(qi, (0, 2, 3, 1, 4)).astype(jnp.float32)
        dk_blk = jnp.einsum("bkgts,bkgth->bskh", ds, q_b)
        dq_tile = jnp.transpose(dq_blk, (0, 3, 1, 2, 4))   # (B,qc,K,G,hd)
        dq_cur = jax.lax.dynamic_index_in_dim(dq, iq, 1, keepdims=False)
        dq = jax.lax.dynamic_update_index_in_dim(dq, dq_cur + dq_tile,
                                                 iq, 1)
        dk_cur = jax.lax.dynamic_index_in_dim(dk, ik, 1, keepdims=False)
        dk = jax.lax.dynamic_update_index_in_dim(dk, dk_cur + dk_blk,
                                                 ik, 1)
        dv_cur = jax.lax.dynamic_index_in_dim(dv, ik, 1, keepdims=False)
        dv = jax.lax.dynamic_update_index_in_dim(dv, dv_cur + dv_blk,
                                                 ik, 1)
        return (dq, dk, dv), None

    dq0 = jnp.zeros((B, nq, q_chunk, K, G, hd), jnp.float32)
    dk0 = jnp.zeros((B, nk, k_chunk, K, hd), jnp.float32)
    dv0 = jnp.zeros((B, nk, k_chunk, K, hd), jnp.float32)
    (dq, dk, dv), _ = jax.lax.scan(step, (dq0, dk0, dv0), (iqs, iks))
    return (dq.reshape(B, Tq, K, G, hd).astype(q.dtype),
            dk.reshape(B, Tk, K, hd).astype(k.dtype),
            dv.reshape(B, Tk, K, hd).astype(v.dtype))


_flash_pairs.defvjp(_flash_pairs_fwd_vjp, _flash_pairs_bwd)

# global switch for the §Perf experiment (build_program flips it)
PAIR_SCHEDULE = False

import contextlib


@contextlib.contextmanager
def pair_schedule(on: bool = True):
    global PAIR_SCHEDULE
    prev = PAIR_SCHEDULE
    PAIR_SCHEDULE = on
    try:
        yield
    finally:
        PAIR_SCHEDULE = prev


# ---------------------------------------------------------------------------
# the full attention block (projections + cache handling)
# ---------------------------------------------------------------------------

def _project_q(p, cfg, x):
    B, T, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    if cfg.attention_multiplier:
        # a softmax scale other than head_dim ** -0.5, folded into q so
        # that every attention path and the paged kernel stay as they are
        q = q * jnp.asarray(cfg.attention_multiplier * cfg.head_dim ** 0.5,
                            x.dtype)
    return q.reshape(B, T, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim)


def _project_kv(p, cfg, x):
    B, T, _ = x.shape
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    return k, v


def attention_block(p, cfg, x, *, mode="causal", window=0, prefix_len=None,
                    positions=None, kv_source=None, flash_threshold=2048):
    """Self- (or cross-) attention over a full sequence (train/prefill).

    x: (B, T, d). kv_source: (B, S, d) for cross-attention.
    Returns (B, T, d).
    """
    from repro.dist.sharding import hint
    B, T, _ = x.shape
    q = _project_q(p, cfg, x)
    kv_in = x if kv_source is None else kv_source
    k, v = _project_kv(p, cfg, kv_in)
    # keep heads on the model axis when the head count divides it —
    # otherwise XLA splits head_dim and all-reduces every score block
    q = hint(q, ("pod", "data"), None, "model", None, None)
    k = hint(k, ("pod", "data"), None, "model", None)
    v = hint(v, ("pod", "data"), None, "model", None)
    if cfg.rope and kv_source is None:
        pos = positions if positions is not None else jnp.arange(T)
        q = rope(q.reshape(B, T, -1, cfg.head_dim), pos,
                 cfg.rope_theta).reshape(q.shape)
        k = rope(k, pos, cfg.rope_theta)

    Tk = k.shape[1]
    use_flash = max(T, Tk) > flash_threshold
    if use_flash:
        pair_mode = PAIR_SCHEDULE and mode in ("causal", "sliding",
                                               "prefix")
        qc = min(512, T)
        kc = qc if pair_mode else min(1024, Tk)
        # pad to chunk multiples
        pq, pk = (-T) % qc, (-Tk) % kc
        if pq:
            q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        if pk:
            k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        fa = flash_attention_pairs if pair_mode else flash_attention
        out = fa(q, k, v, mode=mode, window=window,
                 prefix_len=prefix_len, q_chunk=qc, k_chunk=kc,
                 k_len=Tk if pk else None)
        out = out[:, :T]
    else:
        out = simple_attention(q, k, v, mode=mode, window=window,
                               prefix_len=prefix_len)
    out = out.reshape(B, T, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# decode path: single-token step against a KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16):
    """Cache leaves for ONE layer (the layer axis is added by the stack).

    Ring buffer when cfg.sliding_window > 0 and cache_len > window.
    """
    K, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, cache_len, K, hd), dtype),
        "v": jnp.zeros((batch, cache_len, K, hd), dtype),
    }


def cache_logical_axes():
    return {"k": ("cache_batch", "cache_seq", "cache_kv_heads", "head_dim"),
            "v": ("cache_batch", "cache_seq", "cache_kv_heads", "head_dim")}


def init_paged_cache(cfg, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16):
    """Paged cache leaves for ONE layer: a pool of fixed-size pages
    shared by every slot (page 0 is the reserved dummy page)."""
    K, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((num_pages, page_size, K, hd), dtype),
        "v": jnp.zeros((num_pages, page_size, K, hd), dtype),
    }


def paged_cache_logical_axes():
    ax = ("cache_pages", "page_off", "cache_kv_heads", "head_dim")
    return {"k": ax, "v": ax}


def _paged_scatter(kv, k_new, v_new, flat, layer):
    """Write per-row K/V (B, K, hd) at flat page offsets (B,) into layer
    ``layer`` of the stacked (layers, num_pages, page_size, K, hd)
    pools; returns the updated pool pair. Rows routed to the dummy page
    may collide — nobody reads page 0 unmasked, so last-writer-wins is
    fine."""
    return (write_rows(kv["k"], layer, flat, k_new),
            write_rows(kv["v"], layer, flat, v_new))


def paged_decode_attention(p, cfg, x, cache, pos, page_map, *, window=0,
                           live=None, use_kernel=False, interpret=None,
                           layer):
    """One-token attention step against a PAGED cache.

    x: (B, 1, d); cache: {'k','v'} stacked (layers, num_pages,
    page_size, K, hd) pools, of which ``layer`` (an int or traced
    scalar) is this layer's;
    pos: (B,) absolute positions; page_map: (B, pages_per_slot) int32 —
    each slot's logical pages in position order (dummy page 0 for
    unallocated entries). Unlike the ring path, the paged cache stores
    FULL positions and masks a [pos-window, pos] band, so sliding archs
    match the ring outputs without wraparound arithmetic.

    Returns (out, new_cache). With ``use_kernel`` the gather+softmax
    runs in the Pallas paged-decode kernel (interpret mode off-TPU),
    which walks no page for a lane that ``live`` (B,) marks dead; the
    gather path ignores ``live``. A dead lane's output is meant to be
    discarded either way.
    """
    B = x.shape[0]
    q = _project_q(p, cfg, x)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    k_new, v_new = _project_kv(p, cfg, x)
    if cfg.rope:
        q = rope(q.reshape(B, 1, -1, cfg.head_dim), pos[:, None],
                 cfg.rope_theta).reshape(q.shape)
        k_new = rope(k_new, pos[:, None], cfg.rope_theta)
    from repro.dist.sharding import hint
    q = hint(q, ("pod", "data"), None, "model", None, None)
    k_new = hint(k_new, ("pod", "data"), None, "model", None)
    v_new = hint(v_new, ("pod", "data"), None, "model", None)

    ps = cache["k"].shape[-3]
    P = page_map.shape[1]
    # the new token's page: slots mid-prefill / retired carry an
    # all-dummy page-map row, so their write lands in the page-0 sink
    pg = jnp.take_along_axis(page_map,
                             jnp.clip(pos // ps, 0, P - 1)[:, None],
                             axis=1)[:, 0]
    flat = pg * ps + pos % ps                        # (B,)
    k_pages, v_pages = _paged_scatter(cache, k_new[:, 0], v_new[:, 0],
                                      flat, layer)

    if use_kernel:
        from repro.kernels.paged_attn import paged_decode
        out = paged_decode(q[:, 0], k_pages, v_pages, page_map, pos,
                           window=window, live=live, layer=layer,
                           interpret=interpret)
        out = out[:, None].astype(x.dtype)           # (B, 1, K, G, hd)
    else:
        kv_shape = (B, P * ps) + k_pages.shape[-2:]
        kg = read_pages(k_pages, layer, page_map).reshape(kv_shape)
        vg = read_pages(v_pages, layer, page_map).reshape(kv_shape)
        scale = cfg.head_dim ** -0.5
        s = _gqa_scores(q * scale, kg.astype(q.dtype))   # (B,K,G,1,S)
        k_pos = jnp.arange(P * ps)
        valid = k_pos[None, :] <= pos[:, None]
        if window:
            valid = valid & (k_pos[None, :] > pos[:, None] - window)
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = _gqa_out(w, vg.astype(q.dtype)).astype(x.dtype)
    out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype), {"k": k_pages, "v": v_pages}


def decode_attention(p, cfg, x, cache, pos, *, window=0,
                     kv_source_cache=None, layer=None):
    """One-token attention step.

    x: (B, 1, d); cache: {'k','v'} the stacked (layers, B, S, K, hd)
    ring, of which ``layer`` (an int or traced scalar) is this layer's
    (the new row is written there, the layer read in place);
    ``kv_source_cache`` (B, S, K, hd) is read as this layer's own and
    takes no ``layer``; pos: int32 scalar or
    ``(B,)`` vector — the absolute position of each slot's new token
    (a scalar broadcasts to all slots). Returns (out, new_cache).

    Ring-buffer semantics when window > 0 and S == window: slot =
    pos % window and all cache entries are valid once pos >= window.
    Keys are stored post-RoPE (absolute rotation).
    """
    B = x.shape[0]
    q = _project_q(p, cfg, x)

    if kv_source_cache is not None:
        # cross-attention: cache holds the (pre-projected) encoder K/V
        k, v = kv_source_cache["k"], kv_source_cache["v"]
        scale = cfg.head_dim ** -0.5
        s = _gqa_scores(q * scale, k.astype(q.dtype))
        w = jax.nn.softmax(s, axis=-1)
        out = _gqa_out(w, v.astype(q.dtype)).astype(x.dtype)
        out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
        return out @ p["wo"].astype(x.dtype), cache

    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim > 0                          # (B,) vector positions
    pos = jnp.broadcast_to(pos.reshape(-1), (B,))

    k_new, v_new = _project_kv(p, cfg, x)
    if cfg.rope:
        pos_arr = pos[:1, None] if not per_slot else pos[:, None]  # bcast B
        q = rope(q.reshape(B, 1, -1, cfg.head_dim), pos_arr,
                 cfg.rope_theta).reshape(q.shape)
        k_new = rope(k_new, pos_arr, cfg.rope_theta)
    # tensor-parallel decode: per-token projections sharded over heads
    # (shape-aware — a no-op on single device / indivisible head counts)
    from repro.dist.sharding import hint
    q = hint(q, ("pod", "data"), None, "model", None, None)
    k_new = hint(k_new, ("pod", "data"), None, "model", None)
    v_new = hint(v_new, ("pod", "data"), None, "model", None)

    S = cache["k"].shape[-3]
    slot = jnp.where(window > 0, pos % jnp.maximum(S, 1), pos)
    slot = jnp.minimum(slot, S - 1)                  # (B,)
    rows = {"k": k_new, "v": v_new}
    if per_slot:
        new = {n: cache[n].at[layer, jnp.arange(B), slot].set(
            r[:, 0].astype(cache[n].dtype)) for n, r in rows.items()}
    else:
        # aligned batch: one contiguous slice update beats a scatter
        new = {n: jax.lax.dynamic_update_slice(
            cache[n], r[None].astype(cache[n].dtype),
            (layer, 0, slot[0], 0, 0)) for n, r in rows.items()}
    k, v = read(new["k"], layer), read(new["v"], layer)

    scale = cfg.head_dim ** -0.5
    s = _gqa_scores(q * scale, k.astype(q.dtype))    # (B,K,G,1,S)
    k_pos = jnp.arange(S)
    if window > 0:
        # ring: all valid once a slot's position wraps past the window
        valid = (k_pos[None, :] <= slot[:, None]) | (pos[:, None] >= S)
    else:
        valid = k_pos[None, :] <= pos[:, None]       # (B, S)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = _gqa_out(w, v.astype(q.dtype)).astype(x.dtype)
    out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype), new
