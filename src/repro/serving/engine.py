"""Serving runtime: KV/state caches, prefill, and single-token decode
for every arch family.

Cache layout: one pytree per model with the params' layer trees
(``models/layout.py``): scanned stacks' leaves carry a leading
``layers`` axis. Prefill builds them as the scan's per-layer outputs;
decode and chunked prefill carry the stacked tree through the scan
beside the activations, each layer reading and writing only its own
rows (``layout.read``/``write``/``read_pages``/``write_rows``), so a
caller that donates the cache has it updated in place. The decode step
is a single compact HLO program regardless of depth. Every phase walks
the layout with ``layout.run`` and its own per-role layer bodies.

Sliding-window archs (and the *sliding-window serving variant* used for
``long_500k`` on full-attention archs) keep a **ring buffer** of
``window`` positions: slot = pos % window, keys stored post-RoPE
(dot-product relative property keeps scores exact). SSM / RG-LRU archs
carry O(1) recurrent state — no KV growth at all.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layout
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models import rglru as rgm
from repro.models import ssm as ssmm
from repro.models.common import apply_norm, sinusoidal_positions
from repro.models.transformer import (_embed_tokens, _unembed, hybrid_ffn,
                                      hybrid_residual)


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def effective_window(cfg, serve_window: int = 0) -> int:
    """The serving attention window: the arch's own sliding window, the
    hybrid local-attention window, or a serving-variant override."""
    if cfg.kind == "hybrid":
        return cfg.attention_window
    if cfg.sliding_window:
        return cfg.sliding_window
    return serve_window


def cache_len_for(cfg, seq_len: int, serve_window: int = 0) -> int:
    w = effective_window(cfg, serve_window)
    return min(seq_len, w) if w else seq_len


def init_cache_tree(cfg, batch: int, seq_len: int, dtype=jnp.bfloat16,
                    serve_window: int = 0, mesh=None, cache_rules=None):
    """Cache pytree for the whole model (all layers stacked).

    With ``mesh``, every leaf is placed via a ``NamedSharding`` resolved
    from its ``cache_logical_axes_tree`` logical axes under
    ``cache_rules`` (default ``serving.sharding.SERVE_CACHE_RULES`` —
    heads/experts sharded over ``model``, sequence as the fallback,
    slots over the replica axes), so the live batch starts sharded and
    every later splice/decode preserves that placement.
    """
    tree = _init_cache_tree(cfg, batch, seq_len, dtype, serve_window)
    if mesh is None:
        return tree
    from repro.serving.sharding import SERVE_CACHE_RULES
    rules = cache_rules or SERVE_CACHE_RULES
    axes = cache_logical_axes_tree(cfg)
    is_ax = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten(tree)
    flat_ax = jax.tree_util.tree_flatten(axes, is_leaf=is_ax)[0]
    assert len(flat) == len(flat_ax)
    from jax.sharding import NamedSharding
    out = [jax.device_put(l, NamedSharding(
        mesh, rules.spec_for_shape(tuple(ax), tuple(l.shape), mesh)))
        for l, ax in zip(flat, flat_ax)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _stack_cache(body, _key, n):
    """``n`` copies of one body's cache on a leading ``layers`` axis."""
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), body(None))


def _stack_axes(body, _key, n):
    return jax.tree.map(lambda a: ("layers",) + tuple(a), body(None),
                        is_leaf=lambda x: isinstance(x, tuple))


def _cache_maker(cfg, batch, dtype, attn_cache):
    """``make(role, key)`` of a cache tree: per-slot recurrent state for
    the recurrent roles, ``attn_cache(role)`` for the attention ones."""
    def make(role, _key):
        if role in ("ssm", "mamba"):
            return ssmm.init_ssm_cache(cfg, batch, dtype)
        if role == "rec":
            return rgm.init_rglru_cache(cfg, batch, dtype)
        return attn_cache(role)
    return make


def _axes_maker(cfg, attn_axes):
    """The logical axes of :func:`_cache_maker`'s trees."""
    def make(role, _key):
        if role in ("ssm", "mamba"):
            return ssmm.ssm_cache_logical_axes(cfg)
        if role == "rec":
            return rgm.rglru_cache_logical_axes(cfg)
        return attn_axes(role)
    return make


def _init_cache_tree(cfg, batch: int, seq_len: int, dtype=jnp.bfloat16,
                     serve_window: int = 0):
    S = cache_len_for(cfg, seq_len, serve_window)

    def attn_cache(role):
        c = attn.init_cache(cfg, batch, S, dtype)
        if role == "cross":
            K, hd = cfg.num_kv_heads, cfg.head_dim
            c["cross_k"] = jnp.zeros((batch, cfg.enc_seq_len, K, hd), dtype)
            c["cross_v"] = jnp.zeros((batch, cfg.enc_seq_len, K, hd), dtype)
        return c
    return layout.build(layout.layer_layout(cfg),
                        _cache_maker(cfg, batch, dtype, attn_cache),
                        _stack_cache)


def cache_logical_axes_tree(cfg, long_context: bool = False):
    """Logical axes matching init_cache_tree's structure."""
    def attn_axes(role):
        d = attn.cache_logical_axes()
        if role == "cross":
            d["cross_k"] = ("cache_batch", None, "cache_kv_heads", "head_dim")
            d["cross_v"] = ("cache_batch", None, "cache_kv_heads", "head_dim")
        return d
    return layout.build(layout.layer_layout(cfg),
                        _axes_maker(cfg, attn_axes), _stack_axes)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _ring_fill(k_all, v_all, S, dtype, lengths=None):
    """Place the last S tokens of (B, T, K, hd) into ring slots t % S.

    With per-request ``lengths`` (B,), each row i keeps the last S of its
    own ``lengths[i]`` valid (right-aligned) tokens; ring slots that no
    valid token maps to are zeroed, so padded prefixes never enter the
    cache.
    """
    T = k_all.shape[1]
    if lengths is None:
        if T <= S:
            pad = S - T
            k = jnp.pad(k_all, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v_all, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return k.astype(dtype), v.astype(dtype)
        idx = T - S + jnp.arange(S)
        slots = idx % S
        k = jnp.zeros((k_all.shape[0], S) + k_all.shape[2:], dtype)
        v = jnp.zeros_like(k)
        k = k.at[:, slots].set(k_all[:, idx].astype(dtype))
        v = v.at[:, slots].set(v_all[:, idx].astype(dtype))
        return k, v
    # largest valid token index t with t ≡ s (mod S), per row
    s = jnp.arange(S)[None, :]                              # (1, S)
    t = s + S * ((lengths[:, None] - 1 - s) // S)           # (B, S)
    valid = t >= 0
    idx = jnp.clip(t, 0, T - 1)[..., None, None]
    k = jnp.where(valid[..., None, None],
                  jnp.take_along_axis(k_all, idx, axis=1), 0)
    v = jnp.where(valid[..., None, None],
                  jnp.take_along_axis(v_all, idx, axis=1), 0)
    return k.astype(dtype), v.astype(dtype)


def _conv_state_at(x_pre, lengths, K):
    """Per-row causal-conv trailing context at position ``lengths``.

    x_pre: (B, T, D) pre-activation conv inputs; returns (B, K-1, D) —
    row i holds inputs lengths[i]-K+1 .. lengths[i]-1, zero-padded on
    the left exactly like a fresh causal conv.
    """
    if K <= 1:
        return jnp.zeros_like(x_pre[:, :0])
    xp = jnp.concatenate([jnp.zeros_like(x_pre[:, : K - 1]), x_pre], axis=1)
    idx = lengths[:, None] + jnp.arange(K - 1)[None, :]     # (B, K-1)
    return jnp.take_along_axis(xp, idx[..., None], axis=1)


def _prefill_attn_layer(lp, cfg, x, *, mode, window, S, cache_dtype,
                        enc_out=None, prefix_len=None, lengths=None):
    """Dense-family layer forward that also emits its KV cache slice."""
    T = x.shape[1]
    out, cache = _prefill_attn_mixer(
        lp["attn"], cfg, apply_norm(cfg, lp["ln_attn"], x), mode=mode,
        window=window, S=S, cache_dtype=cache_dtype, prefix_len=prefix_len,
        lengths=lengths)
    x = x + out

    if enc_out is not None and "cross" in lp:
        h = apply_norm(cfg, lp["ln_cross"], x)
        h = attn.attention_block(lp["cross"], cfg, h, mode="full",
                                 kv_source=enc_out)
        x = x + h

    h = apply_norm(cfg, lp["ln_mlp"], x)
    if "moe" in lp:
        # pad tokens must not consume expert capacity or skew routing
        tmask = None if lengths is None else \
            jnp.arange(T)[None, :] < lengths[:, None]
        h, _ = moem.apply_moe(lp["moe"], cfg, h, token_mask=tmask)
    else:
        h = mlpm.apply_mlp(lp["mlp"], cfg, h)
    x = x + h

    if enc_out is not None and "cross" in lp:
        ek, ev = attn._project_kv(lp["cross"], cfg, enc_out)
        cache["cross_k"] = ek.astype(cache_dtype)
        cache["cross_v"] = ev.astype(cache_dtype)
    return x, cache


def _prefill_attn_mixer(pa, cfg, h, *, mode, window, S, cache_dtype,
                        prefix_len=None, lengths=None):
    """The self-attention of ``_prefill_attn_layer`` on its normed input
    h: returns (output projection, ring-cache K/V)."""
    from repro.models.common import rope as rope_fn
    B, T, _ = h.shape
    # projections (duplicated from attention_block to capture K/V)
    from repro.dist.sharding import hint
    q = attn._project_q(pa, cfg, h)
    k, v = attn._project_kv(pa, cfg, h)
    q = hint(q, ("pod", "data"), None, "model", None, None)
    k = hint(k, ("pod", "data"), None, "model", None)
    v = hint(v, ("pod", "data"), None, "model", None)
    if cfg.rope:
        pos = jnp.arange(T)
        q = rope_fn(q.reshape(B, T, -1, cfg.head_dim), pos,
                    cfg.rope_theta).reshape(q.shape)
        k = rope_fn(k, pos, cfg.rope_theta)
    # pin the flash inputs AFTER rope: otherwise the cache output's
    # seq-sharding propagates backwards and every flash q-step
    # all-gathers the whole K/V (HC2 in EXPERIMENTS.md §Perf)
    q = hint(q, ("pod", "data"), None, "model", None, None)
    k = hint(k, ("pod", "data"), None, "model", None)
    v = hint(v, ("pod", "data"), None, "model", None)
    use_flash = T > 2048
    if use_flash:
        pair_mode = attn.PAIR_SCHEDULE and mode in ("causal", "sliding",
                                                    "prefix")
        qc = min(512, T)
        kc = qc if pair_mode else min(1024, T)
        pq, pk = (-T) % qc, (-T) % kc
        qq = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0))) if pq else q
        kk = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
        vv = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
        fa = attn.flash_attention_pairs if pair_mode else attn.flash_attention
        out = fa(qq, kk, vv, mode=mode, window=window,
                 prefix_len=prefix_len, q_chunk=qc,
                 k_chunk=kc, k_len=T if pk else None)[:, :T]
    else:
        out = attn.simple_attention(q, k, v, mode=mode, window=window,
                                    prefix_len=prefix_len)
    out = out.reshape(B, T, cfg.num_heads * cfg.head_dim)
    ck, cv = _ring_fill(k, v, S, cache_dtype, lengths)
    return out @ pa["wo"].astype(h.dtype), {"k": ck, "v": cv}


def _prefill_ssm_layer(lp, cfg, x, lengths=None):
    return _prefill_ssm_mixer(lp["ssm"], cfg, apply_norm(cfg, lp["ln"], x),
                              lengths, residual=lambda y: x + y)


def _prefill_ssm_mixer(ps, cfg, h, lengths, *, residual):
    """The Mamba-2 block of ``_prefill_ssm_layer`` on its normed input h:
    returns (``residual(output projection)``, its per-row state)."""
    b, T, d = h.shape
    d_in, H, P, S = ssmm._dims(cfg)
    proj = h @ ps["w_in"].astype(h.dtype)
    z, xs, Bm, Cm, dt_raw = ssmm._split_proj(cfg, proj)
    xs_pre, Bm_pre, Cm_pre = xs, Bm, Cm
    (xs, Bm, Cm), (cx, cB, cC) = ssmm.conv3(ps, xs, Bm, Cm)
    xs, Bm, Cm = jax.nn.silu(xs), jax.nn.silu(Bm), jax.nn.silu(Cm)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + ps["dt_bias"].astype(jnp.float32))
    if lengths is not None:
        # dt = 0 on padded steps freezes the recurrence (decay exp(0)=1,
        # input contribution dt·B·x = 0) so h_fin is each row's state at
        # its own last valid token — exactly like the zero-padding
        # ssd_chunked itself applies for chunk alignment
        keep = (jnp.arange(T)[None, :] < lengths[:, None])[..., None]
        dt = jnp.where(keep, dt, 0.0)
        K = cfg.ssm_conv_width
        cx = _conv_state_at(xs_pre, lengths, K).astype(cx.dtype)
        cB = _conv_state_at(Bm_pre, lengths, K).astype(cB.dtype)
        cC = _conv_state_at(Cm_pre, lengths, K).astype(cC.dtype)
    A = -jnp.exp(ps["A_log"].astype(jnp.float32))
    y, h_fin = ssmm.ssd_chunked(xs.reshape(b, T, H, P), dt, dt * A, Bm, Cm,
                                chunk=cfg.ssm_chunk)
    y = y + xs.reshape(b, T, H, P) * ps["D"].astype(
        h.dtype)[None, None, :, None]
    y = ssmm.gate(ps, cfg, y.reshape(b, T, d_in), z)
    x = residual(y @ ps["w_out"].astype(h.dtype))
    # conv caches hold the last (K-1) *pre-activation* inputs
    cache = {"h": h_fin, "conv_x": cx, "conv_B": cB, "conv_C": cC}
    return x, cache


def _prefill_rec_layer(lp, cfg, x, lengths=None):
    dt = x.dtype
    h = apply_norm(cfg, lp["ln_rec"], x)
    ga = jax.nn.gelu(h @ lp["rec"]["w_gelu"].astype(dt), approximate=True)
    xb = h @ lp["rec"]["w_rec"].astype(dt)
    xb_pre = xb
    xb, conv_state = rgm._causal_conv(xb, lp["rec"]["conv"])
    a, beta = rgm._gates(lp["rec"], xb)
    b = beta * xb.astype(jnp.float32)

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2

    _, hs = jax.lax.associative_scan(combine, (a, b), axis=1)
    y = (ga.astype(jnp.float32) * hs).astype(dt)
    x = x + y @ lp["rec"]["w_out"].astype(dt)
    x = x + mlpm.apply_mlp(lp["mlp"], cfg,
                           apply_norm(cfg, lp["ln_mlp"], x))
    if lengths is None:
        cache = {"h": hs[:, -1], "conv": conv_state}
    else:
        # per-row recurrent state at each row's own last valid token
        last = jnp.clip(lengths - 1, 0)[:, None, None]
        h_last = jnp.take_along_axis(hs, last, axis=1)[:, 0]
        conv = _conv_state_at(xb_pre, lengths, cfg.rglru_conv_width)
        cache = {"h": h_last, "conv": conv.astype(conv_state.dtype)}
    return x, cache


def prefill(p, cfg, batch, *, dtype=jnp.bfloat16, cache_dtype=jnp.bfloat16,
            serve_window: int = 0, remat: bool = True,
            cache_len: int | None = None, lengths=None):
    """Process the full prompt; return (last-token logits, cache, pos).

    batch: {"tokens": (B, T)} + frontend extras (patches/frames).
    ``cache_len``: total cache capacity to allocate (>= prompt length;
    defaults to the prompt length — pass the generation horizon).

    ``lengths``: optional (B,) int32 per-request prompt lengths for
    mixed-length batches. Prompts must then be RIGHT-padded (tokens
    [0, lengths[i]) real, the rest pad): real queries never attend to
    pad keys under the causal/sliding/prefix masks because every pad
    position sorts after them, recurrent state is frozen at each row's
    own last valid token, and pad positions never enter the KV cache.
    The returned logits are taken at each row's last valid token and
    ``pos`` is a per-slot (B,) vector (scalar when ``lengths`` is None).
    """
    kind = cfg.kind
    tokens = batch["tokens"]
    B, T = tokens.shape
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    x = _embed_tokens(p, cfg, tokens, dtype)
    mode, window = "causal", 0
    if cfg.sliding_window:
        mode, window = "sliding", cfg.sliding_window
    elif serve_window and kind not in ("ssm", "hybrid"):
        mode, window = "sliding", serve_window

    prefix = None
    enc_out = None
    if kind == "vlm":
        patches = batch["patches"].astype(dtype)
        x = jnp.concatenate([patches, x], axis=1)
        mode, window, prefix = "prefix", 0, cfg.enc_seq_len
    if kind in ("encdec", "audio"):
        frames = batch["frames"].astype(dtype)
        pos_e = sinusoidal_positions(frames.shape[1],
                                     cfg.d_model).astype(dtype)
        h = frames + pos_e[None]
        def enc_body(lp, hh):
            y = attn.attention_block(lp["attn"], cfg,
                                     apply_norm(cfg, lp["ln_attn"], hh),
                                     mode="full")
            hh = hh + y
            hh = hh + mlpm.apply_mlp(lp["mlp"], cfg,
                                     apply_norm(cfg, lp["ln_mlp"], hh))
            return hh, None
        h, _ = layout.run(layout.encoder_layout(cfg), p, h,
                          {"enc": enc_body})
        enc_out = apply_norm(cfg, p["enc_ln_final"], h)
        if not cfg.rope:
            dpos = sinusoidal_positions(T, cfg.d_model).astype(dtype)
            x = x + dpos[None]

    S = cache_len_for(cfg, max(cache_len or 0, x.shape[1]), serve_window)

    # valid length of the concatenated sequence (vlm prefixes count)
    lens_x = None
    if lengths is not None:
        lens_x = lengths + (cfg.enc_seq_len if kind == "vlm" else 0)

    def attn_body(lp, xx):
        return _prefill_attn_layer(
            lp, cfg, xx, mode=mode, window=window, S=S,
            cache_dtype=cache_dtype, prefix_len=prefix, lengths=lens_x)

    def local_attn_body(lp, xx):
        return _prefill_attn_layer(
            lp, cfg, xx, mode="sliding", window=cfg.attention_window, S=S,
            cache_dtype=cache_dtype, lengths=lens_x)

    def cross_body(lp, xx):
        return _prefill_attn_layer(lp, cfg, xx, mode="causal", window=0,
                                   S=S, cache_dtype=cache_dtype,
                                   enc_out=enc_out, lengths=lens_x)

    def mamba_body(lp, xx):
        with jax.named_scope("ssm_mixer"):
            xx, c = _prefill_ssm_mixer(
                lp["ssm"], cfg, apply_norm(cfg, lp["ln"], xx), lens_x,
                residual=functools.partial(hybrid_residual, cfg, xx))
        return hybrid_ffn(lp, cfg, xx), c

    def attention_body(lp, xx):
        with jax.named_scope("attn_mixer"):
            y, c = _prefill_attn_mixer(
                lp["attn"], cfg, apply_norm(cfg, lp["ln_attn"], xx),
                mode=mode, window=window, S=S, cache_dtype=cache_dtype,
                lengths=lens_x)
        return hybrid_ffn(lp, cfg, hybrid_residual(cfg, xx, y)), c

    bodies = {
        "attn": attn_body, "moe": attn_body, "local_attn": local_attn_body,
        "cross": cross_body,
        "ssm": lambda lp, xx: _prefill_ssm_layer(lp, cfg, xx, lens_x),
        "rec": lambda lp, xx: _prefill_rec_layer(lp, cfg, xx, lens_x),
        "mamba": mamba_body, "attention": attention_body,
    }
    x, cache = layout.run(layout.layer_layout(cfg), p, x, bodies,
                          remat=remat)

    x = apply_norm(cfg, p["ln_final"], x)
    if lens_x is None:
        logits = _unembed(p, cfg, x[:, -1:])
        total = T + (cfg.enc_seq_len if kind == "vlm" else 0)
        return logits, cache, jnp.asarray(total, jnp.int32)
    # per-slot: logits at each row's last valid token, (B,) positions
    last = jnp.clip(lens_x - 1, 0)[:, None, None]
    x_last = jnp.take_along_axis(x, last, axis=1)           # (B, 1, d)
    logits = _unembed(p, cfg, x_last)
    return logits, cache, lens_x


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def decode_step(p, cfg, token, cache, pos, *, dtype=jnp.bfloat16,
                serve_window: int = 0):
    """One-token generation step.

    token: (B, 1) int32; cache: tree from init_cache_tree/prefill;
    pos: int32 absolute position — a scalar (all slots aligned) or a
    ``(B,)`` vector of per-slot positions (continuous batching).
    Returns (logits, new_cache).
    """
    kind = cfg.kind
    B = token.shape[0]
    pos = jnp.asarray(pos, jnp.int32)   # scalar or (B,): rank picks the
    x = _embed_tokens(p, cfg, token, dtype)   # aligned vs per-slot path
    if kind in ("encdec", "audio") and not cfg.rope:
        # sinusoidal decoder position for each slot's current step
        d = cfg.d_model
        half = d // 2
        freq = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half)
                       / max(half - 1, 1))
        pos_b = jnp.broadcast_to(pos.reshape(-1), (B,))
        ang = pos_b.astype(jnp.float32)[:, None] * freq     # (B, half)
        dpos = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)],
                               axis=-1)[:, None]            # (B, 1, d)
        x = x + dpos.astype(dtype)

    w = effective_window(cfg, serve_window)

    def attn_decode(lp, xx, c, i, *, cross=False):
        h = apply_norm(cfg, lp["ln_attn"], xx)
        ring = w if (c["k"].shape[-3] == w and w) else 0
        out, c_new = attn.decode_attention(lp["attn"], cfg, h,
                                           {"k": c["k"], "v": c["v"]},
                                           pos, window=ring, layer=i)
        xx = xx + out
        if cross and "cross" in lp:
            h = apply_norm(cfg, lp["ln_cross"], xx)
            kv = layout.read({"k": c["cross_k"], "v": c["cross_v"]}, i)
            out, _ = attn.decode_attention(lp["cross"], cfg, h, {},
                                           pos, kv_source_cache=kv)
            xx = xx + out
        h = apply_norm(cfg, lp["ln_mlp"], xx)
        if "moe" in lp:
            h, _ = moem.apply_moe(lp["moe"], cfg, h)
        else:
            h = mlpm.apply_mlp(lp["mlp"], cfg, h)
        new = dict(c)
        new["k"], new["v"] = c_new["k"], c_new["v"]
        return xx + h, new

    def ssm_decode(lp, xx, c, i):
        h = apply_norm(cfg, lp["ln"], xx)
        y, c_new = ssmm.decode_ssm(lp["ssm"], cfg, h, layout.read(c, i))
        return xx + y, layout.write(c, i, c_new)

    def rec_decode(lp, xx, c, i):
        h = apply_norm(cfg, lp["ln_rec"], xx)
        y, c_new = rgm.decode_rglru(lp["rec"], cfg, h, layout.read(c, i))
        xx = xx + y
        xx = xx + mlpm.apply_mlp(lp["mlp"], cfg,
                                 apply_norm(cfg, lp["ln_mlp"], xx))
        return xx, layout.write(c, i, c_new)

    def mamba_dec(lp, xx, c, i):
        with jax.named_scope("ssm_mixer"):
            y, c_new = ssmm.decode_ssm(
                lp["ssm"], cfg, apply_norm(cfg, lp["ln"], xx),
                layout.read(c, i))
            c = layout.write(c, i, c_new)
        return hybrid_ffn(lp, cfg, hybrid_residual(cfg, xx, y)), c

    def attention_dec(lp, xx, c, i):
        with jax.named_scope("attn_mixer"):
            ring = w if (c["k"].shape[-3] == w and w) else 0
            y, c = attn.decode_attention(
                lp["attn"], cfg, apply_norm(cfg, lp["ln_attn"], xx),
                c, pos, window=ring, layer=i)
        return hybrid_ffn(lp, cfg, hybrid_residual(cfg, xx, y)), c

    bodies = {
        "attn": attn_decode, "moe": attn_decode, "local_attn": attn_decode,
        "cross": functools.partial(attn_decode, cross=True),
        "ssm": ssm_decode, "rec": rec_decode,
        "mamba": mamba_dec, "attention": attention_dec,
    }
    x, new_cache = layout.run(layout.layer_layout(cfg), p, x, bodies,
                              cache=cache)

    x = apply_norm(cfg, p["ln_final"], x)
    logits = _unembed(p, cfg, x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# slot-indexed cache writes (continuous batching)
# ---------------------------------------------------------------------------

def write_cache_slot(cfg, cache, one_cache, slot, *, pos=None,
                     one_pos=None, cache_rules=None):
    """Write a single-request cache into slot ``slot`` of a live batch.

    ``one_cache`` comes from a batch-1 :func:`prefill` with the same
    ``cache_len``/``serve_window`` as the live ``cache`` — every leaf is
    inserted along its ``cache_batch`` axis (located via the logical-axes
    tree, so SSM state / conv context / cross-KV leaves, whose batch
    axis sits at different ranks, all route correctly) with
    ``jax.lax.dynamic_update_slice``: ``slot`` may be traced, keeping
    one jit signature for the process lifetime.

    With ``cache_rules`` and an active mesh, every spliced leaf is
    re-pinned to the sharding its logical axes resolve to — the splice
    PRESERVES leaf shardings (the batch-1 source is resharded into the
    live layout; the live cache never moves).

    Optionally also splices ``one_pos`` (scalar or (1,)) into the
    per-slot ``pos`` vector. Returns ``new_cache`` (and ``new_pos``
    when ``pos`` is given).
    """
    from repro.dist.sharding import _ambient_mesh
    axes = cache_logical_axes_tree(cfg)
    flat_dst, treedef = jax.tree_util.tree_flatten(cache)
    flat_src = jax.tree_util.tree_flatten(one_cache)[0]
    flat_ax = jax.tree_util.tree_flatten(
        axes, is_leaf=lambda x: isinstance(x, tuple))[0]
    assert len(flat_dst) == len(flat_src) == len(flat_ax)
    mesh = _ambient_mesh() if cache_rules is not None else None
    slot = jnp.asarray(slot, jnp.int32)
    out = []
    for dst, src, ax in zip(flat_dst, flat_src, flat_ax):
        b = ax.index("cache_batch")
        start = [jnp.zeros((), jnp.int32)] * dst.ndim
        start[b] = slot
        new = jax.lax.dynamic_update_slice(
            dst, src.astype(dst.dtype), tuple(start))
        if mesh is not None:
            from jax.sharding import NamedSharding
            new = jax.lax.with_sharding_constraint(
                new, NamedSharding(mesh, cache_rules.spec_for_shape(
                    tuple(ax), tuple(new.shape), mesh)))
        out.append(new)
    new_cache = jax.tree_util.tree_unflatten(treedef, out)
    if pos is None:
        return new_cache
    one_pos = jnp.asarray(one_pos, jnp.int32).reshape(())
    new_pos = pos.at[slot].set(one_pos)
    return new_cache, new_pos


# ---------------------------------------------------------------------------
# paged cache (DESIGN.md §15): attention K/V in a shared page pool,
# recurrent state per-slot; chunked prefill + page-map decode
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# matmul operands: a bf16 copy of the stacked layers' matmul weights
# ---------------------------------------------------------------------------

# leaves that the layer code uses only as a matmul's right operand,
# ``x @ w.astype(x.dtype)`` (or the experts' einsums)
OPERAND_WEIGHTS = frozenset({"wq", "wk", "wv", "wo", "w_up", "w_down",
                             "w_gate", "w_in", "w_out", "w_gelu", "w_rec"})


def map_operands(cfg, fn, params):
    """``params`` with ``fn`` applied to the matmul weights of the
    stacked layer trees (those ``lax.scan`` consumes; unrolled layers
    are left alone); every other leaf is the same object."""
    def walk(tree):
        return {k: fn(v) if k in OPERAND_WEIGHTS and not isinstance(v, dict)
                else walk(v) if isinstance(v, dict) else v
                for k, v in tree.items()}
    stacked = layout.stacked_keys(layout.layer_layout(cfg))
    return {k: walk(v) if k in stacked else v for k, v in params.items()}


def bf16_operands_exact(params) -> bool:
    """Do the params' matmuls take bf16 operands already? On a TPU at
    DEFAULT precision an f32 dot rounds both operands to bf16, so a bf16
    copy of a weight computes exactly what the f32 weight does; on a CPU,
    or at a higher precision, it would not."""
    prec = jax.config.jax_default_matmul_precision
    if prec is not None and str(prec).lower() not in ("default",
                                                      "bfloat16"):
        return False
    return _on_tpu(params)


def _on_tpu(params) -> bool:
    leaf = jax.tree.leaves(params)[0]
    devices = leaf.devices() if isinstance(leaf, jax.Array) else ()
    return bool(devices) and all(d.platform == "tpu" for d in devices)


def serve_operands(cfg, params):
    """The tree the serving programs compute with: ``params`` whose
    stacked matmul weights are cast to bf16 once, where that is exact
    (:func:`bf16_operands_exact`), else ``params`` itself (also where no
    layer tree is stacked). A scan over f32 stacks would convert each
    whole stack on every call.
    """
    stacks = [params[k]
              for k in layout.stacked_keys(layout.layer_layout(cfg))]
    if not stacks or not bf16_operands_exact(stacks):
        return params
    return map_operands(cfg, lambda w: w.astype(jnp.bfloat16)
                        if w.dtype == jnp.float32 else w, params)


def init_paged_cache_tree(cfg, slots: int, num_pages: int, page_size: int,
                          dtype=jnp.bfloat16, mesh=None, cache_rules=None):
    """Paged cache pytree: attention K/V leaves become a page pool
    ``(layers, num_pages, page_size, K, hd)`` shared by all slots (page
    0 reserved as the dummy sink); SSM/RG-LRU/conv state is O(1) per
    request and stays per-slot, identical to the ring layout.
    """
    if cfg.kind in layout.FRONTEND_KINDS:
        raise ValueError(
            f"paged serving is token-only; arch kind {cfg.kind!r} is "
            "not served by the request schedulers")
    tree = _init_paged_cache_tree(cfg, slots, num_pages, page_size, dtype)
    if mesh is None:
        return tree
    from repro.serving.sharding import SERVE_CACHE_RULES
    rules = cache_rules or SERVE_CACHE_RULES
    axes = paged_cache_logical_axes_tree(cfg)
    from jax.sharding import NamedSharding
    flat, treedef = jax.tree_util.tree_flatten(tree)
    flat_ax = jax.tree_util.tree_flatten(
        axes, is_leaf=lambda x: isinstance(x, tuple))[0]
    assert len(flat) == len(flat_ax)
    out = [jax.device_put(l, NamedSharding(
        mesh, rules.spec_for_shape(tuple(ax), tuple(l.shape), mesh)))
        for l, ax in zip(flat, flat_ax)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _init_paged_cache_tree(cfg, slots, num_pages, page_size, dtype):
    def pool(role):
        return attn.init_paged_cache(cfg, num_pages, page_size, dtype)
    return layout.build(layout.layer_layout(cfg),
                        _cache_maker(cfg, slots, dtype, pool), _stack_cache)


def paged_cache_logical_axes_tree(cfg):
    """Logical axes matching init_paged_cache_tree's structure."""
    return layout.build(
        layout.layer_layout(cfg),
        _axes_maker(cfg, lambda role: attn.paged_cache_logical_axes()),
        _stack_axes)


def _chunk_attn_layer(lp, cfg, x, kv, *, mode, window, start, valid,
                      page_row, layer):
    """One attn layer over a prefill chunk, writing K/V into pages.

    x: (1, C, d); kv: {'k','v'} stacked page pools, of which ``layer``
    is this layer's; start/valid: traced scalars
    (chunk offset, #real tokens in the chunk); page_row:
    (pages_per_slot,) this slot's pages. Rows j >= valid are padding:
    their writes go to the dummy page, their queries never feed the
    cache or the logits, and MoE routing masks them out.
    """
    h = apply_norm(cfg, lp["ln_attn"], x)
    out, new_kv = _chunk_attn_mixer(lp["attn"], cfg, h, kv, mode=mode,
                                    window=window, start=start, valid=valid,
                                    page_row=page_row, layer=layer)
    x = x + out

    h = apply_norm(cfg, lp["ln_mlp"], x)
    if "moe" in lp:
        tmask = (jnp.arange(x.shape[1]) < valid)[None, :]
        h, _ = moem.apply_moe(lp["moe"], cfg, h, token_mask=tmask)
    else:
        h = mlpm.apply_mlp(lp["mlp"], cfg, h)
    return x + h, new_kv


def _chunk_attn_mixer(pa, cfg, h, kv, *, mode, window, start, valid,
                      page_row, layer):
    """The attention of ``_chunk_attn_layer`` on its normed input h:
    returns (output projection, updated page pools)."""
    from repro.dist.sharding import hint
    from repro.models.common import rope as rope_fn
    B, C, _ = h.shape
    q = attn._project_q(pa, cfg, h)
    k, v = attn._project_kv(pa, cfg, h)
    q = hint(q, ("pod", "data"), None, "model", None, None)
    k = hint(k, ("pod", "data"), None, "model", None)
    v = hint(v, ("pod", "data"), None, "model", None)
    if cfg.rope:
        tpos = start + jnp.arange(C)
        q = rope_fn(q.reshape(B, C, -1, cfg.head_dim), tpos,
                    cfg.rope_theta).reshape(q.shape)
        k = rope_fn(k, tpos, cfg.rope_theta)
    q = hint(q, ("pod", "data"), None, "model", None, None)
    k = hint(k, ("pod", "data"), None, "model", None)
    v = hint(v, ("pod", "data"), None, "model", None)

    ps = kv["k"].shape[-3]
    P = page_row.shape[0]
    j = jnp.arange(C)
    tgt = start + j                                  # absolute positions
    pg = page_row[jnp.clip(tgt // ps, 0, P - 1)]
    flat = jnp.where(j < valid, pg * ps + tgt % ps, j % ps)
    k_pages, v_pages = attn._paged_scatter(kv, k[0], v[0], flat, layer)

    kv_shape = (1, P * ps) + k_pages.shape[-2:]
    kg = layout.read_pages(k_pages, layer, page_row).reshape(kv_shape)
    vg = layout.read_pages(v_pages, layer, page_row).reshape(kv_shape)
    out = attn.simple_attention(q, kg.astype(q.dtype), vg.astype(q.dtype),
                                mode=mode, window=window, q_offset=start,
                                k_len=start + valid)
    out = out.reshape(B, C, cfg.num_heads * cfg.head_dim)
    return out @ pa["wo"].astype(h.dtype), {"k": k_pages, "v": v_pages}


def _chunk_ssm_layer(lp, cfg, x, c, *, slot, start, valid, layer):
    """One SSM layer over a prefill chunk, carrying slot state across
    chunks: conv context + SSD ``h0`` are read from (and written back
    to) the slot's lane of layer ``layer`` of the stacked cache leaves;
    ``start == 0`` starts fresh."""
    return _chunk_ssm_mixer(lp["ssm"], cfg, apply_norm(cfg, lp["ln"], x),
                            c, slot=slot, start=start, valid=valid,
                            residual=lambda y: x + y, layer=layer)


def _chunk_ssm_mixer(ps, cfg, h, c, *, slot, start, valid, residual,
                     layer):
    """The Mamba-2 block of ``_chunk_ssm_layer`` on its normed input h:
    returns (``residual(output projection)``, updated per-slot state).
    The residual add stays before the state writes, where the ``ssm``
    kind's program has always had it."""
    b, C, _ = h.shape
    d_in, H, P, S = ssmm._dims(cfg)
    K = cfg.ssm_conv_width
    fresh = start == 0
    old = layout.read(c, layer, slot)
    h0, cx0, cB0, cC0 = (jnp.where(fresh, 0.0, old[n])
                         for n in ("h", "conv_x", "conv_B", "conv_C"))

    proj = h @ ps["w_in"].astype(h.dtype)
    z, xs, Bm, Cm, dt_raw = ssmm._split_proj(cfg, proj)
    xs_pre, Bm_pre, Cm_pre = xs, Bm, Cm
    (xs, Bm, Cm), _ = ssmm.conv3(ps, xs, Bm, Cm, (cx0, cB0, cC0))
    xs, Bm, Cm = jax.nn.silu(xs), jax.nn.silu(Bm), jax.nn.silu(Cm)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + ps["dt_bias"].astype(jnp.float32))
    # dt = 0 freezes the recurrence on pad rows (same trick as the
    # mixed-length one-shot prefill), so h_fin is the state at valid-1
    keep = (jnp.arange(C)[None, :] < valid)[..., None]
    dt = jnp.where(keep, dt, 0.0)
    A = -jnp.exp(ps["A_log"].astype(jnp.float32))
    y, h_fin = ssmm.ssd_chunked(xs.reshape(b, C, H, P), dt, dt * A,
                                Bm, Cm, h0=h0, chunk=cfg.ssm_chunk)
    y = y + xs.reshape(b, C, H, P) * ps["D"].astype(
        h.dtype)[None, None, :, None]
    y = ssmm.gate(ps, cfg, y.reshape(b, C, d_in), z)
    x = residual(y @ ps["w_out"].astype(h.dtype))

    def conv_next(state0, pre):
        if K <= 1:
            return state0
        xp = jnp.concatenate([state0.astype(pre.dtype), pre], axis=1)
        return jax.lax.dynamic_slice_in_dim(xp, valid, K - 1, axis=1)

    new = {"h": h_fin, "conv_x": conv_next(cx0, xs_pre),
           "conv_B": conv_next(cB0, Bm_pre), "conv_C": conv_next(cC0, Cm_pre)}
    return x, layout.write(c, layer, new, slot)


def _chunk_rec_layer(lp, cfg, x, c, *, slot, start, valid, layer):
    """One RG-LRU layer over a prefill chunk with carried (h, conv)
    state: the inbound hidden state is folded into the first scan
    element (h_0 = a_0 h_in + b_0), which continues the recurrence
    exactly."""
    dt = x.dtype
    K = cfg.rglru_conv_width
    h = apply_norm(cfg, lp["ln_rec"], x)
    ga = jax.nn.gelu(h @ lp["rec"]["w_gelu"].astype(dt), approximate=True)
    xb = h @ lp["rec"]["w_rec"].astype(dt)
    xb_pre = xb
    fresh = start == 0
    old = layout.read(c, layer, slot)
    h0 = jnp.where(fresh, 0.0, old["h"])                    # (1, w)
    conv0 = jnp.where(fresh, 0.0, old["conv"])
    xb, _ = rgm._causal_conv(xb, lp["rec"]["conv"], conv0)
    a, beta = rgm._gates(lp["rec"], xb)
    b = beta * xb.astype(jnp.float32)
    b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2

    _, hs = jax.lax.associative_scan(combine, (a, b), axis=1)
    y = (ga.astype(jnp.float32) * hs).astype(dt)
    x = x + y @ lp["rec"]["w_out"].astype(dt)
    x = x + mlpm.apply_mlp(lp["mlp"], cfg,
                           apply_norm(cfg, lp["ln_mlp"], x))
    h_last = jax.lax.dynamic_slice_in_dim(
        hs, jnp.clip(valid - 1, 0), 1, axis=1)[:, 0]

    if K > 1:
        xp = jnp.concatenate([conv0.astype(xb_pre.dtype), xb_pre], axis=1)
        conv1 = jax.lax.dynamic_slice_in_dim(xp, valid, K - 1, axis=1)
    else:
        conv1 = conv0
    return x, layout.write(c, layer, {"h": h_last, "conv": conv1}, slot)


def prefill_chunk(p, cfg, cache, tokens, start, valid, page_row, slot,
                  *, dtype=jnp.float32, serve_window: int = 0):
    """Process ONE page_size-multiple chunk of a prompt into the paged
    cache (chunked prefill, DESIGN.md §15).

    tokens: (1, C) right-padded chunk; start: traced absolute offset of
    the chunk (a page_size multiple — or the shared-prefix length when
    earlier pages came from the prefix trie); valid: #real tokens in
    the chunk; page_row: (pages_per_slot,) int32 page ids; slot: traced
    recurrent-state lane. One jit signature serves single-shot prefill
    (C >= prompt length) and streamed long prompts alike.

    Returns (new_cache, logits at token ``start + valid - 1``). The
    caller flips the slot live only after the LAST chunk — until then
    the decode-visible page map row stays all-dummy, so interleaved
    decode ticks cannot observe a half-written prefix.
    """
    kind = cfg.kind
    if kind in layout.FRONTEND_KINDS:
        raise ValueError(kind)
    B, C = tokens.shape
    start = jnp.asarray(start, jnp.int32).reshape(())
    valid = jnp.asarray(valid, jnp.int32).reshape(())
    slot = jnp.asarray(slot, jnp.int32).reshape(())
    page_row = jnp.asarray(page_row, jnp.int32)
    x = _embed_tokens(p, cfg, tokens, dtype)
    mode, window = "causal", 0
    if cfg.sliding_window:
        mode, window = "sliding", cfg.sliding_window
    elif serve_window and kind not in ("ssm", "hybrid"):
        mode, window = "sliding", serve_window

    def attn_body(lp, xx, c, i):
        return _chunk_attn_layer(lp, cfg, xx, c, mode=mode, window=window,
                                 start=start, valid=valid,
                                 page_row=page_row, layer=i)

    def local_attn_body(lp, xx, c, i):
        return _chunk_attn_layer(lp, cfg, xx, c, mode="sliding",
                                 window=cfg.attention_window, start=start,
                                 valid=valid, page_row=page_row, layer=i)

    # pad rows (j >= valid) run through the experts like the others:
    # the expert layer is row-independent, so they touch no real row
    def mamba_body(lp, xx, c, i):
        with jax.named_scope("ssm_mixer"):
            xx, c = _chunk_ssm_mixer(
                lp["ssm"], cfg, apply_norm(cfg, lp["ln"], xx), c,
                slot=slot, start=start, valid=valid,
                residual=functools.partial(hybrid_residual, cfg, xx),
                layer=i)
        return hybrid_ffn(lp, cfg, xx), c

    def attention_body(lp, xx, c, i):
        with jax.named_scope("attn_mixer"):
            y, c = _chunk_attn_mixer(
                lp["attn"], cfg, apply_norm(cfg, lp["ln_attn"], xx), c,
                mode=mode, window=window, start=start, valid=valid,
                page_row=page_row, layer=i)
        return hybrid_ffn(lp, cfg, hybrid_residual(cfg, xx, y)), c

    state = dict(slot=slot, start=start, valid=valid)
    bodies = {
        "attn": attn_body, "moe": attn_body, "local_attn": local_attn_body,
        "ssm": lambda lp, xx, c, i: _chunk_ssm_layer(lp, cfg, xx, c,
                                                     layer=i, **state),
        "rec": lambda lp, xx, c, i: _chunk_rec_layer(lp, cfg, xx, c,
                                                     layer=i, **state),
        "mamba": mamba_body, "attention": attention_body,
    }
    x, new_cache = layout.run(layout.layer_layout(cfg), p, x, bodies,
                              cache=cache)

    x = apply_norm(cfg, p["ln_final"], x)
    x_last = jax.lax.dynamic_slice_in_dim(
        x, jnp.clip(valid - 1, 0), 1, axis=1)        # (1, 1, d)
    logits = _unembed(p, cfg, x_last)
    return new_cache, logits


def _write_live(c, i, new, old, live):
    """``c`` with layer ``i``'s recurrent state set to ``new`` on live
    lanes and kept ``old`` on the others (mid-prefill / retired slots
    must not have their carried state trampled by decode ticks): the
    ``where`` fuses into the layer's write. Leaves with a leading slots
    axis only — page pools self-protect via the dummy page."""
    def gate(n, o):
        return jnp.where(live.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
    return layout.write(c, i, jax.tree.map(gate, new, old))


def decode_step_paged(p, cfg, token, cache, pos, page_map, live, *,
                      dtype=jnp.bfloat16, serve_window: int = 0,
                      use_kernel: bool = False):
    """One-token generation step against the PAGED cache.

    token: (B, 1); cache: tree from init_paged_cache_tree; pos: (B,);
    page_map: (B, pages_per_slot) int32 (dummy rows for inactive
    slots); live: (B,) bool — recurrent-state updates are masked off
    for non-live lanes, and their attention writes land in the dummy
    page via the page map. Returns (logits, new_cache).
    """
    kind = cfg.kind
    if kind in layout.FRONTEND_KINDS:
        raise ValueError(kind)
    B = token.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    live = jnp.asarray(live, bool).reshape(B)
    x = _embed_tokens(p, cfg, token, dtype)
    w = effective_window(cfg, serve_window)

    def attn_dec(lp, xx, c, i):
        h = apply_norm(cfg, lp["ln_attn"], xx)
        out, c_new = attn.paged_decode_attention(
            lp["attn"], cfg, h, c, pos, page_map, window=w, live=live,
            use_kernel=use_kernel, layer=i)
        xx = xx + out
        h = apply_norm(cfg, lp["ln_mlp"], xx)
        if "moe" in lp:
            h, _ = moem.apply_moe(lp["moe"], cfg, h)
        else:
            h = mlpm.apply_mlp(lp["mlp"], cfg, h)
        return xx + h, c_new

    def ssm_dec(lp, xx, c, i):
        h = apply_norm(cfg, lp["ln"], xx)
        old = layout.read(c, i)
        y, c_new = ssmm.decode_ssm(lp["ssm"], cfg, h, old)
        return xx + y, _write_live(c, i, c_new, old, live)

    def rec_dec(lp, xx, c, i):
        h = apply_norm(cfg, lp["ln_rec"], xx)
        old = layout.read(c, i)
        y, c_new = rgm.decode_rglru(lp["rec"], cfg, h, old)
        xx = xx + y
        xx = xx + mlpm.apply_mlp(lp["mlp"], cfg,
                                 apply_norm(cfg, lp["ln_mlp"], xx))
        return xx, _write_live(c, i, c_new, old, live)

    def mamba_dec(lp, xx, c, i):
        with jax.named_scope("ssm_mixer"):
            old = layout.read(c, i)
            y, c_new = ssmm.decode_ssm(
                lp["ssm"], cfg, apply_norm(cfg, lp["ln"], xx), old)
            c = _write_live(c, i, c_new, old, live)
        return hybrid_ffn(lp, cfg, hybrid_residual(cfg, xx, y)), c

    def attention_dec(lp, xx, c, i):
        with jax.named_scope("attn_mixer"):
            y, c_new = attn.paged_decode_attention(
                lp["attn"], cfg, apply_norm(cfg, lp["ln_attn"], xx),
                c, pos, page_map, window=w, live=live,
                use_kernel=use_kernel, layer=i)
        return hybrid_ffn(lp, cfg, hybrid_residual(cfg, xx, y)), c_new

    bodies = {
        "attn": attn_dec, "moe": attn_dec, "local_attn": attn_dec,
        "ssm": ssm_dec, "rec": rec_dec,
        "mamba": mamba_dec, "attention": attention_dec,
    }
    x, new_cache = layout.run(layout.layer_layout(cfg), p, x, bodies,
                              cache=cache)

    x = apply_norm(cfg, p["ln_final"], x)
    logits = _unembed(p, cfg, x)
    return logits, new_cache
