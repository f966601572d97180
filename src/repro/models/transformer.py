"""Transformer stacks for every assigned architecture family.

Design notes
------------
* **Scan over layers.** Per-layer parameters are stacked on a leading
  ``layers`` axis and the stack runs under ``jax.lax.scan`` — compact
  HLO (one layer body) so the 48-layer/512-device dry-runs compile
  quickly, and the standard structure for activation rematerialization.
* **Heterogeneous stacks** (llama4's interleaved MoE, RecurrentGemma's
  2-recurrent:1-attention pattern) scan over *groups* — the smallest
  repeating unit — so no parameter space is wasted on union layouts.
* **Caches** are pytrees with the same leading ``layers``/``groups``
  axis, threaded through the scan during decode.
* **Published layer orders** (``mamba_hybrid``: Mamba-2 and attention
  mixers as ``cfg.layer_types`` lists them) are a list of per-layer
  trees, unrolled: the mixers differ in kind, so no stack holds them.
* **One layout per kind.** Which stacks a kind has, what one body of
  each holds and in what order is described once, by
  ``models/layout.layer_layout``. ``init_model`` builds the params from
  it, ``forward`` walks it (``layout.run``), and so do the serving
  engine's cache builders and its four phases. Adding a kind is one
  branch there plus a body per new layer role in each phase; this
  module then needs an init and a forward body per role.

Every init function returns `Px(value, logical_axes)` leaves; the
registry splits them (`split_tree`) and captures the axes tree during an
`eval_shape` trace, so abstract init never allocates.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layout
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models import rglru as rgm
from repro.models import ssm as ssmm
from repro.models.common import (
    Px, apply_norm, embed_init, norm_init, softmax_cross_entropy,
    sinusoidal_positions, split_tree,
)


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

def init_dense_layer(key, cfg, *, use_moe: bool = False,
                     cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "ln_attn": norm_init(ks[0], cfg, cfg.d_model),
        "attn": attn.init_attention(ks[0], cfg),
        "ln_mlp": norm_init(ks[1], cfg, cfg.d_model),
    }
    p["moe" if use_moe else "mlp"] = (
        moem.init_moe(ks[1], cfg) if use_moe else mlpm.init_mlp(ks[1], cfg))
    if cross:
        p["ln_cross"] = norm_init(ks[2], cfg, cfg.d_model)
        p["cross"] = attn.init_attention(ks[3], cfg, cross=True)
    return p


def apply_dense_layer(p, cfg, x, *, mode="causal", window=0,
                      prefix_len=None, enc_out=None, positions=None):
    from repro.dist.sharding import hint
    x = hint(x, ("pod", "data"), None, None)   # batch stays data-sharded
    h = apply_norm(cfg, p["ln_attn"], x)
    h = attn.attention_block(p["attn"], cfg, h, mode=mode, window=window,
                             prefix_len=prefix_len, positions=positions)
    x = x + h
    aux = None
    if "cross" in p:
        h = apply_norm(cfg, p["ln_cross"], x)
        h = attn.attention_block(p["cross"], cfg, h, mode="full",
                                 kv_source=enc_out)
        x = x + h
    h = apply_norm(cfg, p["ln_mlp"], x)
    if "moe" in p:
        h, aux = moem.apply_moe(p["moe"], cfg, h)
    else:
        h = mlpm.apply_mlp(p["mlp"], cfg, h)
    return x + h, aux


def init_ssm_layer(key, cfg) -> dict:
    return {"ln": norm_init(key, cfg, cfg.d_model),
            "ssm": ssmm.init_ssm(key, cfg)}


def apply_ssm_layer(p, cfg, x, use_pallas=False):
    from repro.dist.sharding import hint
    x = hint(x, ("pod", "data"), None, None)
    return x + ssmm.apply_ssm(p["ssm"], cfg,
                              apply_norm(cfg, p["ln"], x),
                              use_pallas=use_pallas)


def init_rec_layer(key, cfg) -> dict:
    ks = jax.random.split(key, 2)
    return {"ln_rec": norm_init(ks[0], cfg, cfg.d_model),
            "rec": rgm.init_rglru(ks[0], cfg),
            "ln_mlp": norm_init(ks[1], cfg, cfg.d_model),
            "mlp": mlpm.init_mlp(ks[1], cfg)}


def apply_rec_layer(p, cfg, x):
    from repro.dist.sharding import hint
    x = hint(x, ("pod", "data"), None, None)
    x = x + rgm.apply_rglru(p["rec"], cfg, apply_norm(cfg, p["ln_rec"], x))
    return x + mlpm.apply_mlp(p["mlp"], cfg, apply_norm(cfg, p["ln_mlp"], x))


def init_mamba_hybrid_layer(key, cfg, layer_type: str) -> dict:
    """One layer: a Mamba-2 or attention mixer, then the expert FFN."""
    ks = jax.random.split(key, 3)
    if layer_type == "mamba":
        p = {"ln": norm_init(ks[0], cfg, cfg.d_model),
             "ssm": ssmm.init_ssm(ks[0], cfg)}
    else:
        p = {"ln_attn": norm_init(ks[0], cfg, cfg.d_model),
             "attn": attn.init_attention(ks[0], cfg)}
    p["ln_mlp"] = norm_init(ks[1], cfg, cfg.d_model)
    p["moe"] = moem.init_held_moe(ks[2], cfg)
    return p


def hybrid_residual(cfg, x, y):
    """x + residual_multiplier * y."""
    return x + y * jnp.asarray(cfg.residual_multiplier, x.dtype)


def hybrid_ffn(lp, cfg, x):
    """The layer's second half: x + r * (experts + shared)(rms(x))."""
    h = apply_norm(cfg, lp["ln_mlp"], x)
    return hybrid_residual(cfg, x, moem.apply_held_moe(lp["moe"], cfg, h))


def apply_mamba_hybrid_layer(lp, cfg, x, use_pallas=False):
    """One layer over a whole sequence (no cache)."""
    if "ssm" in lp:
        with jax.named_scope("ssm_mixer"):
            y = ssmm.apply_ssm(lp["ssm"], cfg, apply_norm(cfg, lp["ln"], x),
                               use_pallas=use_pallas)
    else:
        with jax.named_scope("attn_mixer"):
            y = attn.attention_block(lp["attn"], cfg,
                                     apply_norm(cfg, lp["ln_attn"], x),
                                     mode="causal")
    return hybrid_ffn(lp, cfg, hybrid_residual(cfg, x, y))


# ---------------------------------------------------------------------------
# stack init
# ---------------------------------------------------------------------------

def _stack(init_one: Callable, key, n: int):
    """vmap-stack n layer inits; Px axes handled by a capture trick:
    we init one layer for the axes structure (under eval_shape upstream
    this never materializes), and vmap the value-only init for params."""
    keys = jax.random.split(key, n)
    template = init_one(keys[0])
    _, axes = split_tree(template)

    def values_only(k):
        params, _ = split_tree(init_one(k))
        return params

    stacked = jax.vmap(values_only)(keys)
    axes = jax.tree.map(lambda a: ("layers",) + tuple(a), axes,
                        is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.map(lambda v, a: Px(v, a), stacked, axes,
                        is_leaf=lambda x: not isinstance(x, (dict,)))


# ---------------------------------------------------------------------------
# the model: init
# ---------------------------------------------------------------------------

def _init_layer(role: str, key, cfg) -> dict:
    if role == "ssm":
        return init_ssm_layer(key, cfg)
    if role == "rec":
        return init_rec_layer(key, cfg)
    if role in ("mamba", "attention"):
        return init_mamba_hybrid_layer(key, cfg, role)
    return init_dense_layer(key, cfg, use_moe=role == "moe",
                            cross=role == "cross")


def init_model(key, cfg, dtype=jnp.float32) -> dict:
    """Full parameter tree (Px leaves) for any arch kind."""
    ks = jax.random.split(key, 8)
    V = cfg.padded_vocab
    # embedding d_model dim deliberately NOT fsdp-sharded: vocab/model
    # sharding already divides it 16x, and a data-sharded d dim makes
    # GSPMD all-gather activations instead of weights.
    p: dict[str, Any] = {
        "embed": embed_init(ks[0], V, cfg.d_model, ("vocab", "embed_nomodel")),
        "ln_final": norm_init(ks[1], cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = embed_init(ks[2], V, cfg.d_model,
                                  ("vocab", "embed_nomodel"))
    segs = layout.encoder_layout(cfg) + layout.layer_layout(cfg)
    # the stacks draw ks[3], ks[4] in order; a hybrid's tail draws ks[4]
    # even where it has no groups before it
    keys = [ks[4] if s.key == "tail" else ks[3 + i]
            for i, s in enumerate(segs)]
    p.update(layout.build(segs, lambda role, k: _init_layer(role, k, cfg),
                          _stack, keys))
    if layout.encoder_layout(cfg):
        p["enc_ln_final"] = norm_init(ks[5], cfg, cfg.d_model)
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_tokens(p, cfg, tokens, dtype):
    from repro.dist.sharding import hint
    x = jnp.take(p["embed"].astype(dtype), tokens, axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, dtype)
    return hint(x, ("pod", "data"), None, None)


def _unembed(p, cfg, x):
    from repro.dist.sharding import hint
    w = p["unembed"] if "unembed" in p else p["embed"]
    logits = jnp.einsum("btd,vd->btv", x, w.astype(x.dtype))
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    # keep the vocab dim model-sharded through the loss — materializing
    # replicated (B, T, V) logits is a multi-GB/device temp
    return hint(logits, ("pod", "data"), None, "model")


def forward(p, cfg, batch, *, dtype=jnp.bfloat16, remat: bool = True,
            use_pallas: bool = False):
    """Full-sequence forward -> (logits, aux_losses).

    batch: {"tokens": (B, T) int32, and per-frontend extras:
            "patches": (B, enc_seq, d) for vlm (stub vision output)
            "frames":  (B, enc_seq, d) for audio (stub codec output)}
    """
    kind = cfg.kind
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = _embed_tokens(p, cfg, tokens, dtype)
    mode, window, prefix_len = "causal", 0, None
    if cfg.sliding_window:
        mode, window = "sliding", cfg.sliding_window

    if kind == "vlm":
        # prefix-LM over [patch embeds | text]
        patches = batch["patches"].astype(dtype)
        x = jnp.concatenate([patches, x], axis=1)
        mode, prefix_len = "prefix", cfg.enc_seq_len

    enc_out = None
    if kind in ("encdec", "audio"):
        frames = batch["frames"].astype(dtype)
        pos = sinusoidal_positions(frames.shape[1], cfg.d_model).astype(dtype)
        h = frames + pos[None]
        def enc_body(lp, hh):
            y, _ = apply_dense_layer(lp, cfg, hh, mode="full")
            return y, None
        h, _ = layout.run(layout.encoder_layout(cfg), p, h,
                          {"enc": enc_body}, remat=remat)
        enc_out = apply_norm(cfg, p["enc_ln_final"], h)
        if not cfg.rope:
            dpos = sinusoidal_positions(T, cfg.d_model).astype(dtype)
            x = x + dpos[None]

    def attn_body(lp, xx):
        return apply_dense_layer(lp, cfg, xx, mode=mode, window=window,
                                 prefix_len=prefix_len)

    def local_attn_body(lp, xx):
        return apply_dense_layer(lp, cfg, xx, mode="sliding",
                                 window=cfg.attention_window)

    def cross_body(lp, xx):
        return apply_dense_layer(lp, cfg, xx, mode="causal", enc_out=enc_out)

    def mamba_hybrid_body(lp, xx):
        return apply_mamba_hybrid_layer(lp, cfg, xx,
                                        use_pallas=use_pallas), None

    bodies = {
        "attn": attn_body, "moe": attn_body, "local_attn": local_attn_body,
        "cross": cross_body,
        "ssm": lambda lp, xx: (apply_ssm_layer(lp, cfg, xx,
                                               use_pallas=use_pallas), None),
        "rec": lambda lp, xx: (apply_rec_layer(lp, cfg, xx), None),
        "mamba": mamba_hybrid_body, "attention": mamba_hybrid_body,
    }
    x, outs = layout.run(layout.layer_layout(cfg), p, x, bodies, remat=remat)
    # the MoE layers' aux losses: the only non-None layer outputs
    aux = jax.tree.leaves(outs, is_leaf=lambda a: isinstance(a, dict)
                          and "load_balance" in a)

    x = apply_norm(cfg, p["ln_final"], x)
    if kind == "vlm":
        x = x[:, cfg.enc_seq_len:]          # predict text positions only
    logits = _unembed(p, cfg, x)
    aux_losses = {}
    if aux:
        aux_losses["load_balance"] = jnp.mean(aux[0]["load_balance"])
        aux_losses["router_z"] = jnp.mean(aux[0]["router_z"])
    return logits, aux_losses


def loss_fn(p, cfg, batch, *, dtype=jnp.bfloat16, remat=True,
            use_pallas=False):
    logits, aux = forward(p, cfg, batch, dtype=dtype, remat=remat,
                          use_pallas=use_pallas)
    loss = softmax_cross_entropy(logits, batch["labels"])
    if "load_balance" in aux:
        loss = loss + cfg.moe_aux_loss_weight * aux["load_balance"] \
            + 1e-3 * aux["router_z"]
    return loss
