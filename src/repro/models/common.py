"""Shared model-zoo building blocks: params-with-logical-axes, norms,
embeddings, initializers.

Parameters are plain pytrees of arrays. Sharding is expressed by a
*parallel* pytree of logical-axis tuples produced at init time: every
init function returns ``Px(array, logical_axes)`` leaves; ``split_tree``
separates them into (params, axes). ``dist.sharding`` maps logical axes
to mesh axes.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class Px(NamedTuple):
    """A parameter leaf bundled with its logical sharding axes."""
    value: Any
    axes: tuple


def split_tree(tree):
    """Pytree of Px -> (params, logical_axes) with identical structure."""
    is_px = lambda x: isinstance(x, Px)
    params = jax.tree.map(lambda p: p.value, tree, is_leaf=is_px)
    axes = jax.tree.map(lambda p: tuple(p.axes), tree, is_leaf=is_px)
    return params, axes


# ---------------------------------------------------------------------------
# initializers (operate on key, produce Px)
# ---------------------------------------------------------------------------

def dense_init(key, shape, axes, dtype=jnp.float32, scale: float = 1.0,
               fan_in: int | None = None) -> Px:
    fan = fan_in if fan_in is not None else shape[0]
    std = scale / np.sqrt(max(fan, 1))
    return Px(jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype),
              axes)


def embed_init(key, vocab, dim, axes, dtype=jnp.float32) -> Px:
    return Px(jax.random.normal(key, (vocab, dim), dtype) * 0.02, axes)


def zeros_init(shape, axes, dtype=jnp.float32) -> Px:
    return Px(jnp.zeros(shape, dtype), axes)


def ones_init(shape, axes, dtype=jnp.float32) -> Px:
    return Px(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    # gemma convention: multiply by (1 + scale)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def norm_init(key, cfg, dim: int) -> dict:
    if cfg.norm == "rmsnorm":
        return {"scale": zeros_init((dim,), ("embed_nomodel",))}
    return {"scale": ones_init((dim,), ("embed_nomodel",)),
            "bias": zeros_init((dim,), ("embed_nomodel",))}


def apply_norm(cfg, p: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"], cfg.norm_eps)
    return layernorm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array,
         theta: float = 10_000.0) -> jax.Array:
    """Rotary embeddings. x: (..., T, n, hd); positions: (..., T)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq   # (..., T, half)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def sinusoidal_positions(T: int, dim: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings (T, dim)."""
    half = dim // 2
    freq = jnp.exp(-np.log(10_000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = jnp.arange(T)[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token NLL; logits (..., V) possibly vocab-sharded (XLA inserts
    the collectives), labels int (...)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
