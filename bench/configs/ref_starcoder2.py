"""Plain reference of a StarCoder2 decoder (arXiv:2402.19173) in
``jax.numpy``: pre-LayerNorm blocks of grouped-query attention with
rotary positions inside a sliding window, and a GELU (tanh) MLP, all
with biases; a tied embedding.

No kernel, no cache, no paging: attention over the whole sequence,
materialised one block of queries at a time so that a 4,096-token
sequence fits beside the weights. It imports nothing of the program and
is handed nothing the program made: :func:`init` builds the weights
from the seed in the parameter layout the program is served with.

Departures from the published model, which the program shares (they
are its layout, stated in the configuration file): the output
projection has no bias; the embedding is padded to a multiple of 256
rows and logits cover the padded rows too.

``precision`` is the matmul precision of every contraction and
``dtype`` the compute dtype: the reference proper runs float32 at
``HIGHEST``; ``precision="fp8"`` is the lower-precision control (every
contraction's operands rounded to float8 e4m3 at a per-tensor scale).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.configs.ref_mamba2 import HIGHEST, contract, padded_vocab

Q_BLOCK = 512
NEG_INF = -1e30


def init(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Weights from a key, layer leaves stacked on a leading axis. Dense
    weights N(0, 1/fan_in), the embedding N(0, 0.02^2); LayerNorm gains
    1 and every bias 0."""
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    H, K = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // H
    V = padded_vocab(cfg)
    ks = jax.random.split(key, 7)

    def normal(k, shape, fan):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan)

    ones = lambda n: jnp.ones((L, n), jnp.float32)      # noqa: E731
    zeros = lambda n: jnp.zeros((L, n), jnp.float32)    # noqa: E731
    p = {
        "embed": jax.random.normal(ks[0], (V, d), jnp.float32) * 0.02,
        "ln_final": {"scale": jnp.ones((d,), jnp.float32),
                     "bias": jnp.zeros((d,), jnp.float32)},
        "layers": {
            "ln_attn": {"scale": ones(d), "bias": zeros(d)},
            "attn": {"wq": normal(ks[1], (L, d, H * hd), d),
                     "wk": normal(ks[2], (L, d, K * hd), d),
                     "wv": normal(ks[3], (L, d, K * hd), d),
                     "wo": normal(ks[4], (L, H * hd, d), H * hd),
                     "bq": zeros(H * hd), "bk": zeros(K * hd),
                     "bv": zeros(K * hd)},
            "ln_mlp": {"scale": ones(d), "bias": zeros(d)},
            "mlp": {"w_up": normal(ks[5], (L, d, f), d), "b_up": zeros(f),
                    "w_down": normal(ks[6], (L, f, d), f),
                    "b_down": zeros(d)},
        },
    }
    return jax.tree.map(lambda x: x.astype(dtype), p)


def layernorm(x, p, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta: float):
    """Rotary embedding, rotate-half form. x: (T, n, hd); pos: (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def attention(p, cfg, h, precision):
    """h: (T, d) one sequence -> (T, d)."""
    T = h.shape[0]
    H, K = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or cfg["d_model"] // H
    G, window = H // K, cfg.get("sliding_window") or T
    dt_ = h.dtype
    q = contract("td,de->te", h, p["wq"].astype(dt_), precision) + p["bq"]
    k = contract("td,de->te", h, p["wk"].astype(dt_), precision) + p["bk"]
    v = contract("td,de->te", h, p["wv"].astype(dt_), precision) + p["bv"]
    pos = jnp.arange(T)
    theta = cfg["rope_theta"]
    q = rope(q.reshape(T, H, hd).astype(dt_), pos, theta)
    k = rope(k.reshape(T, K, hd).astype(dt_), pos, theta)
    v = v.reshape(T, K, hd).astype(dt_)
    nb = min(Q_BLOCK, T)
    q = q.reshape(T // nb, nb, K, G, hd)

    def block(args):
        qb, i = args
        s = contract("qkgh,skh->kgqs", qb * hd ** -0.5, k,
                     precision).astype(jnp.float32)
        qp = i * nb + jnp.arange(nb)
        keep = (pos[None, :] <= qp[:, None]) & (pos[None, :]
                                                > qp[:, None] - window)
        w = jax.nn.softmax(jnp.where(keep, s, NEG_INF), -1).astype(dt_)
        return contract("kgqs,skh->qkgh", w, v, precision)

    out = jax.lax.map(block, (q, jnp.arange(T // nb)))
    out = out.reshape(T, H * hd).astype(dt_)
    return contract("te,ed->td", out, p["wo"].astype(dt_), precision)


def mlp(p, h, precision):
    dt_ = h.dtype
    u = contract("td,df->tf", h, p["w_up"].astype(dt_), precision) \
        + p["b_up"].astype(dt_)
    u = jax.nn.gelu(u, approximate=True)
    return contract("tf,fd->td", u, p["w_down"].astype(dt_), precision) \
        + p["b_down"].astype(dt_)


def logits(params, cfg: dict, tokens, *, dtype=jnp.float32,
           precision=HIGHEST):
    """(1, T) tokens, T a multiple of 512 (or under it) -> (1, T, padded
    vocab) float32 logits."""
    emb = params["embed"].astype(dtype)
    x = emb[tokens[0]]

    def layer(x, lp):
        x = x + attention(lp["attn"], cfg, layernorm(x, lp["ln_attn"]),
                          precision)
        return x + mlp(lp["mlp"], layernorm(x, lp["ln_mlp"]), precision), \
            None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = layernorm(x, params["ln_final"])
    return contract("td,vd->tv", x, emb, precision).astype(
        jnp.float32)[None]
