"""Plain reference of Mamba-2 (SSD, arXiv:2405.21060) in ``jax.numpy``.

No kernel, no cache, no batching tricks, no chunking: the SSD mixer is
evaluated in its quadratic "dual" form over the whole sequence,

    y_t = sum_{u <= t} (C_t . B_u) exp(sum_{r=u+1..t} dt_r A) dt_u x_u,

with the stable segment sum of the paper's minimal SSD listing. It
imports nothing of the program and is handed nothing the program made:
:func:`init` builds the weights from the seed in the parameter layout
the program is served with, and both sides are given that one tree.

Departures from the published block, all of which the program shares
(they are its parameterisation, stated in the configuration file):
RMSNorm gains are ``1 + scale`` (zero-initialised); B, C and x get
separate depthwise causal convolutions; there is no inner RMSNorm
before ``out_proj``; the embedding is tied and padded to a multiple of
256 rows, and logits cover the padded rows too.

``precision`` is the matmul precision of every contraction and
``dtype`` the compute dtype: the reference proper runs float32 at
``HIGHEST``. ``precision="fp8"`` is the lower-precision control: every
operand of every contraction is rounded to float8 (e4m3, scaled per
tensor to its largest magnitude) and contracted at ``HIGHEST``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                 # largest finite float8_e4m3fn


def fp8(x):
    """x rounded to float8 e4m3 at a per-tensor scale, back in x's dtype."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-30) \
        / FP8_MAX
    q = (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(x.dtype)


def contract(spec: str, a, b, precision):
    """``jnp.einsum`` at ``precision``; "fp8" rounds both operands."""
    if isinstance(precision, str):
        assert precision == "fp8", precision
        a, b, precision = fp8(a), fp8(b), HIGHEST
    return jnp.einsum(spec, a, b, precision=precision)


def dims(cfg: dict):
    d = cfg["d_model"]
    d_in = cfg["ssm_expand"] * d
    H, P, S = cfg["ssm_num_heads"], cfg["ssm_head_dim"], cfg["ssm_state_dim"]
    assert H * P == d_in, (H, P, d_in)
    return d, d_in, H, P, S


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def init(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Weights from a key, in the program's layout: layer leaves stacked
    on a leading ``layers`` axis. Dense weights are N(0, 1/fan_in), the
    embedding N(0, 0.02^2), convolutions N(0, 0.1^2); A, D and dt's bias
    follow the Mamba-2 initialisation (A in [1, 16], dt in [1e-3, 1e-1])."""
    d, d_in, H, P, S = dims(cfg)
    L, K, V = cfg["num_layers"], cfg["ssm_conv_width"], padded_vocab(cfg)
    ks = jax.random.split(key, 7)

    def normal(k, shape, std):
        return jax.random.normal(k, shape, jnp.float32) * std

    per_layer = lambda v: jnp.broadcast_to(v, (L,) + v.shape)  # noqa: E731
    p = {
        "embed": normal(ks[0], (V, d), 0.02),
        "ln_final": {"scale": jnp.zeros((d,), jnp.float32)},
        "layers": {
            "ln": {"scale": jnp.zeros((L, d), jnp.float32)},
            "ssm": {
                "w_in": normal(ks[1], (L, d, 2 * d_in + 2 * S + H),
                               1 / np.sqrt(d)),
                "conv_x": normal(ks[2], (L, K, d_in), 0.1),
                "conv_B": normal(ks[3], (L, K, S), 0.1),
                "conv_C": normal(ks[4], (L, K, S), 0.1),
                "A_log": per_layer(jnp.log(jnp.linspace(1.0, 16.0, H))),
                "D": jnp.ones((L, H), jnp.float32),
                "dt_bias": per_layer(jnp.log(jnp.expm1(
                    jnp.linspace(1e-3, 1e-1, H)))),
                "w_out": normal(ks[5], (L, d_in, d), 1 / np.sqrt(d_in)),
            },
        },
    }
    return jax.tree.map(lambda x: x.astype(dtype), p)


def rmsnorm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def causal_conv(x, w):
    """Depthwise causal convolution: y_t = sum_i w_i x_{t-(K-1)+i}."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, i:i + T] * w[i].astype(x.dtype) for i in range(K))


def segsum(a):
    """Stable segment sums: out[..., t, u] = sum_{r=u+1..t} a[..., r]
    for t >= u, -inf above the diagonal. a: (..., T)."""
    T = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (T,))
    strict = jnp.tril(jnp.ones((T, T), bool), -1)
    s = jnp.cumsum(jnp.where(strict, x, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def ssm(p, cfg, h, precision):
    d, d_in, H, P, S = dims(cfg)
    b, T, _ = h.shape
    dt_ = h.dtype
    proj = contract("btd,de->bte", h, p["w_in"].astype(dt_), precision)
    z, xs, Bm, Cm, dt_raw = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + S, 2 * d_in + 2 * S], axis=-1)
    xs = jax.nn.silu(causal_conv(xs, p["conv_x"]))
    Bm = jax.nn.silu(causal_conv(Bm, p["conv_B"]))
    Cm = jax.nn.silu(causal_conv(Cm, p["conv_C"]))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))      # (b,T,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(segsum(jnp.moveaxis(dt * A, -1, 1)))          # (b,H,T,T)
    G = contract("bts,bus->btu", Cm, Bm, precision)
    M = (G[:, None].astype(jnp.float32) * decay
         * jnp.moveaxis(dt, -1, 1)[:, :, None, :]).astype(dt_)
    x = xs.reshape(b, T, H, P)
    y = contract("bhtu,buhp->bthp", M, x, precision)
    y = y + x * p["D"].astype(dt_)[None, None, :, None]
    y = y.reshape(b, T, d_in) * jax.nn.silu(z)
    return contract("bte,ed->btd", y, p["w_out"].astype(dt_), precision)


def logits(params, cfg: dict, tokens, *, dtype=jnp.float32,
           precision=HIGHEST):
    """(b, T) tokens -> (b, T, padded vocab) float32 logits."""
    emb = params["embed"].astype(dtype)
    x = emb[tokens]

    def layer(x, lp):
        return x + ssm(lp["ssm"], cfg, rmsnorm(x, lp["ln"]["scale"]),
                       precision), None

    # recompute each layer in the backward pass: the (b, H, T, T) decay
    # and mixing matrices of every layer would not fit beside the
    # replicas otherwise
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = rmsnorm(x, params["ln_final"]["scale"])
    return contract("btd,vd->btv", x, emb, precision).astype(jnp.float32)


def loss(params, cfg: dict, tokens, labels, *, dtype=jnp.float32,
         precision=HIGHEST):
    """Mean next-token cross-entropy over every position."""
    lg = logits(params, cfg, tokens, dtype=dtype, precision=precision)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
