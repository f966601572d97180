"""Plain reference of IBM Granite 4.0-H (``granitemoehybrid``) in
``jax.numpy``: pre-RMSNorm layers whose mixer is a Mamba-2 block or
grouped-query attention without positional encoding, in the published
``layer_types`` order; after every mixer a mixture of routed SwiGLU
experts (top-k of the router's logits, a softmax over the selected
ones) plus a shared SwiGLU expert. Each layer is

    x += r * mixer(rms(x));  x += r * (experts(rms(x)) + shared(rms(x)))

with r the residual multiplier; the embedding is scaled by the
embedding multiplier, the logits divided by the logits scaling, and
attention's softmax scale is the attention multiplier. The Mamba-2
output passes the gated RMSNorm, ``rms(y * silu(z)) * w``, before
``out_proj``; its convolution has a bias.

No kernel, no cache, no paging, no batching: the SSD runs over the
sequence in chunks of ``ssm_chunk`` tokens that carry the state (each
chunk in the quadratic "dual" form), attention one block of queries at
a time over every earlier key, so that one 8,192-token sequence fits
beside the weights. The experts are evaluated one at a time (a scan
over the held experts) on every token, weighted by the token's gate (0
where the token did not select the expert). It imports nothing of the program and is handed nothing
the program made: :func:`init` builds the weights from the seed in the
parameter layout the program is served with.

Held experts: the configuration holds ``moe_experts_held`` of the
router's ``moe_num_experts`` (the first ones); the router keeps its
published width and top-k, and the layer's result is the held experts'
part of the mixture, as one chip of an expert-parallel deployment
computes it. :func:`experts` takes the first held expert's index, so a
test can add up the parts of every share.

Departures from the published model, which the program shares (they
are its layout, stated in the configuration file): RMSNorm gains are
``1 + scale`` (zero-initialised), the inner gated norm's too; B, C and
x get separate depthwise convolutions (each with its slice of the
bias); an expert's input projection is two matrices (gate, up) and not
one of twice the width; the embedding is padded to a multiple of 256
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

from bench.configs.ref_mamba2 import (HIGHEST, causal_conv, contract,
                                      padded_vocab, segsum)

Q_BLOCK = 512
NEG_INF = -1e30
# the embedding's init scale: the embedding enters the residual stream
# x12 and leaves it tied, so at the usual N(0, 0.02^2) the token just
# fed is the argmax of every position on random weights (the residual
# branches are scaled by 0.22), and a check of served tokens could not
# see a fault; at 0.02 / 24 it is the argmax about as rarely as any
# other token
EMBED_STD = 0.02 / 24
# the attention layer's init gains: at N(0, 1/fan_in) its scores at the
# published scale of 1/128 spread by 0.09, every query averages its
# whole context into a near-constant vector, and no fault in the K/V
# pages, the block walk or the scale moves a served token; with q and k
# at 8x their scores spread by about 5.7 (8^2 * sqrt(128) / 128) and a
# query picks out a few keys, as a trained model's attention does, and
# the output projection at 3x gives the layer a share of the residual
# stream that a fault in it shows through
QK_GAIN = 8.0
OUT_GAIN = 3.0


def _ssm_dims(cfg: dict):
    d = cfg["d_model"]
    d_in = cfg["ssm_expand"] * d
    H, P, S = cfg["ssm_num_heads"], cfg["ssm_head_dim"], cfg["ssm_state_dim"]
    assert H * P == d_in, (H, P, d_in)
    return d, d_in, H, P, S


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _init_ffn(key, cfg: dict) -> dict:
    d, f, E = cfg["d_model"], cfg["d_ff"], cfg["moe_num_experts"]
    n = cfg.get("moe_experts_held") or E
    fs = cfg["moe_shared_d_ff"]
    ks = jax.random.split(key, 7)
    return {
        "router": _normal(ks[0], (d, E), 1 / np.sqrt(d)),
        "w_gate": _normal(ks[1], (n, d, f), 1 / np.sqrt(d)),
        "w_up": _normal(ks[2], (n, d, f), 1 / np.sqrt(d)),
        "w_down": _normal(ks[3], (n, f, d), 1 / np.sqrt(f)),
        "shared": {"w_gate": _normal(ks[4], (d, fs), 1 / np.sqrt(d)),
                   "w_up": _normal(ks[5], (d, fs), 1 / np.sqrt(d)),
                   "w_down": _normal(ks[6], (fs, d), 1 / np.sqrt(fs))},
    }


def _init_mamba(key, cfg: dict) -> dict:
    d, d_in, H, P, S = _ssm_dims(cfg)
    K = cfg["ssm_conv_width"]
    ks = jax.random.split(key, 8)
    return {
        "w_in": _normal(ks[0], (d, 2 * d_in + 2 * S + H), 1 / np.sqrt(d)),
        "conv_x": _normal(ks[1], (K, d_in), 0.1),
        "conv_B": _normal(ks[2], (K, S), 0.1),
        "conv_C": _normal(ks[3], (K, S), 0.1),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.linspace(1e-3, 1e-1, H))),
        "w_out": _normal(ks[4], (d_in, d), 1 / np.sqrt(d_in)),
        "conv_x_bias": _normal(ks[5], (d_in,), 0.1),
        "conv_B_bias": _normal(ks[6], (S,), 0.1),
        "conv_C_bias": _normal(ks[7], (S,), 0.1),
        "inner_norm": jnp.zeros((d_in,), jnp.float32),
    }


def _init_attention(key, cfg: dict) -> dict:
    d, H, K = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // H
    ks = jax.random.split(key, 4)
    return {"wq": _normal(ks[0], (d, H * hd), QK_GAIN / np.sqrt(d)),
            "wk": _normal(ks[1], (d, K * hd), QK_GAIN / np.sqrt(d)),
            "wv": _normal(ks[2], (d, K * hd), 1 / np.sqrt(d)),
            "wo": _normal(ks[3], (H * hd, d), OUT_GAIN / np.sqrt(H * hd))}


def init(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Weights from a key, in the program's layout: a list of per-layer
    trees in ``layer_types`` order. Dense weights N(0, 1/fan_in)
    (attention's q and k at QK_GAIN, its output at OUT_GAIN times that
    deviation), the embedding N(0, EMBED_STD^2), convolutions and their
    biases N(0, 0.1^2);
    A, D and dt's bias follow the Mamba-2 initialisation (A in [1, 16],
    dt in [1e-3, 1e-1]); every norm gain 1 (scale 0)."""
    d = cfg["d_model"]
    ks = jax.random.split(key, 2 + 2 * cfg["num_layers"])
    zeros = lambda: {"scale": jnp.zeros((d,), jnp.float32)}  # noqa: E731
    layers = []
    for i, t in enumerate(cfg["layer_types"]):
        km, kf = ks[2 + 2 * i], ks[3 + 2 * i]
        if t == "mamba":
            lp = {"ln": zeros(), "ssm": _init_mamba(km, cfg)}
        else:
            lp = {"ln_attn": zeros(), "attn": _init_attention(km, cfg)}
        lp["ln_mlp"] = zeros()
        lp["moe"] = _init_ffn(kf, cfg)
        layers.append(lp)
    p = {"embed": _normal(ks[0], (padded_vocab(cfg), d), EMBED_STD),
         "ln_final": zeros(), "layers": layers}
    return jax.tree.map(lambda x: x.astype(dtype), p)


def rmsnorm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def ssd(x, dt, A, B, C, chunk: int, precision):
    """The SSD recurrence over one sequence, chunk by chunk. x: (T, H,
    P); dt: (T, H); B, C: (T, S). Inside a chunk the quadratic form;
    across chunks the carried state h (H, S, P)."""
    T, H, P = x.shape
    S = B.shape[-1]
    pad = (-T) % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, B, C))
    n = (T + pad) // chunk
    split = lambda v: v.reshape((n, chunk) + v.shape[1:])  # noqa: E731

    def step(h, inp):
        xc, dtc, Bc, Cc = inp
        a = dtc * A                                          # (Q, H)
        decay = jnp.exp(segsum(a.T))                         # (H, Q, Q)
        G = contract("ts,us->tu", Cc, Bc, precision).astype(jnp.float32)
        M = (G[None] * decay * dtc.T[:, None, :]).astype(xc.dtype)
        y = contract("htu,uhp->thp", M, xc, precision)
        cum = jnp.cumsum(a, axis=0)                          # (Q, H)
        y = y + contract("ts,hsp->thp", Cc, h.astype(xc.dtype),
                         precision) * jnp.exp(cum)[..., None].astype(y.dtype)
        total = cum[-1]                                      # (H,)
        w = (jnp.exp(total[None] - cum) * dtc).astype(xc.dtype)
        h = jnp.exp(total)[:, None, None] * h + contract(
            "us,uhp->hsp", Bc, xc * w[..., None], precision).astype(
                jnp.float32)
        return h, y

    h0 = jnp.zeros((H, S, P), jnp.float32)
    _, ys = jax.lax.scan(step, h0, tuple(map(split, (x, dt, B, C))))
    return ys.reshape(n * chunk, H, P)[:T]


def mamba(p, cfg: dict, h, precision):
    """h: (T, d) one sequence -> (T, d)."""
    d, d_in, H, P, S = _ssm_dims(cfg)
    T = h.shape[0]
    dt_ = h.dtype
    proj = contract("td,de->te", h, p["w_in"].astype(dt_), precision)
    z, xs, Bm, Cm, dt_raw = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + S, 2 * d_in + 2 * S], axis=-1)
    conv = lambda v, n: causal_conv(v[None], p[f"conv_{n}"])[0] \
        + p[f"conv_{n}_bias"].astype(dt_)                    # noqa: E731
    xs = jax.nn.silu(conv(xs, "x"))
    Bm = jax.nn.silu(conv(Bm, "B"))
    Cm = jax.nn.silu(conv(Cm, "C"))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (T, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    x = xs.reshape(T, H, P)
    y = ssd(x, dt, A, Bm, Cm, cfg["ssm_chunk"], precision)
    y = y + x * p["D"].astype(dt_)[None, :, None]
    g = y.reshape(T, d_in).astype(jnp.float32) * jax.nn.silu(
        z.astype(jnp.float32))
    y = rmsnorm(g, p["inner_norm"], cfg["norm_eps"]).astype(dt_)
    return contract("te,ed->td", y, p["w_out"].astype(dt_), precision)


def attention(p, cfg: dict, h, precision):
    """Causal grouped-query attention without positions. h: (T, d)."""
    T = h.shape[0]
    H, K = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or cfg["d_model"] // H
    G = H // K
    dt_ = h.dtype
    q = contract("td,de->te", h, p["wq"].astype(dt_), precision)
    k = contract("td,de->te", h, p["wk"].astype(dt_), precision)
    v = contract("td,de->te", h, p["wv"].astype(dt_), precision)
    nb = min(Q_BLOCK, T)
    pad = (-T) % nb
    q = jnp.pad(q, ((0, pad), (0, 0))).reshape(-1, nb, K, G, hd)
    k, v = k.reshape(T, K, hd), v.reshape(T, K, hd)
    pos = jnp.arange(T)
    scale = cfg["attention_multiplier"]

    def block(args):
        qb, i = args
        s = contract("qkgh,skh->kgqs", qb * scale, k,
                     precision).astype(jnp.float32)
        keep = pos[None, :] <= (i * nb + jnp.arange(nb))[:, None]
        w = jax.nn.softmax(jnp.where(keep, s, NEG_INF), -1).astype(dt_)
        return contract("kgqs,skh->qkgh", w, v, precision)

    out = jax.lax.map(block, (q, jnp.arange(q.shape[0])))
    out = out.reshape(-1, H * hd)[:T].astype(dt_)
    return contract("te,ed->td", out, p["wo"].astype(dt_), precision)


def swiglu(h, w_gate, w_up, w_down, precision):
    dt_ = h.dtype
    g = contract("td,df->tf", h, w_gate.astype(dt_), precision)
    u = contract("td,df->tf", h, w_up.astype(dt_), precision)
    return contract("tf,fd->td", jax.nn.silu(g) * u, w_down.astype(dt_),
                    precision)


def experts(p, cfg: dict, h, precision, first: int = 0):
    """The part of the routed mixture that experts ``first ..`` (as many
    as ``p`` holds) give. h: (T, d). The router takes the top
    ``moe_top_k`` of all its logits and a softmax over those."""
    logits = contract("td,de->te", h, p["router"].astype(h.dtype),
                      precision).astype(jnp.float32)
    top, idx = jax.lax.top_k(logits, cfg["moe_top_k"])
    w = jax.nn.softmax(top, -1)

    def one(y, args):
        e, wg, wu, wd = args
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)   # (T,)
        return y + gate[:, None].astype(h.dtype) * swiglu(
            h, wg, wu, wd, precision), None

    n = p["w_up"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(n), p["w_gate"], p["w_up"], p["w_down"]))
    return y


def ffn(p, cfg: dict, h, precision):
    """The held experts' part plus the shared expert."""
    s = p["shared"]
    return experts(p, cfg, h, precision) + swiglu(
        h, s["w_gate"], s["w_up"], s["w_down"], precision)


def logits(params, cfg: dict, tokens, *, dtype=jnp.float32,
           precision=HIGHEST):
    """(1, T) tokens -> (1, T, padded vocab) float32 logits."""
    emb = params["embed"].astype(dtype)
    eps, r = cfg["norm_eps"], cfg["residual_multiplier"]
    x = emb[tokens[0]] * cfg["embedding_multiplier"]
    for lp, t in zip(params["layers"], cfg["layer_types"]):
        if t == "mamba":
            y = mamba(lp["ssm"], cfg, rmsnorm(x, lp["ln"]["scale"], eps),
                      precision)
        else:
            y = attention(lp["attn"], cfg,
                          rmsnorm(x, lp["ln_attn"]["scale"], eps), precision)
        x = x + r * y
        x = x + r * ffn(lp["moe"], cfg,
                        rmsnorm(x, lp["ln_mlp"]["scale"], eps), precision)
    x = rmsnorm(x, params["ln_final"]["scale"], eps)
    out = contract("td,vd->tv", x, emb, precision) / cfg["logits_scaling"]
    return out.astype(jnp.float32)[None]
