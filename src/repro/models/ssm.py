"""Mamba-2 block (SSD — state-space duality, arXiv:2405.21060).

Layer: in_proj -> [z | x | B | C | dt] ; short causal conv on (x,B,C)
(optionally with a bias); SSD scan  h_t = exp(dt*A) h_{t-1} + dt * B_t
(x) x_t,  y_t = C_t h_t + D*x_t ; gate by silu(z) (optionally followed
by an RMSNorm over the inner width, Mamba-2's gated norm); out_proj.

Two SSD execution paths:
* ``chunked jnp`` (default in models): lax.scan over chunks carrying the
  (H, S, P) state — compact HLO for the multi-pod dry-run, identical
  math to the Pallas kernel.
* ``pallas`` (TPU target): `repro.kernels.ssd_scan`.

Decode: O(1) single-step state update (the whole point of SSMs for the
``long_500k`` shape).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import (Px, dense_init, ones_init, rmsnorm,
                                 zeros_init)


def _dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    H = cfg.ssm_num_heads
    P = cfg.ssm_head_dim
    S = cfg.ssm_state_dim
    assert H * P == d_in, (H, P, d_in)
    return d_in, H, P, S


def init_ssm(key, cfg) -> dict:
    d = cfg.d_model
    d_in, H, P, S = _dims(cfg)
    ks = jax.random.split(key, 8)
    conv_k = cfg.ssm_conv_width
    p = {
        "w_in": dense_init(ks[0], (d, 2 * d_in + 2 * S + H),
                           ("embed", "ssm_in")),
        "conv_x": Px(jax.random.normal(ks[1], (conv_k, d_in)) * 0.1,
                     ("conv_k", "ssm_in")),
        "conv_B": Px(jax.random.normal(ks[2], (conv_k, S)) * 0.1,
                     ("conv_k", "ssm_state")),
        "conv_C": Px(jax.random.normal(ks[3], (conv_k, S)) * 0.1,
                     ("conv_k", "ssm_state")),
        "A_log": Px(jnp.log(jnp.linspace(1.0, 16.0, H)), ("ssm_heads",)),
        "D": ones_init((H,), ("ssm_heads",)),
        "dt_bias": Px(jnp.log(jnp.expm1(
            jnp.linspace(1e-3, 1e-1, H))), ("ssm_heads",)),
        "w_out": dense_init(ks[4], (d_in, d), ("ssm_in", "embed"),
                            fan_in=d_in),
    }
    if cfg.ssm_conv_bias:
        p["conv_x_bias"] = zeros_init((d_in,), ("ssm_in",))
        p["conv_B_bias"] = zeros_init((S,), ("ssm_state",))
        p["conv_C_bias"] = zeros_init((S,), ("ssm_state",))
    if cfg.ssm_gated_norm:
        p["inner_norm"] = zeros_init((d_in,), ("ssm_in",))
    return p


def _split_proj(cfg, proj):
    d_in, H, P, S = _dims(cfg)
    z, xs, B, C, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + S, 2 * d_in + 2 * S], axis=-1)
    return z, xs, B, C, dt


def _causal_conv(x, w, state=None, bias=None):
    """Depthwise causal conv. x: (B, T, D); w: (K, D); bias: (D,) or None.

    state: (B, K-1, D) trailing context for decode; returns (y, new_state).
    """
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros_like(x[:, : K - 1])
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)          # (B, T+K-1, D)
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype)
            for i in range(K))
    if bias is not None:
        y = y + bias.astype(x.dtype)
    new_state = xp[:, -(K - 1):] if K > 1 else jnp.zeros_like(x[:, :0])
    return y, new_state


def conv3(p, xs, Bm, Cm, states=(None, None, None)):
    """The depthwise causal convolutions of x, B and C (with their
    biases where the block has them); returns ((xs, Bm, Cm), states)."""
    outs = [_causal_conv(v, p[f"conv_{n}"], st, p.get(f"conv_{n}_bias"))
            for v, n, st in zip((xs, Bm, Cm), "xBC", states)]
    return tuple(o[0] for o in outs), tuple(o[1] for o in outs)


def gate(p, cfg, y, z):
    """y * silu(z); with the gated inner norm, RMSNorm of that over the
    inner width (one group), computed in float32."""
    if "inner_norm" not in p:
        return y * jax.nn.silu(z)
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return rmsnorm(g, p["inner_norm"], cfg.norm_eps).astype(y.dtype)


def ssd_chunked(x, dt, loga, B, C, h0=None, chunk: int = 256):
    """Chunked SSD, vectorized jnp (same math as kernels/ssd_scan).

    x: (b, T, H, P); dt/loga: (b, T, H); B/C: (b, T, S) (state shared
    across heads, per Mamba-2's single B/C group). Returns
    (y: (b,T,H,P), h: (b,H,S,P)).
    """
    b, T, H, P = x.shape
    S = B.shape[-1]
    pad = (-T) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        loga = jnp.pad(loga, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    Tp = T + pad
    nc = Tp // chunk

    # reshape to chunks, move chunk axis to front for scan
    xs = jnp.moveaxis(x.reshape(b, nc, chunk, H, P), 1, 0)
    dts = jnp.moveaxis(dt.reshape(b, nc, chunk, H), 1, 0)
    las = jnp.moveaxis(loga.reshape(b, nc, chunk, H), 1, 0)
    Bs = jnp.moveaxis(B.reshape(b, nc, chunk, S), 1, 0)
    Cs = jnp.moveaxis(C.reshape(b, nc, chunk, S), 1, 0)

    if h0 is None:
        h0 = jnp.zeros((b, H, S, P), jnp.float32)

    def chunk_step(h, inp):
        xc, dtc, lac, bc, cc = inp
        xc = xc.astype(jnp.float32)
        dtc = dtc.astype(jnp.float32)
        lac = lac.astype(jnp.float32)
        bc = bc.astype(jnp.float32)
        cc = cc.astype(jnp.float32)
        l = jnp.cumsum(lac, axis=1)                  # (b, Q, H)
        # intra-chunk
        g = jnp.einsum("bts,bus->btu", cc, bc)       # (b, Q, Q)
        q = xc.shape[1]
        ti = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        ui = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        causal = (ti >= ui)[None, :, :, None]
        # l is non-increasing, so causal (t >= u) exponents are <= 0;
        # clamping is exact there and keeps the non-causal entries
        # (discarded by the where) from overflowing exp in f32 — an inf
        # behind a where still poisons the BACKWARD pass (0 * inf = nan)
        decay = jnp.exp(jnp.minimum(
            l[:, :, None, :] - l[:, None, :, :], 0.0))        # (b,Q,Q,H)
        m = jnp.where(causal, g[..., None] * decay * dtc[:, None, :, :], 0.0)
        y = jnp.einsum("btuh,buhp->bthp", m, xc)
        # inter-chunk (carried state)
        cdec = cc[:, :, None, :] * jnp.exp(l)[..., None]      # (b,Q,H,S)
        y = y + jnp.einsum("bths,bhsp->bthp", cdec, h)
        # state update
        total = l[:, -1, :]                                   # (b, H)
        bdec = bc[:, :, None, :] * (jnp.exp(total[:, None, :] - l)
                                    * dtc)[..., None]         # (b,Q,H,S)
        h_new = jnp.exp(total)[..., None, None] * h + \
            jnp.einsum("bths,bthp->bhsp", bdec, xc)
        return h_new, y

    h_fin, ys = jax.lax.scan(chunk_step, h0, (xs, dts, las, Bs, Cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, Tp, H, P)[:, :T]
    return y.astype(x.dtype), h_fin


def apply_ssm(p, cfg, x, *, use_pallas: bool = False):
    """Full-sequence SSD block. x: (B, T, d) -> (B, T, d)."""
    b, T, d = x.shape
    d_in, H, P, S = _dims(cfg)
    dt_model = x.dtype

    proj = x @ p["w_in"].astype(dt_model)
    z, xs, Bm, Cm, dt_raw = _split_proj(cfg, proj)
    (xs, Bm, Cm), _ = conv3(p, xs, Bm, Cm)
    xs = jax.nn.silu(xs)
    Bm = jax.nn.silu(Bm)
    Cm = jax.nn.silu(Cm)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (b,T,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))              # (H,)
    loga = dt * A                                             # (b,T,H)

    xh = xs.reshape(b, T, H, P)
    if use_pallas:
        from repro.kernels import ops as kops
        xbh = xh.transpose(0, 2, 1, 3).reshape(b * H, T, P)
        dtb = dt.transpose(0, 2, 1).reshape(b * H, T)
        lab = loga.transpose(0, 2, 1).reshape(b * H, T)
        Bb = jnp.broadcast_to(Bm[:, None], (b, H, T, S)).reshape(b * H, T, S)
        Cb = jnp.broadcast_to(Cm[:, None], (b, H, T, S)).reshape(b * H, T, S)
        ybh, _ = kops.ssd_scan(xbh, dtb, lab, Bb, Cb, chunk=cfg.ssm_chunk)
        y = ybh.reshape(b, H, T, P).transpose(0, 2, 1, 3)
    else:
        y, _ = ssd_chunked(xh, dt, loga, Bm, Cm, chunk=cfg.ssm_chunk)

    y = y + xh * p["D"].astype(dt_model)[None, None, :, None]
    y = y.reshape(b, T, d_in)
    y = gate(p, cfg, y, z)
    return y @ p["w_out"].astype(dt_model)


# ---------------------------------------------------------------------------
# decode: O(1) recurrent step
# ---------------------------------------------------------------------------

def init_ssm_cache(cfg, batch: int, dtype=jnp.float32) -> dict:
    d_in, H, P, S = _dims(cfg)
    K = cfg.ssm_conv_width
    return {
        "h": jnp.zeros((batch, H, S, P), jnp.float32),
        "conv_x": jnp.zeros((batch, K - 1, d_in), dtype),
        "conv_B": jnp.zeros((batch, K - 1, S), dtype),
        "conv_C": jnp.zeros((batch, K - 1, S), dtype),
    }


def ssm_cache_logical_axes(cfg) -> dict:
    return {
        "h": ("cache_batch", "ssm_heads", "ssm_state", None),
        "conv_x": ("cache_batch", None, "ssm_in"),
        "conv_B": ("cache_batch", None, None),
        "conv_C": ("cache_batch", None, None),
    }


def decode_ssm(p, cfg, x, cache):
    """x: (B, 1, d) -> (y, new_cache)."""
    b = x.shape[0]
    d_in, H, P, S = _dims(cfg)
    dt_model = x.dtype

    proj = x @ p["w_in"].astype(dt_model)
    z, xs, Bm, Cm, dt_raw = _split_proj(cfg, proj)
    (xs, Bm, Cm), (cx, cB, cC) = conv3(
        p, xs, Bm, Cm, (cache["conv_x"], cache["conv_B"], cache["conv_C"]))
    xs = jax.nn.silu(xs)[:, 0]                    # (b, d_in)
    Bm = jax.nn.silu(Bm)[:, 0]                    # (b, S)
    Cm = jax.nn.silu(Cm)[:, 0]

    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))   # (b, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = jnp.exp(dt * A)                                        # (b, H)

    xh = xs.reshape(b, H, P).astype(jnp.float32)
    # tensor-parallel decode: recurrent state sharded over SSM heads
    # (shape-aware — a no-op on single device / indivisible head counts)
    from repro.dist.sharding import hint
    xh = hint(xh, ("pod", "data"), "model", None)
    h = cache["h"]
    h = a[..., None, None] * h + \
        dt[..., None, None] * Bm[:, None, :, None] * xh[:, :, None, :]
    h = hint(h, ("pod", "data"), "model", None, None)
    y = jnp.einsum("bs,bhsp->bhp", Cm, h)                      # (b, H, P)
    y = y + xh * p["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(b, 1, d_in).astype(dt_model)
    y = gate(p, cfg, y, z)
    new_cache = {"h": h, "conv_x": cx.astype(cache["conv_x"].dtype),
                 "conv_B": cB.astype(cache["conv_B"].dtype),
                 "conv_C": cC.astype(cache["conv_C"].dtype)}
    return y @ p["w_out"].astype(dt_model), new_cache
