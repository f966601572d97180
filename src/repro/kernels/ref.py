"""Pure-jnp oracles for every Pallas kernel (the correctness ground
truth; tests sweep shapes/dtypes against these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def consensus_mix_ref(z: jax.Array, V: jax.Array,
                      gamma: jax.Array) -> jax.Array:
    """z: (N, s, ...); V: (N, s, s); gamma: (N,) int32 -> V_c^{gamma_c} z_c.

    Reference: explicit per-round einsum with per-cluster masking.
    gamma must be CONCRETE (the loop unrolls in Python) — it is read
    through numpy so the oracle also works on constants inside a jit
    trace; traced gamma raises TracerArrayConversionError.
    """
    import numpy as np
    gamma = np.asarray(gamma, np.int32)
    max_gamma = int(gamma.max()) if gamma.size else 0

    out = z.astype(jnp.float32)
    Vf = V.astype(jnp.float32)
    for r in range(max_gamma):
        mixed = jnp.einsum("nij,nj...->ni...", Vf, out,
                           precision=jax.lax.Precision.HIGHEST)
        keep = jnp.asarray((r < gamma).reshape((-1,) + (1,) * (z.ndim - 1)))
        out = jnp.where(keep, mixed, out)
    return out.astype(z.dtype)


def ssd_scan_ref(x: jax.Array, dt: jax.Array, loga: jax.Array,
                 B: jax.Array, C: jax.Array,
                 h0: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Mamba-2 SSD recurrence, sequential reference.

    x:    (BH, T, P)   per-head inputs
    dt:   (BH, T)      input gates (discretization steps, > 0)
    loga: (BH, T)      log decay per step (= dt * A_head, < 0)
    B:    (BH, T, S)   input projections onto the state
    C:    (BH, T, S)   output projections
    h0:   (BH, S, P)   initial state (zeros if None)

    returns y: (BH, T, P), h_final: (BH, S, P)

      h_t = exp(loga_t) * h_{t-1} + dt_t * B_t (x) x_t
      y_t = C_t @ h_t
    """
    BH, T, P = x.shape
    S = B.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((BH, S, P), jnp.float32)

    def step(h, inp):
        xt, dtt, lat, bt, ct = inp
        h = jnp.exp(lat)[:, None, None] * h + \
            dtt[:, None, None] * bt[:, :, None] * xt[:, None, :]
        y = jnp.einsum("bs,bsp->bp", ct, h)
        return h, y

    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(loga, 1, 0).astype(jnp.float32),
          jnp.moveaxis(B, 1, 0).astype(jnp.float32),
          jnp.moveaxis(C, 1, 0).astype(jnp.float32))
    h_final, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)
    return y, h_final


def fused_sgd_ref(w: jax.Array, g: jax.Array, eta: jax.Array,
                  weight_decay: float = 0.0) -> jax.Array:
    """w <- w - eta * (g + wd * w)."""
    gg = g.astype(jnp.float32) + weight_decay * w.astype(jnp.float32)
    return (w.astype(jnp.float32) - eta * gg).astype(w.dtype)


def fused_consensus_sgd_ref(w: jax.Array, g: jax.Array, W: jax.Array,
                            eta: jax.Array,
                            weight_decay: float = 0.0) -> jax.Array:
    """W_c @ (w_c - eta * (g_c + wd * w_c)); w, g: (N, s, M), W: (N, s, s)."""
    wp = fused_sgd_ref(w, g, eta, weight_decay=weight_decay)
    return jnp.einsum("nij,nj...->ni...", W.astype(jnp.float32),
                      wp.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32).astype(w.dtype)
