"""Feed-forward blocks: SwiGLU / GeGLU / plain GELU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import Px, dense_init, zeros_init


def init_mlp(key, cfg, d_model: int | None = None,
             d_ff: int | None = None) -> dict:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_variant in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d, f), ("embed", "ffn")),
            "w_up": dense_init(ks[1], (d, f), ("embed", "ffn")),
            "w_down": dense_init(ks[2], (f, d), ("ffn", "embed"), fan_in=f),
        }
    return {
        "w_up": dense_init(ks[0], (d, f), ("embed", "ffn")),
        "b_up": zeros_init((f,), ("ffn",)),
        "w_down": dense_init(ks[1], (f, d), ("ffn", "embed"), fan_in=f),
        "b_down": zeros_init((d,), ("embed_nomodel",)),
    }


def apply_mlp(p, cfg, x: jax.Array) -> jax.Array:
    dt = x.dtype
    if cfg.mlp_variant in ("swiglu", "geglu"):
        g = x @ p["w_gate"].astype(dt)
        u = x @ p["w_up"].astype(dt)
        act = jax.nn.silu(g) if cfg.mlp_variant == "swiglu" \
            else jax.nn.gelu(g, approximate=True)
        return (act * u) @ p["w_down"].astype(dt)
    h = jax.nn.gelu(x @ p["w_up"].astype(dt) + p["b_up"].astype(dt),
                    approximate=True)
    return h @ p["w_down"].astype(dt) + p["b_down"].astype(dt)
