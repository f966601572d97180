"""Mixture-of-Experts FFNs.

``apply_held_moe`` (end of the module) is the dropless top-k layer with
a shared expert of the ``mamba_hybrid`` kind (Granite 4.0-H): it is
told which experts it holds, routes over all of them, and computes the
held experts' part of the result. ``apply_moe``, the ``moe`` kind's
top-1 (Switch-style) capacity layer:

Dispatch/combine are one-hot EINSUMS over token groups (scatter-free —
see apply_moe's docstring), giving the *active*-FLOPs formulation
(top_k x dense, not E x) with the expert axis sharded over ``model``
(expert parallelism) and optional ``expert_ffn`` sharding for the
weights-stay-put/tokens-move layout (EXPERIMENTS.md §Perf HC4).
Overflow tokens beyond per-group capacity are dropped (residual passes
through), the standard Switch behaviour.

Aux losses: Switch load-balance loss E * sum_e f_e * p_e and router
z-loss; both returned for the trainer to weigh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import mlp as mlpm
from repro.models.common import Px, dense_init


def init_moe(key, cfg) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe_num_experts
    ks = jax.random.split(key, 4)
    gated = cfg.mlp_variant in ("swiglu", "geglu")
    p = {
        "router": dense_init(ks[0], (d, E), ("embed", "experts_router")),
        "w_up": dense_init(ks[1], (E, d, f),
                           ("experts", "embed_fsdp", "expert_ffn")),
        "w_down": dense_init(ks[2], (E, f, d),
                             ("experts", "expert_ffn", "embed_fsdp"),
                             fan_in=f),
    }
    if gated:
        p["w_gate"] = dense_init(ks[3], (E, d, f),
                                 ("experts", "embed_fsdp", "expert_ffn"))
    return p


def _group_size(G: int, target: int = 2048) -> int:
    """Largest divisor of G that is <= target (dispatch tile size)."""
    if G <= target:
        return G
    n = -(-G // target)           # ceil
    while G % n:
        n += 1
    return G // n


def apply_moe(p, cfg, x: jax.Array, capacity_factor: float | None = None,
              token_mask: jax.Array | None = None):
    """x: (B, T, D) -> (y, aux) with y: (B, T, D).

    Dispatch/combine are ONE-HOT EINSUMS over token groups (no scatter):
    GSPMD partitions them cleanly — groups follow the batch sharding,
    the expert axis follows the 'model' sharding — whereas a scatter
    into an expert-sharded buffer makes the partitioner replicate the
    whole token stream. Capacity is per group (Switch-style dropping);
    the dispatch one-hot costs ~(E*c/3F) of the expert FLOPs (~8%).

    ``token_mask``: optional (B, T) bool — False tokens (serving pad)
    are excluded from dispatch entirely: they consume no expert
    capacity, contribute nothing to the load-balance stats, and get
    y = 0 (residual passthrough). Masked mode also makes token groups
    PER ROW (n = B, s = T) so routing and capacity are row-independent:
    a slot in a mixed batch dispatches exactly like the same prompt in
    a batch-1 prefill of the same padded length — no cross-request
    capacity interference in serving.
    """
    from repro.dist.sharding import hint
    B, T, D = x.shape
    E = cfg.moe_num_experts
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    G = B * T
    dt = x.dtype
    if token_mask is not None:
        s, n = T, B
    else:
        s = _group_size(G)
        n = G // s
    c = int(max(1, round(s * capacity_factor / E)))
    xg = hint(x.reshape(n, s, D), ("pod", "data"), None, None)

    logits = jnp.einsum("nsd,de->nse", xg,
                        p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (n, s, E)
    eid = jnp.argmax(logits, axis=-1)                        # (n, s)
    gate = jnp.max(probs, axis=-1)                           # (n, s)

    onehot_e = jax.nn.one_hot(eid, E, dtype=jnp.float32)     # (n, s, E)
    if token_mask is not None:
        keep_tok = token_mask.reshape(n, s).astype(jnp.float32)
        onehot_e = onehot_e * keep_tok[..., None]
    pos_in_e = jnp.cumsum(onehot_e, axis=1) - onehot_e       # (n, s, E)
    pos = jnp.sum(pos_in_e * onehot_e, axis=-1)              # (n, s) f32
    keep = pos < c
    onehot_c = jax.nn.one_hot(pos.astype(jnp.int32), c,
                              dtype=jnp.float32)             # (n, s, c)
    disp = (onehot_e[..., None] * onehot_c[:, :, None, :]
            * keep[..., None, None]).astype(dt)              # (n, s, E, c)
    disp = hint(disp, ("pod", "data"), None, "model", None)

    buf = jnp.einsum("nsec,nsd->necd", disp, xg)             # (n, E, c, D)
    buf = hint(buf, ("pod", "data"), "model", None, None)
    gated = "w_gate" in p
    up = jnp.einsum("necd,edf->necf", buf, p["w_up"].astype(dt))
    up = hint(up, ("pod", "data"), "model", None, None)
    if gated:
        g = jnp.einsum("necd,edf->necf", buf, p["w_gate"].astype(dt))
        g = hint(g, ("pod", "data"), "model", None, None)
        act = jax.nn.silu(g) if cfg.mlp_variant == "swiglu" \
            else jax.nn.gelu(g, approximate=True)
        h = act * up
    else:
        h = jax.nn.gelu(up, approximate=True)
    out = jnp.einsum("necf,efd->necd", h, p["w_down"].astype(dt))
    out = hint(out, ("pod", "data"), "model", None, None)
    y = jnp.einsum("nsec,necd->nsd", disp, out)              # (n, s, D)
    y = hint(y, ("pod", "data"), None, None)
    y = y * gate[..., None].astype(dt)

    # aux: Switch load-balance + z-loss (over real tokens only when a
    # token_mask is given — pads must not bias the router losses)
    lse2 = jax.scipy.special.logsumexp(logits, axis=-1) ** 2
    if token_mask is None:
        frac_tokens = jnp.mean(onehot_e, axis=(0, 1))        # f_e
        frac_probs = jnp.mean(probs, axis=(0, 1))            # p_e
        z_loss = jnp.mean(lse2)
        drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
    else:
        n_real = jnp.maximum(jnp.sum(keep_tok), 1.0)
        frac_tokens = jnp.sum(onehot_e, axis=(0, 1)) / n_real
        frac_probs = jnp.sum(probs * keep_tok[..., None],
                             axis=(0, 1)) / n_real
        z_loss = jnp.sum(lse2 * keep_tok) / n_real
        drop_frac = 1.0 - jnp.sum(keep.astype(jnp.float32)
                                  * keep_tok) / n_real
    lb_loss = E * jnp.sum(frac_tokens * frac_probs)
    aux = {"load_balance": lb_loss, "router_z": z_loss,
           "drop_frac": drop_frac}
    return y.reshape(B, T, D), aux


# ---------------------------------------------------------------------------
# held experts, dropless top-k, shared expert (mamba_hybrid)
# ---------------------------------------------------------------------------

def init_held_moe(key, cfg) -> dict:
    """The router over all ``moe_num_experts``, the SwiGLU weights of the
    held experts (``moe_experts_held``, 0 = all) and the shared expert."""
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe_num_experts
    n = cfg.moe_experts_held or E
    ks = jax.random.split(key, 5)
    ax = ("experts", "embed_fsdp", "expert_ffn")
    p = {
        "router": dense_init(ks[0], (d, E), ("embed", "experts_router")),
        "w_gate": dense_init(ks[1], (n, d, f), ax, fan_in=d),
        "w_up": dense_init(ks[2], (n, d, f), ax, fan_in=d),
        "w_down": dense_init(ks[3], (n, f, d),
                             ("experts", "expert_ffn", "embed_fsdp"),
                             fan_in=f),
    }
    if cfg.moe_shared_d_ff:
        p["shared"] = mlpm.init_mlp(ks[4], cfg, d_ff=cfg.moe_shared_d_ff)
    return p


def held_gates(logits, top_k: int, first: int, held: int):
    """Gates of experts ``first .. first + held - 1`` for router logits
    (..., E): a softmax over each row's ``top_k`` largest logits, 0 for
    an expert the row did not select. Each row on its own."""
    vals, idx = jax.lax.top_k(logits, top_k)
    w = jax.nn.softmax(vals, axis=-1)                      # (..., k)
    mine = idx[..., None] == first + jnp.arange(held)      # (..., k, n)
    return jnp.sum(jnp.where(mine, w[..., None], 0.0), axis=-2)


def apply_held_moe(p, cfg, x: jax.Array, first: int = 0) -> jax.Array:
    """x: (B, T, d) -> (B, T, d): the part of the routed mixture that
    the held experts (``first`` onward, as many as ``p`` holds) give,
    plus the shared expert where ``p`` has one.

    Dropless and row-independent: every held expert runs on every token
    and is weighted by its gate, 0 where the token did not select it,
    so no token routed to a held expert is dropped at any batch
    composition and a row's result does not depend on the other rows."""
    dt = x.dtype
    with jax.named_scope("moe_router"):
        logits = jnp.einsum("btd,de->bte", x, p["router"].astype(dt)
                            ).astype(jnp.float32)
        gates = held_gates(logits, cfg.moe_top_k, first,
                           p["w_up"].shape[0]).astype(dt)
    with jax.named_scope("moe_experts"):
        g = jnp.einsum("btd,edf->btef", x, p["w_gate"].astype(dt))
        u = jnp.einsum("btd,edf->btef", x, p["w_up"].astype(dt))
        h = jax.nn.silu(g) * u * gates[..., None]
        y = jnp.einsum("btef,efd->btd", h, p["w_down"].astype(dt))
    if "shared" in p:
        with jax.named_scope("shared_expert"):
            y = y + mlpm.apply_mlp(p["shared"], cfg, x)
    return y
