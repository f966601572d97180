"""Sharded step builders: train_step / prefill_step / serve_step with
phase-specific sharding rule tables.

This is the single place where logical axes meet the physical mesh; the
§Perf hillclimb edits these tables (or passes overrides) without
touching model code.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.dist.sharding import ShardingRules
from repro.models import layout
from repro.models.registry import ModelApi
from repro.optim import make_optimizer, apply_updates
from repro.optim.schedules import constant

# ---------------------------------------------------------------------------
# rule tables per phase
# ---------------------------------------------------------------------------

TRAIN_RULES = ShardingRules((
    ("batch", ("pod", "data")),
    # params: FSDP over (pod, data) on d_model dims, tensor over model
    ("embed", ("pod", "data")),
    ("embed_nomodel", None),
    ("vocab", "model"),
    ("q_proj", "model"),
    ("kv_proj", "model"),
    ("ffn", "model"),
    ("experts", "model"),
    ("expert_ffn", None),
    ("experts_router", None),
    ("embed_fsdp", ("pod", "data")),
    ("ssm_in", "model"),
    ("ssm_heads", "model"),
    ("ssm_state", None),
    ("rnn_width", "model"),
    ("rnn_width_in", ("pod", "data")),
    ("conv_k", None),
    ("layers", None),
))

# Serving: weights replicated over data (latency path), tensor-parallel
# over model; expert weights stay FSDP-sharded (memory). The table
# lives with the serving stack (DESIGN.md §14); re-exported here so the
# §Perf hillclimb still edits rule tables in one module.
from repro.serving.sharding import (  # noqa: E402
    SERVE_CACHE_RULES, SERVE_PARAM_RULES as SERVE_RULES)

CACHE_RULES_DECODE = ShardingRules((
    ("cache_batch", ("pod", "data")),
    ("cache_seq", "model"),
    ("cache_kv_heads", None),
    ("head_dim", None),
    ("ssm_heads", "model"),
    ("ssm_state", None),
    ("ssm_in", "model"),
    ("rnn_width", "model"),
    ("layers", None),
))

# long_500k: batch = 1 -> parallelize over the sequence/state dims.
CACHE_RULES_LONG = CACHE_RULES_DECODE.with_overrides(
    cache_batch=None,
    cache_seq=("pod", "data", "model"),
)


def _shard(tree_axes, mesh: Mesh, rules: ShardingRules):
    return jax.tree.map(
        lambda ax: NamedSharding(mesh, rules.spec(tuple(ax), mesh)),
        tree_axes, is_leaf=lambda x: isinstance(x, tuple))


def _replicated(mesh):
    return NamedSharding(mesh, P())


def batch_shardings(batch_specs: dict, mesh: Mesh, rules: ShardingRules):
    out = {}
    for k, v in batch_specs.items():
        nd = len(v.shape)
        spec = rules.spec(("batch",) + (None,) * (nd - 1), mesh)
        out[k] = NamedSharding(mesh, spec)
    return out


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def make_train_step(model: ModelApi, *, optimizer: str = "sgd",
                    lr: float = 1e-3, dtype=jnp.bfloat16, remat=True,
                    accum_steps: int = 1):
    """Returns (step_fn, opt) — step(params, opt_state, batch, step_idx).

    ``accum_steps > 1`` scans over microbatches with fp32 gradient
    accumulation: the per-layer activation stack (the dominant training
    temp) shrinks by the same factor.
    """
    opt = make_optimizer(optimizer)
    sched = constant(lr)

    def loss_of(params, mb):
        return model.loss(params, mb, dtype=dtype, remat=remat)

    def step(params, opt_state, batch, step_idx):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
        else:
            def resh(x):
                b = x.shape[0]
                return x.reshape((accum_steps, b // accum_steps)
                                 + x.shape[1:])
            micro = jax.tree.map(resh, batch)
            # accumulate in fp32 for fp32 params; for bf16-param giants
            # accumulate in bf16 (SGD-only path; on real TPUs pair with
            # stochastic rounding) — halves the accumulator footprint.
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32
                                    if p.dtype == jnp.float32
                                    else p.dtype), params)

            def acc(carry, mb):
                gacc, lacc = carry
                l, g = jax.value_and_grad(loss_of)(params, mb)
                gacc = jax.tree.map(
                    lambda a, b_: a + b_.astype(a.dtype), gacc, g)
                return (gacc, lacc + l), None

            (grads, loss), _ = jax.lax.scan(
                acc, (g0, jnp.zeros((), jnp.float32)), micro)
            loss = loss / accum_steps
        # grads are SUMMED over microbatches; fold the 1/accum into lr
        # (exact for SGD — avoids a full-param-sized divide temp)
        updates, opt_state = opt.update(grads, opt_state, params,
                                        sched(step_idx) / accum_steps)
        params = apply_updates(params, updates)
        return params, opt_state, loss

    return step, opt


def accum_steps_for(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                    budget_bytes: float = 2e9) -> int:
    """Pick gradient-accumulation so the saved per-layer activation
    stack (scan length x b_local x T x d x 2B) stays under ~4 GB."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    repl = sizes.get("pod", 1) * sizes.get("data", 1)
    b_local = max(shape.global_batch // repl, 1)
    n_saves = layout.depth(layout.encoder_layout(cfg)
                           + layout.layer_layout(cfg))
    stack = n_saves * b_local * shape.seq_len * cfg.d_model * 2
    a = 1
    while stack / a > budget_bytes and a < b_local:
        a *= 2
    return a


def opt_state_shardings(optimizer: str, param_shardings, mesh: Mesh):
    if optimizer == "sgd":
        return ()
    if optimizer == "momentum":
        return {"m": param_shardings}
    if optimizer == "adamw":
        return {"m": param_shardings, "v": param_shardings,
                "count": _replicated(mesh)}
    raise ValueError(optimizer)


def make_prefill_step(model: ModelApi, *, dtype=jnp.bfloat16,
                      serve_window=0, remat=True):
    def step(params, batch):
        return model.prefill(params, batch, dtype=dtype,
                             serve_window=serve_window, remat=remat)
    return step


def make_decode_step(model: ModelApi, *, dtype=jnp.bfloat16,
                     serve_window=0):
    def step(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos, dtype=dtype,
                                 serve_window=serve_window)
    return step


# ---------------------------------------------------------------------------
# fully-wired jit programs per (arch, shape, mesh)
# ---------------------------------------------------------------------------

def serve_window_for(cfg: ModelConfig, shape: InputShape) -> int:
    """The sliding-window *serving variant* for long-context decode on
    full-attention archs (DESIGN.md §6)."""
    if shape.name == "long_500k" and cfg.kind in ("dense", "moe", "vlm"):
        return 4096
    return 0


def param_dtype_for(cfg: ModelConfig):
    """bf16 master weights for the giant MoEs (SGD — the paper's
    optimizer — keeps no state, so this is the whole memory story)."""
    if cfg.param_count() > 5e10:
        return jnp.bfloat16
    return jnp.float32


def build_program(model: ModelApi, shape: InputShape, mesh: Mesh, *,
                  optimizer: str = "sgd", dtype=jnp.bfloat16,
                  rules_override: ShardingRules | None = None,
                  cache_rules_override: ShardingRules | None = None,
                  remat: bool = True):
    """Lowerable jit program + abstract inputs for one (arch, shape).

    Returns (jitted_fn, abstract_args) ready for `.lower(*args)`.
    """
    cfg = model.cfg
    pdt = param_dtype_for(cfg)
    params_abs, axes = model.abstract_params(dtype=pdt)
    sw = serve_window_for(cfg, shape)
    specs = model.input_specs(shape, serve_window=sw)

    if shape.phase == "train":
        rules = rules_override or TRAIN_RULES
        p_sh = _shard(axes, mesh, rules)
        step, opt = make_train_step(model, optimizer=optimizer, dtype=dtype,
                                    remat=remat,
                                    accum_steps=accum_steps_for(
                                        cfg, shape, mesh))
        opt_abs = jax.eval_shape(opt.init, params_abs)
        o_sh = opt_state_shardings(optimizer, p_sh, mesh)
        b_sh = batch_shardings(specs["batch"], mesh, rules)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, b_sh, _replicated(mesh)),
            out_shardings=(p_sh, o_sh, _replicated(mesh)),
            donate_argnums=(0, 1),
        )
        args = (params_abs, opt_abs, specs["batch"],
                jax.ShapeDtypeStruct((), jnp.int32))
        return fn, args

    rules = rules_override or SERVE_RULES
    p_sh = _shard(axes, mesh, rules)

    if shape.phase == "prefill":
        cache_rules = cache_rules_override or CACHE_RULES_DECODE
        c_axes = model.cache_axes()
        c_sh = _shard(c_axes, mesh, cache_rules)
        b_sh = batch_shardings(specs["batch"], mesh, rules)
        step = make_prefill_step(model, dtype=dtype, serve_window=sw,
                                 remat=remat)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, b_sh),
            out_shardings=(_replicated(mesh), c_sh, _replicated(mesh)),
        )
        args = (params_abs, specs["batch"])
        return fn, args

    # decode
    cache_rules = cache_rules_override or (
        CACHE_RULES_LONG if shape.name == "long_500k" else
        CACHE_RULES_DECODE)
    c_axes = model.cache_axes()
    c_sh = _shard(c_axes, mesh, cache_rules)
    tok_sh = NamedSharding(
        mesh, cache_rules.spec(("cache_batch", None), mesh))
    step = make_decode_step(model, dtype=dtype, serve_window=sw)
    fn = jax.jit(
        step,
        in_shardings=(p_sh, tok_sh, c_sh, _replicated(mesh)),
        out_shardings=(tok_sh, c_sh),
        donate_argnums=(2,),
    )
    args = (params_abs, specs["token"], specs["cache"], specs["pos"])
    return fn, args


def build_serve_program(model: ModelApi, mesh: Mesh, *,
                        slots: int = 8, max_prompt: int = 1024,
                        max_total: int = 2048, dtype=jnp.bfloat16,
                        rules: ShardingRules | None = None,
                        cache_rules: ShardingRules | None = None):
    """The continuous-batching serving pair on a production mesh:

    * ``admission`` — batch-1 prefill + ``write_cache_slot`` splice into
      the live ``(slots, max_total)`` cache (traced slot index);
    * ``decode`` — one sharded decode step over all slots with a
      per-slot ``pos`` vector (the cache buffer is donated, mirroring
      the scheduler's steady state).

    Returns ``{"admission": (fn, args), "decode": (fn, args)}`` with
    every boundary pinned by :func:`repro.serving.serve_shardings` —
    the dryrun serve mode lowers exactly what ``ContinuousScheduler``
    runs (ISSUE 8 / DESIGN.md §14).
    """
    from repro.serving import serve_shardings
    cfg = model.cfg
    if cfg.kind in layout.FRONTEND_KINDS:
        raise ValueError(
            f"serve program is token-only; arch kind {cfg.kind!r} needs "
            "frontend inputs the request path does not carry")
    pdt = param_dtype_for(cfg)
    sh = serve_shardings(model, mesh, slots=slots, max_total=max_total,
                         dtype=dtype, param_dtype=pdt, rules=rules,
                         cache_rules=cache_rules)
    params_abs, _ = model.abstract_params(dtype=pdt)
    cache_abs = model.abstract_cache(slots, max_total, dtype)
    i32 = jnp.int32
    logits_abs = jax.ShapeDtypeStruct((slots, 1, cfg.padded_vocab),
                                      dtype)
    pos_abs = jax.ShapeDtypeStruct((slots,), i32)

    def admission(params, cache, pos, logits, tokens, length, slot):
        lg1, c1, p1 = model.prefill(
            params, {"tokens": tokens}, dtype=dtype, cache_dtype=dtype,
            cache_len=max_total, lengths=length)
        cache, pos = model.write_cache_slot(
            cache, c1, slot, pos=pos, one_pos=p1[0],
            cache_rules=sh.cache_rules)
        logits = jax.lax.dynamic_update_slice(
            logits, lg1.astype(logits.dtype), (slot, 0, 0))
        return cache, pos, logits

    adm = jax.jit(
        admission,
        in_shardings=(sh.params, sh.cache, sh.pos, sh.logits,
                      sh.replicated, sh.replicated, sh.replicated),
        out_shardings=(sh.cache, sh.pos, sh.logits),
        donate_argnums=(1,),
    )
    adm_args = (params_abs, cache_abs, pos_abs, logits_abs,
                jax.ShapeDtypeStruct((1, max_prompt), i32),
                jax.ShapeDtypeStruct((1,), i32),
                jax.ShapeDtypeStruct((), i32))

    def decode(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos, dtype=dtype)

    dec = jax.jit(
        decode,
        in_shardings=(sh.params, sh.token, sh.cache, sh.pos),
        out_shardings=(sh.logits, sh.cache),
        donate_argnums=(2,),
    )
    dec_args = (params_abs, jax.ShapeDtypeStruct((slots, 1), i32),
                cache_abs, pos_abs)
    return {"admission": (adm, adm_args), "decode": (dec, dec_args)}


def build_paged_serve_program(model: ModelApi, mesh: Mesh, *,
                              slots: int = 8, max_prompt: int = 1024,
                              max_total: int = 2048, page_size: int = 64,
                              cache_pages: int | None = None,
                              prefill_chunk: int | None = None,
                              dtype=jnp.bfloat16,
                              rules: ShardingRules | None = None,
                              cache_rules: ShardingRules | None = None):
    """The PAGED serving pair on a production mesh (DESIGN.md §15):

    * ``admission_chunk`` — one chunked-prefill step writing a
      ``prefill_chunk``-token piece of a prompt into the slot's pages
      (traced start/valid/page-row, so one lowering serves every chunk
      of every prompt);
    * ``decode`` — one paged decode step over all slots, gathering K/V
      through the ``(slots, pages_per_slot)`` page map (cache donated,
      mirroring the scheduler's steady state).

    Returns ``{"admission_chunk": (fn, args), "decode": (fn, args)}``
    pinned exactly as ``PagedContinuousScheduler`` pins them.
    """
    from repro.serving import pages_per_slot, serve_shardings
    cfg = model.cfg
    if cfg.kind in layout.FRONTEND_KINDS:
        raise ValueError(
            f"serve program is token-only; arch kind {cfg.kind!r} needs "
            "frontend inputs the request path does not carry")
    P = pages_per_slot(max_total, page_size)
    if cache_pages is None:
        cache_pages = slots * P + 1
    if prefill_chunk is None:
        prefill_chunk = -(-max_prompt // page_size) * page_size
    assert prefill_chunk % page_size == 0
    pdt = param_dtype_for(cfg)
    sh = serve_shardings(model, mesh, slots=slots, max_total=max_total,
                         dtype=dtype, param_dtype=pdt,
                         page_size=page_size, cache_pages=cache_pages,
                         rules=rules, cache_rules=cache_rules)
    params_abs, _ = model.abstract_params(dtype=pdt)
    cache_abs = model.abstract_paged_cache(slots, cache_pages, page_size,
                                           dtype)
    i32 = jnp.int32
    logits_abs = jax.ShapeDtypeStruct((slots, 1, cfg.padded_vocab), dtype)
    pos_abs = jax.ShapeDtypeStruct((slots,), i32)

    def admission_chunk(params, cache, logits, tokens, start, valid, row,
                        slot):
        c1, lg = model.prefill_chunk(params, cache, tokens, start, valid,
                                     row, slot, dtype=dtype)
        logits = jax.lax.dynamic_update_slice(
            logits, lg.astype(logits.dtype), (slot, 0, 0))
        return c1, logits

    adm = jax.jit(
        admission_chunk,
        in_shardings=(sh.params, sh.paged_cache, sh.logits,
                      sh.replicated, sh.replicated, sh.replicated,
                      sh.replicated, sh.replicated),
        out_shardings=(sh.paged_cache, sh.logits),
        donate_argnums=(1, 2),
    )
    adm_args = (params_abs, cache_abs, logits_abs,
                jax.ShapeDtypeStruct((1, prefill_chunk), i32),
                jax.ShapeDtypeStruct((), i32),
                jax.ShapeDtypeStruct((), i32),
                jax.ShapeDtypeStruct((P,), i32),
                jax.ShapeDtypeStruct((), i32))

    def decode(params, token, cache, pos, page_map, live):
        return model.decode_step_paged(params, token, cache, pos,
                                       page_map, live, dtype=dtype)

    dec = jax.jit(
        decode,
        in_shardings=(sh.params, sh.token, sh.paged_cache, sh.pos,
                      sh.page_map, sh.live),
        out_shardings=(sh.logits, sh.paged_cache),
        donate_argnums=(2,),
    )
    dec_args = (params_abs, jax.ShapeDtypeStruct((slots, 1), i32),
                cache_abs, pos_abs,
                jax.ShapeDtypeStruct((slots, P), i32),
                jax.ShapeDtypeStruct((slots,), jnp.bool_))
    return {"admission_chunk": (adm, adm_args), "decode": (dec, dec_args)}
