"""Sharded serving (DESIGN.md §14).

Fast: every registry config's cache/param logical-axes trees resolve
to VALID PartitionSpecs under the default serve rule tables on the
production and host mesh geometries (a mesh axis shards at most one
dimension, and only one it divides); the seq-fallback contract for
GQA configs whose head count does not divide ``model``; and the
mesh-threaded schedulers reproduce the single-device token streams on
a trivial (1, 1) mesh in-process.

Slow (subprocess, 8 forced host devices): data-parallel continuous
batching is BITWISE-identical to single-device (per-row computation is
unchanged — only placement differs), and tensor-parallel prefill +
decode logits match to numerical tolerance (reductions are split, so
only allclose is guaranteed).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import ARCHS, get_arch
from repro.models import build_model
from repro.serving import SERVE_CACHE_RULES, SERVE_PARAM_RULES

ALL_ARCHS = sorted(ARCHS)

# production multi-pod geometry (sizes only — AbstractMesh never
# touches devices, so the 1-CPU test session can resolve 512-chip specs)
MULTIPOD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
HOST8 = AbstractMesh((2, 4), ("data", "model"))


def _entries(spec, ndim):
    """Per-dimension mesh-axis tuples of a PartitionSpec, padded."""
    dims = list(spec) + [None] * (ndim - len(spec))
    return [() if e is None else ((e,) if isinstance(e, str) else tuple(e))
            for e in dims]


def _assert_valid(spec, shape, mesh, where=""):
    sizes = dict(mesh.shape)
    used = []
    for dim, axes in zip(shape, _entries(spec, len(shape))):
        prod = 1
        for m in axes:
            assert m in sizes, f"{where}: unknown mesh axis {m!r}"
            assert m not in used, f"{where}: mesh axis {m!r} used twice"
            used.append(m)
            prod *= sizes[m]
        assert dim % prod == 0, \
            f"{where}: dim {dim} not divisible by {prod} ({spec}, {shape})"


def _flat_axes_and_shapes(axes_tree, abs_tree):
    is_ax = lambda x: isinstance(x, tuple)  # noqa: E731
    flat_ax = jax.tree_util.tree_flatten(axes_tree, is_leaf=is_ax)[0]
    flat_ab = jax.tree_util.tree_flatten(abs_tree)[0]
    assert len(flat_ax) == len(flat_ab)
    return list(zip(flat_ax, flat_ab))


@pytest.mark.parametrize("mesh", [MULTIPOD, HOST8],
                         ids=["multipod", "host8"])
@pytest.mark.parametrize("name", ALL_ARCHS)
def test_cache_axes_resolve_every_arch(name, mesh):
    """Satellite: every leaf of cache_logical_axes_tree resolves to a
    valid PartitionSpec under SERVE_CACHE_RULES for every registry
    config — full size, production slot geometry."""
    model = build_model(get_arch(name))
    slots, seq = 16, 2048
    axes = model.cache_axes()
    abs_c = model.abstract_cache(slots, seq, jnp.bfloat16)
    any_model = False
    for ax, ab in _flat_axes_and_shapes(axes, abs_c):
        spec = SERVE_CACHE_RULES.spec_for_shape(tuple(ax), tuple(ab.shape),
                                                mesh)
        _assert_valid(spec, ab.shape, mesh, where=f"{name} cache {ax}")
        any_model = any_model or any(
            "model" in e for e in _entries(spec, len(ab.shape)))
    # a full-size config must never serve with a fully model-replicated
    # cache: heads take the model axis, or the 2048 seq fallback does
    assert any_model, f"{name}: no cache leaf sharded over 'model'"


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_param_axes_resolve_every_arch(name):
    model = build_model(get_arch(name))
    abs_p, axes = model.abstract_params(dtype=jnp.bfloat16)
    for ax, ab in _flat_axes_and_shapes(axes, abs_p):
        spec = SERVE_PARAM_RULES.spec_for_shape(tuple(ax), tuple(ab.shape),
                                                MULTIPOD)
        _assert_valid(spec, ab.shape, MULTIPOD,
                      where=f"{name} param {ax}")


def test_gqa_seq_fallback_on_production_mesh():
    """maverick's kv_heads=8 does not divide model=16: the KV cache
    must fall back to sharding the sequence dim over 'model' (table
    order is the priority), never silently replicate."""
    cfg = get_arch("llama4-maverick-400b-a17b")
    assert cfg.num_kv_heads % 16 != 0     # the premise of the fallback
    spec = SERVE_CACHE_RULES.spec_for_shape(
        ("cache_batch", "cache_seq", "cache_kv_heads", "head_dim"),
        (16, 2048, cfg.num_kv_heads, cfg.head_dim), MULTIPOD)
    assert spec[1] == "model"             # seq picked up the model axis
    assert spec[2] is None                # heads replicated (8 % 16)
    # …and a config whose head count DOES divide keeps heads on model
    spec2 = SERVE_CACHE_RULES.spec_for_shape(
        ("cache_batch", "cache_seq", "cache_kv_heads", "head_dim"),
        (16, 2048, 16, 64), MULTIPOD)
    assert spec2[2] == "model"
    assert spec2[1] is None


def _reduced(name="qwen1.5-0.5b"):
    cfg = get_arch(name).reduced()
    if cfg.kind == "hybrid":
        cfg = dataclasses.replace(cfg, attention_window=16)
    if cfg.moe_num_experts:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    return cfg


def _poisson_trace(cfg, n_req, max_prompt, seed=0):
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    arrivals, step = [], 0
    for rid in range(n_req):
        plen = int(rng.integers(2, max_prompt + 1))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).astype(np.int32)
        arrivals.append((step, Request(rid=rid, prompt=prompt, max_new=6)))
        step += int(rng.poisson(1.5))
    return arrivals


def _run_tokens(model, params, mesh, kind="continuous", slots=4,
                n_req=8, max_prompt=12, max_total=32):
    from repro.serving import make_scheduler, run_trace, shard_params
    p = params if mesh is None else shard_params(params, model, mesh)
    arrivals = _poisson_trace(model.cfg, n_req, max_prompt)
    sched = make_scheduler(kind, model, slots=slots, max_prompt=max_prompt,
                           max_total=max_total, temperature=0.0, seed=0,
                           mesh=mesh)
    stats = run_trace(sched, p, arrivals)
    assert stats.requests_done == n_req
    return {req.rid: list(req.out_tokens) for _, req in arrivals}


@pytest.mark.parametrize("kind", ["continuous", "wave"])
def test_scheduler_mesh_threading_parity_one_device(kind):
    """The mesh code path end-to-end in-process: a (1, 1) mesh over the
    single test device must reproduce the no-mesh token streams
    exactly (and exercises sharded init_cache/write_cache_slot/jit
    out_shardings without needing forced host devices)."""
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((1, 1), ("data", "model"))
    model = build_model(_reduced())
    params = model.init(jax.random.PRNGKey(0))
    base = _run_tokens(model, params, None, kind=kind)
    sharded = _run_tokens(model, params, mesh, kind=kind)
    assert base == sharded


@pytest.mark.slow
def test_sharded_smoke_8dev_subprocess():
    """8 simulated host devices (the CI serving-shard-smoke config):
    data-parallel continuous batching is bitwise-identical to
    single-device; tensor-parallel logits allclose."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        capture_output=True, text=True, timeout=1200, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.splitlines()[-1])
    assert rec["devices"] == 8
    assert rec["done_single"] == rec["done_data"] == 16
    assert rec["bitwise_equal"], \
        "data-parallel token stream diverged from single-device"
    assert rec["tp_max_abs_diff"] < 1e-4, rec


# ---------------------------------------------------------------------------
# child entry for the slow smoke (runs under 8 forced host devices)
# ---------------------------------------------------------------------------

def _child_main():
    from repro.launch.mesh import make_serve_mesh
    from repro.serving import serve_shardings, shard_params

    assert len(jax.devices()) == 8, jax.devices()
    cfg = _reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # 1) scheduler trace: single-device vs data-parallel (slots=8 over
    #    data=8) — per-row computation unchanged, must be bitwise equal
    kw = dict(kind="continuous", slots=8, n_req=16, max_prompt=16,
              max_total=48)
    t_single = _run_tokens(model, params, None, **kw)
    t_data = _run_tokens(model, params, make_serve_mesh("data"), **kw)

    # 2) tensor-parallel logits vs single-device, teacher-forced with
    #    one fixed token sequence so a sampling flip cannot cascade
    B, T, G = 8, 16, 4
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    forced = jax.random.randint(jax.random.PRNGKey(2), (G, B, 1), 0,
                                cfg.vocab_size)

    def direct(mesh):
        from contextlib import nullcontext
        ctx, p, kw_pf, kw_dec = nullcontext(), params, {}, {}
        if mesh is not None:
            sh = serve_shardings(model, mesh, slots=B, max_total=T + G,
                                 dtype=jnp.float32)
            ctx = mesh
            p = shard_params(params, model, mesh)
            kw_pf = {"out_shardings": (sh.logits, sh.cache,
                                       sh.replicated)}
            kw_dec = {"out_shardings": (sh.logits, sh.cache)}
        pf = jax.jit(lambda p_, b: model.prefill(
            p_, b, dtype=jnp.float32, cache_dtype=jnp.float32,
            cache_len=T + G), **kw_pf)
        dec = jax.jit(lambda p_, t_, c, s: model.decode_step(
            p_, t_, c, s, dtype=jnp.float32), **kw_dec)
        outs = []
        with ctx:
            lg, cache, pos = pf(p, {"tokens": tokens})
        outs.append(np.asarray(lg))
        for i in range(G):
            with ctx:
                lg, cache = dec(p, forced[i], cache, pos)
            pos = pos + 1
            outs.append(np.asarray(lg))
        return np.concatenate(outs, axis=1)

    base = direct(None)
    tp = direct(make_serve_mesh("2x4"))
    print(json.dumps({
        "devices": len(jax.devices()),
        "done_single": len(t_single), "done_data": len(t_data),
        "bitwise_equal": bool(t_single == t_data),
        "tp_max_abs_diff": float(np.max(np.abs(base - tp))),
    }))


if __name__ == "__main__" and "--child" in sys.argv:
    _child_main()
