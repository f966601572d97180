"""The scale-mode raw-speed pass (DESIGN.md §12): fused-interval flat
buffer vs the reference step (bitwise in f32), buffer donation on the
trainer's jitted step, and the prefetch loader's determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.distributed import (
    FlatParamSpec, TTHFScaleConfig, make_tthf_train_step, stack_replicas)
from repro.models import build_model
from repro.train import PrefetchLoader, ScaleTrainer, TrainerConfig

# deliberately NON-lane-aligned (d_model=64, odd leaf sizes): the
# bitwise contract must not depend on shape luck
_CFG = get_arch("qwen1.5-0.5b").reduced(num_layers=2, d_model=64,
                                        d_ff=128, vocab_size=128)
_R, _TAU = 4, 4


def _model():
    return build_model(_CFG)


def _scale(**kw):
    kw.setdefault("replicas", _R)
    kw.setdefault("cluster_size", 2)
    kw.setdefault("tau", _TAU)
    kw.setdefault("consensus_every", 2)
    kw.setdefault("gamma_d2d", 2)
    kw.setdefault("lr", 0.05)
    return TTHFScaleConfig(**kw)


def _batch(seed=1, tau=_TAU, T=16):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (tau, _R, 2, T),
                              0, _CFG.vocab_size)
    return {"tokens": toks, "labels": toks}


def _bitwise(tree_a, tree_b):
    return all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(tree_a),
                               jax.tree.leaves(tree_b)))


# ---------------------------------------------------------------------------
# FlatParamSpec
# ---------------------------------------------------------------------------

def test_flat_spec_roundtrip():
    model = _model()
    spec = FlatParamSpec.for_model(model)
    assert spec.padded % 128 == 0 and spec.padded >= spec.total
    params = stack_replicas(model.init(jax.random.PRNGKey(0)), _R)
    flat = spec.flatten(params)
    assert flat.shape == (_R, spec.rows, 128) and flat.dtype == jnp.float32
    # pads zero (exactly the non-parameter entries), roundtrip exact
    assert np.count_nonzero(np.asarray(flat)) == sum(
        np.count_nonzero(np.asarray(l)) for l in jax.tree.leaves(params))
    assert _bitwise(params, spec.unflatten(flat))
    assert _bitwise(jax.tree.map(lambda l: l[2], params),
                    spec.unflatten_one(flat[2]))


def test_flat_spec_rejects_mixed_dtypes():
    with pytest.raises(AssertionError, match="uniform param dtype"):
        FlatParamSpec.for_tree({"a": jnp.zeros((3,), jnp.float32),
                                "b": jnp.zeros((3,), jnp.bfloat16)})


# ---------------------------------------------------------------------------
# fused interval == reference interval, bitwise in f32
# ---------------------------------------------------------------------------

def _run_pair(sync="tthf", agg=None, scale=None, hierarchy=None,
              refreshable=False, refresh=None, fused_kernel=None,
              intervals=2):
    model = _model()
    scale = scale or _scale()
    kw = dict(dtype=jnp.float32, sync=sync, hierarchy=hierarchy,
              refreshable=refreshable)
    ref_step, net = make_tthf_train_step(model, scale, **kw)
    fus_step, _ = make_tthf_train_step(model, scale, fused_interval=True,
                                       fused_kernel=fused_kernel, **kw)
    spec = fus_step.spec
    params = stack_replicas(model.init(jax.random.PRNGKey(0)), _R)
    flat = spec.flatten(params)
    if agg is None:
        agg = jnp.asarray([1, 0], jnp.int32)
    batch = _batch(tau=scale.tau)
    jref, jfus = jax.jit(ref_step), jax.jit(fus_step)
    losses = []
    for i in range(intervals):
        args = (jnp.asarray(i),) + (() if refresh is None else (refresh,))
        params, l_ref = jref(params, batch, agg, *args)
        flat, l_fus = jfus(flat, batch, agg, *args)
        losses.append((float(l_ref), float(l_fus)))
    return params, spec.unflatten(flat), losses


@pytest.mark.parametrize("sync", ["tthf", "star", "local"])
def test_fused_interval_bitwise_across_sync(sync):
    p_ref, p_fus, losses = _run_pair(sync=sync)
    assert all(a == b for a, b in losses)
    assert _bitwise(p_ref, p_fus)


def test_fused_interval_bitwise_weights_agg():
    # sample_per_cluster > 1 routes through the (N, s) weight-matrix
    # aggregation form
    scale = _scale(sample_per_cluster=2)
    w = jnp.asarray([[0.3, 0.2], [0.0, 0.5]], jnp.float32)
    p_ref, p_fus, losses = _run_pair(agg=w, scale=scale)
    assert all(a == b for a, b in losses)
    assert _bitwise(p_ref, p_fus)


def test_fused_interval_bitwise_matrix_agg():
    # a non-flat hierarchy routes through the composed (R, R) device
    # matrix form
    from repro.configs.base import HierarchyConfig
    h = HierarchyConfig(levels=3, taus=(_TAU, 2 * _TAU), sample=(1, 0))
    rng = np.random.default_rng(0)
    M = rng.random((_R, _R))
    M = jnp.asarray(M / M.sum(1, keepdims=True), jnp.float32)
    p_ref, p_fus, losses = _run_pair(agg=M, hierarchy=h)
    assert all(a == b for a, b in losses)
    assert _bitwise(p_ref, p_fus)


def test_fused_interval_bitwise_refreshable():
    # netsim dynamics: per-interval consensus-matrix refresh feeds the
    # once-traced step
    from repro.core.mixing import build_mixing_plan, refresh_matrices
    scale = _scale()
    net = scale.network()
    plan = build_mixing_plan(net, scale.gamma_d2d, backend="fused")
    refresh = refresh_matrices(plan, np.asarray(net.V))
    w = jnp.asarray([[0.5, 0.0], [0.0, 0.5]], jnp.float32)
    p_ref, p_fus, losses = _run_pair(agg=w, refreshable=True,
                                     refresh=refresh, scale=scale)
    assert all(a == b for a, b in losses)
    assert _bitwise(p_ref, p_fus)


def test_fused_interval_rounds_backend_matches_reference():
    # non-fused_power backends keep exact per-event semantics on the
    # flat buffer (no W to collapse into)
    scale = _scale(consensus_mode="rounds")
    p_ref, p_fus, losses = _run_pair(scale=scale)
    assert all(a == b for a, b in losses)
    assert _bitwise(p_ref, p_fus)


def test_fused_interval_kernel_path_close():
    """fused_kernel=True exercises the Pallas block-end (interpret mode
    on CPU). Its inline last-step grad may re-vectorize, so this path
    carries the kernel tolerance, not the bitwise contract."""
    p_ref, p_fus, losses = _run_pair(fused_kernel=True, intervals=1)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_fus)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)
    for a, b in losses:
        assert abs(a - b) < 1e-6


def test_fused_interval_pad_stays_zero():
    model = _model()
    scale = _scale()
    step, _ = make_tthf_train_step(model, scale, dtype=jnp.float32,
                                   fused_interval=True)
    spec = step.spec
    assert spec.padded > spec.total         # rows pad to ROW_ALIGN
    flat = spec.flatten(stack_replicas(model.init(jax.random.PRNGKey(0)),
                                       _R))
    flat, _ = jax.jit(step)(flat, _batch(), jnp.asarray([1, 0], jnp.int32),
                            jnp.asarray(0))
    # every pad entry is zero iff re-packing the unpacked tree is exact
    assert np.array_equal(np.asarray(flat),
                          np.asarray(spec.flatten(spec.unflatten(flat))))


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------

def _mk_trainer(tmp_path, **kw):
    t = TrainerConfig(batch_per_replica=2, seq_len=16, intervals=2,
                      eval_every=0, ckpt_dir=str(tmp_path), **kw)
    return ScaleTrainer(_CFG, _scale(), t)


def test_trainer_step_donates_param_buffer(tmp_path):
    tr = _mk_trainer(tmp_path).init()
    batch = tr._interval_batch()
    args = (tr.params, batch, jnp.asarray([1, 0], jnp.int32),
            jnp.asarray(0))
    lowered = tr._step.lower(*args)
    # the params buffer is aliased to the output in the lowered module…
    assert "tf.aliasing_output" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    if mem is not None and hasattr(mem, "alias_size_in_bytes"):
        param_bytes = sum(np.asarray(l).nbytes
                          for l in jax.tree.leaves(tr.params))
        assert mem.alias_size_in_bytes >= param_bytes
    # …and the donated buffer is actually invalidated by execution
    old = tr.params
    tr.run(1)
    with pytest.raises(RuntimeError):
        _ = np.asarray(jax.tree.leaves(old)[0]) + 0


def test_trainer_donate_off_keeps_buffer(tmp_path):
    tr = _mk_trainer(tmp_path, donate=False).init()
    old = tr.params
    tr.run(1)
    _ = [np.asarray(l) for l in jax.tree.leaves(old)]   # still readable


def test_donation_halves_live_param_buffers(tmp_path):
    """The memory claim behind donate=True: an undonated step must keep
    input AND output param buffers live (2x), a donated step aliases
    them (1x). Compare the compiled executables' argument aliasing."""
    tr_d = _mk_trainer(tmp_path).init()
    tr_u = _mk_trainer(tmp_path, donate=False).init()
    batch = tr_d._interval_batch()
    args = (tr_d.params, batch, jnp.asarray([1, 0], jnp.int32),
            jnp.asarray(0))
    txt_d = tr_d._step.lower(*args).as_text()
    txt_u = tr_u._step.lower(*args).as_text()
    assert "tf.aliasing_output" in txt_d
    assert "tf.aliasing_output" not in txt_u


# ---------------------------------------------------------------------------
# prefetch loader
# ---------------------------------------------------------------------------

def test_prefetch_loader_preserves_order_and_end():
    src = iter(range(7))
    with PrefetchLoader(lambda: next(src), depth=2,
                        put=lambda x: x) as loader:
        got = [loader.get() for _ in range(7)]
        assert got == list(range(7))
        with pytest.raises(StopIteration):
            loader.get()


def test_prefetch_loader_surfaces_worker_error():
    def boom():
        raise ValueError("bad batch")
    loader = PrefetchLoader(boom, put=lambda x: x)
    with pytest.raises(ValueError, match="bad batch"):
        loader.get()
    loader.close()


def test_prefetched_run_matches_synchronous(tmp_path):
    sync_tr = _mk_trainer(tmp_path, prefetch=False).run()
    pre_tr = _mk_trainer(tmp_path, prefetch=True).run()
    assert _bitwise(sync_tr.params, pre_tr.params)
    assert sync_tr._train_draws == pre_tr._train_draws


def test_prefetched_batches_identical_to_interval_batch():
    """The loader consumes the SAME build fn in the same order — the
    batch stream is byte-identical to the synchronous path's."""
    t = TrainerConfig(batch_per_replica=2, seq_len=16)
    a = ScaleTrainer(_CFG, _scale(), t)
    b = ScaleTrainer(_CFG, _scale(), t)
    ref = [a._interval_batch() for _ in range(3)]
    with PrefetchLoader(b._build_interval_batch, depth=1) as loader:
        got = [loader.get() for _ in range(3)]
    for r, g in zip(ref, got):
        for k in r:
            assert np.array_equal(np.asarray(r[k]), np.asarray(g[k]))


# ---------------------------------------------------------------------------
# trainer end-to-end: fused carrier
# ---------------------------------------------------------------------------

def test_trainer_fused_interval_matches_straight(tmp_path):
    straight = _mk_trainer(tmp_path, donate=False, prefetch=False).run()
    fused = _mk_trainer(tmp_path, fused_interval=True).run()
    assert fused._spec is not None
    assert _bitwise(straight.params,
                    fused._spec.unflatten(fused.params))
    # eval goes through the same global model
    assert straight.evaluate() == fused.evaluate()


def test_trainer_fused_checkpoint_cross_mode(tmp_path):
    fused = _mk_trainer(tmp_path, fused_interval=True).run()
    p = fused.save(os.path.join(str(tmp_path), "ck.npz"))
    straight = _mk_trainer(tmp_path, donate=False, prefetch=False)
    straight.restore(p)
    assert _bitwise(straight.params,
                    fused._spec.unflatten(fused.params))
    assert straight.interval == fused.interval
    assert straight._train_draws == fused._train_draws
