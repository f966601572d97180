"""``repro.obs`` on the profiler's clock (DESIGN.md §13).

* every span, instant and counter, from ``NULL_OBS`` and from a
  recording sink alike, is a ``repro.<name>`` event with its args in a
  ``jax.profiler`` session; ``NULL_OBS`` still keeps and writes nothing;
* a span's counters are read as it ends, and only when something
  records them;
* the TT-HF interval step names its parts (``local_sgd``, ``d2d_mix``,
  ``global_agg``, ``local_sgd_mix``) in its HLO ``op_name`` metadata, on
  the per-leaf and the fused-interval paths, and the scopes leave its
  outputs bitwise unchanged;
* the scheduler's jitted programs carry their names, and its spans
  carry their counters.
"""
import contextlib
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs.sink import NULL_OBS, make_obs
from repro.obs.trace import profiling


def _profile(tmp_path, body):
    """Run ``body()`` in a profiler session; {name: [stats dict]} of the
    host events whose names start with ``repro.``."""
    d = tmp_path / "prof"
    jax.profiler.start_trace(str(d))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)[0]
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.setdefault(ev.name, []).append(
                        (dict(ev.stats), ev.duration_ns))
    return out


def _instrument(obs, calls):
    def counters():
        calls.append(1)
        return {"done": 7}

    with obs.span("sched.step", counters=counters, rid=3) as o:
        assert o is obs
        o.instant("sched.admitted", rid=3, slot=1)
        o.counter("pages", free=5)


@pytest.mark.parametrize("sink", ["null", "recording"])
def test_spans_and_counters_reach_the_profiler(tmp_path, sink):
    obs = (NULL_OBS if sink == "null"
           else make_obs(str(tmp_path / "obs"), run_name="t"))
    calls = []
    evs = _profile(tmp_path, lambda: _instrument(obs, calls))
    (step, _), = evs["repro.sched.step"]
    assert step == {"rid": 3, "done": 7}          # counters at span end
    assert evs["repro.sched.admitted"][0][0] == {"rid": 3, "slot": 1}
    assert evs["repro.counter.pages"][0][0] == {"free": 5}
    assert calls == [1]
    if sink == "recording":
        obs.close()
        doc = json.loads((tmp_path / "obs" / "trace.json").read_text())
        by = {(e["ph"], e["name"]): e for e in doc["traceEvents"]}
        span = by[("X", "step")]
        assert span["cat"] == "sched"
        assert span["args"] == {"rid": 3, "done": 7}
        assert by[("C", "step")]["args"] == {"done": 7.0}
        assert by[("i", "admitted")]["cat"] == "sched"
    else:
        # the disabled sink kept nothing and wrote nothing
        assert sorted(p.name for p in tmp_path.iterdir()) == ["prof"]


def test_null_obs_without_profiler_reads_no_counters():
    assert not profiling()
    calls = []
    _instrument(NULL_OBS, calls)
    assert calls == []


def test_recording_sink_reads_counters_without_profiler(tmp_path):
    obs = make_obs(str(tmp_path / "obs"), run_name="t")
    calls = []
    _instrument(obs, calls)
    assert calls == [1]
    obs.close()


def test_profile_only_starts_the_session(tmp_path, monkeypatch):
    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: started.append(d))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    obs = make_obs(str(tmp_path / "obs"), profile=True)
    with obs.span("x"):
        pass
    obs.close()
    assert started == [str(tmp_path / "obs" / "jax_profile")]


# ---------------------------------------------------------------------------
# the interval step's named scopes
# ---------------------------------------------------------------------------

_SCOPES = ("local_sgd", "d2d_mix", "global_agg")


def _tthf_step(fused_interval, fused_kernel=None, agg_kind="picks"):
    from repro.configs import get_arch
    from repro.core.distributed import (
        TTHFScaleConfig, make_tthf_train_step, stack_replicas)
    from repro.models import build_model

    cfg = get_arch("qwen1.5-0.5b").reduced(num_layers=1, d_model=32,
                                           d_ff=64, vocab_size=128)
    model = build_model(cfg)
    scale = TTHFScaleConfig(replicas=4, cluster_size=2, tau=2,
                            consensus_every=1, gamma_d2d=2, lr=0.05,
                            sample_per_cluster=2 if agg_kind == "weights"
                            else 1)
    step, net = make_tthf_train_step(
        model, scale, dtype=jnp.float32, fused_interval=fused_interval,
        fused_kernel=fused_kernel)
    params = stack_replicas(model.init(jax.random.PRNGKey(0)), 4)
    if fused_interval:
        params = step.spec.flatten(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 2, 8), 0, 128)
    if agg_kind == "weights":
        agg = jnp.full((net.num_clusters, 2), 0.25, jnp.float32)
    else:
        agg = jnp.zeros((net.num_clusters,), jnp.int32)
    args = (params, {"tokens": toks, "labels": toks}, agg, jnp.asarray(0))
    return step, args


def _scopes(lowered) -> set:
    """Every path component of the HLO ops' ``op_name`` metadata."""
    text = lowered.as_text("hlo", debug_info=True)
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")}


@pytest.mark.parametrize("fused_interval,agg_kind",
                         [(False, "picks"), (True, "picks"),
                          (False, "weights")])
def test_interval_step_names_its_parts(fused_interval, agg_kind):
    step, args = _tthf_step(fused_interval, agg_kind=agg_kind)
    scopes = _scopes(jax.jit(step).lower(*args))
    assert set(_SCOPES) <= scopes
    assert "local_sgd_mix" not in scopes


def test_fused_kernel_block_end_is_named_local_sgd_mix():
    step, args = _tthf_step(True, fused_kernel=True)
    assert {"local_sgd_mix", "local_sgd", "global_agg"} <= _scopes(
        jax.jit(step).lower(*args))


@pytest.mark.parametrize("fused_interval", [False, True])
def test_scopes_leave_the_step_bitwise(fused_interval, monkeypatch):
    step, args = _tthf_step(fused_interval)
    got = jax.jit(step)(*args)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, args = _tthf_step(fused_interval)
    assert not set(_SCOPES) & _scopes(jax.jit(bare).lower(*args))
    want = jax.jit(bare)(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------

def _paged(obs=NULL_OBS):
    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serving import PagedContinuousScheduler

    cfg = get_arch("qwen1.5-0.5b").reduced(num_layers=1, d_model=32,
                                           d_ff=64, vocab_size=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = PagedContinuousScheduler(
        model, slots=2, max_prompt=16, max_total=32, page_size=8,
        prefill_chunk=8, temperature=0.0, seed=0, obs=obs)
    return sched, params


def test_scheduler_programs_carry_their_names():
    from repro.serving import ContinuousScheduler
    sched, params = _paged()
    names = {"serve_decode": sched._decode_jit,
             "serve_prefill_chunk": sched._chunk_jit,
             "serve_sample": sched._sample_jit}
    for name, fn in names.items():
        assert fn.__name__ == name
    lowered = sched._sample_jit.lower(sched._last_logits, None)
    assert "jit_serve_sample" in lowered.as_text()
    cont = ContinuousScheduler(sched.model, slots=2, max_prompt=8,
                               max_total=16)
    assert cont._decode.__name__ == "serve_decode"
    assert cont._admit_one.__name__ == "serve_admit"


def test_scheduler_step_spans_carry_counters(tmp_path):
    from repro.serving import Request
    sched, params = _paged()
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, 120, size=12).astype(
        np.int32), max_new=3) for i in range(3)]

    def body():
        for r in reqs:
            sched.submit(r)
        while sched.outstanding:
            sched.step(params)

    evs = _profile(tmp_path, body)
    assert sorted(s["rid"] for s, _ in evs["repro.sched.submit"]) \
        == [0, 1, 2]
    admitted = {s["rid"]: s for s, _ in evs["repro.sched.admitted"]}
    assert sorted(admitted) == [0, 1, 2]
    assert all(s["fresh_pages"] == 2 for s in admitted.values())
    steps = [s for s, _ in evs["repro.sched.step"]]
    last = steps[-1]
    assert last["decode_steps"] == sched.stats.decode_steps > 0
    assert last["prefill_chunks"] == sched.prefill_chunks == 6
    assert last["live_slots"] == 0 and last["queue_depth"] == 0
    assert last["free_pages"] == sched.table.num_free
    for name in ("sample", "readback", "emit", "decode_step", "admission",
                 "prefill_chunk"):
        assert f"repro.sched.{name}" in evs, name
    chunk = evs["repro.sched.prefill_chunk"][0][0]
    assert {"rid", "start", "valid", "slot"} <= set(chunk)
    assert sched.live_slots == 0


def test_scheduler_step_span_carries_operand_counters(tmp_path,
                                                      monkeypatch):
    """The bf16 operand copy (forced by patching the platform check: on a
    CPU it is exact only when the weights are rounded already) is built
    once and its bytes are held."""
    from repro.serving import Request, engine
    monkeypatch.setattr(engine, "_on_tpu", lambda p: True)
    sched, params = _paged()
    rng = np.random.default_rng(1)

    def body():
        for i in range(2):
            sched.submit(Request(rid=i, prompt=rng.integers(
                1, 120, size=10).astype(np.int32), max_new=3))
        while sched.outstanding:
            sched.step(params)

    steps = [s for s, _ in _profile(tmp_path, body)["repro.sched.step"]]
    assert len(steps) > 2
    want = sum(2 * w.size for k, w in params["layers"]["attn"].items()
               if k.startswith("w")) + \
        sum(2 * w.size for w in params["layers"]["mlp"].values())
    for s in steps:
        assert (s["operand_casts"], s["operand_bytes"]) == (1, want)


def test_scheduler_step_span_counts_kv_blocks(tmp_path):
    """kv_blocks_walked / kv_blocks_full on the step span equal a count
    by hand from the requests' lengths: the kernel (interpret mode) walks
    the 16-token blocks of each live slot's band, a 16-token window."""
    import dataclasses
    from repro.configs import get_arch
    from repro.kernels.paged_attn import block_pages
    from repro.models import build_model
    from repro.serving import PagedContinuousScheduler, Request

    cfg = dataclasses.replace(
        get_arch("starcoder2-3b").reduced(num_layers=1, d_model=64,
                                          d_ff=128, vocab_size=128),
        sliding_window=16)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ps, max_total, slots = 4, 64, 2
    sched = PagedContinuousScheduler(
        model, slots=slots, max_prompt=40, max_total=max_total,
        page_size=ps, prefill_chunk=8, temperature=0.0, seed=0,
        paged_kernel=True)
    P = max_total // ps
    bt = block_pages(ps, P, cfg.num_kv_heads, cfg.head_dim, 4, 16) * ps
    assert bt == 16 and P * ps // bt == 4
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, 120, size=n).astype(
        np.int32), max_new=m) for i, (n, m) in enumerate(
            [(5, 6), (30, 9), (17, 4), (40, 3)])]

    def body():
        for r in reqs:
            sched.submit(r)
        while sched.outstanding:
            sched.step(params)

    evs = _profile(tmp_path, body)
    last = [s for s, _ in evs["repro.sched.step"]][-1]
    # a request of n tokens out decodes at plen .. plen + n - 2, and its
    # band [pos - 15, pos] covers blocks (pos - 15) // 16 .. pos // 16
    want = sum(pos // bt - max(pos - 15, 0) // bt + 1
               for r in reqs for pos in range(len(r.prompt),
                                              len(r.prompt) + r.max_new - 1))
    assert all(r.done and len(r.out_tokens) == r.max_new for r in reqs)
    assert last["kv_blocks_walked"] == want == sched.kv_blocks_walked
    assert last["kv_blocks_full"] == slots * 4 * sched.stats.decode_steps
    assert last["kv_blocks_walked"] <= last["kv_blocks_full"]


# ---------------------------------------------------------------------------
# the trainer loop
# ---------------------------------------------------------------------------

def test_trainer_interval_spans(tmp_path):
    from repro.configs import get_arch
    from repro.core.distributed import TTHFScaleConfig
    from repro.train import ScaleTrainer, TrainerConfig

    cfg = get_arch("qwen1.5-0.5b").reduced(num_layers=1, d_model=32,
                                           d_ff=64, vocab_size=128)
    scale = TTHFScaleConfig(replicas=4, cluster_size=2, tau=2,
                            consensus_every=1, gamma_d2d=1, lr=0.05)
    tr = ScaleTrainer(cfg, scale, TrainerConfig(
        batch_per_replica=2, seq_len=8, intervals=3, eval_every=0)).init()
    tr.run(1)                                   # compile outside the trace
    evs = _profile(tmp_path, lambda: tr.run(3))
    intervals = [s for s, _ in evs["repro.train.interval"]]
    assert [s["interval"] for s in intervals] == [1, 2, 3]
    assert intervals[-1]["uplinks"] == tr.ledger.uplinks > 0
    assert intervals[-1]["d2d_msgs"] == tr.ledger.d2d_msgs > 0
    assert intervals[-1]["local_steps"] == tr.ledger.local_steps
    for name in ("batch", "resolve", "dispatch", "loss_read"):
        assert len(evs[f"repro.train.{name}"]) == 3, name
    # the prefetch worker builds the next batches on its own thread
    assert len(evs["repro.train.prefetch"]) >= 2
