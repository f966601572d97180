"""The readers of what the program records in a profile
(``bench/program_spans.py``), on synthetic profiles: the program's
``repro.*`` host spans with their args, and device ops whose stats
carry the step's scopes."""
from __future__ import annotations

from types import SimpleNamespace as NS

import numpy as np

from bench import program_spans as ps
from bench import trace

MAIN, WORKER = 0, 1            # host line indices


def ev(name, s, d, **stats):
    return NS(name=name, start_ns=s, duration_ns=d,
              stats=list(stats.items()))


def profile(ops, host, modules=()):
    """``host``: {line index: [events]}; ``ops``, ``modules``: device-0
    op and program events."""
    lines = [NS(name="python", events=host.get(i, []))
             for i in range(max(host) + 1)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=list(modules)),
            NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=lines)])


def reading(pd, lo=0.0, hi=1000.0, op_names=None):
    """A reading as the harness builds it, with the program attached."""
    r = NS(trace=trace.from_profile(pd), lo=lo, hi=hi)
    r.program = ps.from_profile(pd, op_names)
    return r


def test_breakdown_names_gaps_by_innermost_program_span():
    ops = [ev("%fusion.1 = f32[4]{0} fusion(%a)", 0.0, 100.0),
           ev("%fusion.2 = f32[4]{0} fusion(%a)", 300.0, 100.0),
           ev("%fusion.3 = f32[4]{0} fusion(%a)", 450.0, 550.0)]
    host = {MAIN: [ev("bench.window", 0.0, 1000.0),
                   ev("repro.train.interval", 90.0, 330.0, interval=0),
                   ev("repro.train.loss_read", 110.0, 180.0),
                   ev("repro.train.batch", 400.0, 60.0)],
            # the prefetch worker: covers both gaps, yet never names one
            WORKER: [ev("repro.train.prefetch", 100.0, 360.0)]}
    r = reading(profile(ops, host))
    before = trace.breakdown(r.trace, r.lo, r.hi)
    assert [n for n, _ in before["idle_gaps"]] == ["bench.window"] * 2
    assert ps.program(r) is r.program
    b = trace.breakdown(r.trace, r.lo, r.hi)
    assert [n for n, _ in b["idle_gaps"]] == ["repro.train.loss_read",
                                              "repro.train.batch"]
    assert np.allclose([s for _, s in b["idle_gaps"]], [200e-9, 50e-9])
    ps.program(r)                               # attached once only
    assert len(r.trace.spans) == 4


def test_host_ms_per_interval_reads_idle_time_in_intervals():
    ops = [ev("%a = f32[] add()", 0.0, 100.0),
           ev("%a = f32[] add()", 150.0, 100.0),
           ev("%a = f32[] add()", 400.0, 100.0)]
    host = {MAIN: [ev("bench.window", 0.0, 1000.0),
                   ev("repro.train.interval", 50.0, 200.0, interval=0),
                   ev("repro.train.interval", 250.0, 200.0, interval=1)],
            WORKER: [ev("repro.train.interval", 600.0, 100.0)]}
    r = reading(profile(ops, host))
    # 50 idle ns in the first (100-150), 150 in the second (250-400)
    assert np.isclose(ps.host_ms_per_interval(r), 100e-6)


def test_sync_device_share_reads_scoped_self_time():
    scope = "jit(step)/while/body/closed_call/{}/mul"
    op_names = {"jit_step(7)": {
        "while.1": "jit(step)/while",
        "fusion.1": scope.format("local_sgd"),
        "fusion.2": scope.format("d2d_mix"),
        "fusion.3": "jit(step)/global_agg/select_n",
        "fusion.4": scope.format("local_sgd_mix")},
        # another program's instruction of the same name: no scope
        "jit_other(8)": {"fusion.2": "jit(other)/mul"}}
    ops = [ev("%while.1 = () while()", 0.0, 400.0),
           ev("%fusion.1 = f32[] fusion()", 10.0, 190.0),
           ev("%fusion.2 = f32[] fusion()", 200.0, 100.0),
           ev("%fusion.3 = f32[] fusion()", 500.0, 50.0),
           ev("%fusion.4 = f32[] fusion()", 600.0, 50.0),
           ev("%copy.5 = f32[] copy()", 700.0, 100.0),
           ev("%fusion.2 = f32[] fusion()", 900.0, 100.0)]
    modules = [ev("jit_step(7)", 0.0, 800.0), ev("jit_other(8)", 900.0, 100.0)]
    host = {MAIN: [ev("bench.window", 0.0, 1000.0)]}
    r = reading(profile(ops, host, modules), op_names=op_names)
    assert [o[3] for o in r.program.ops[0]] == [
        None, "local_sgd", "d2d_mix", "global_agg", "local_sgd_mix", None,
        None]
    # (100 d2d_mix + 50 global_agg) / 700 busy
    assert np.isclose(ps.sync_device_share(r), 100.0 * 150 / 700)
    # a program found by its name alone, where the ids differ
    renamed = {"jit_step(1)": op_names["jit_step(7)"]}
    r = reading(profile(ops, host, modules), op_names=renamed)
    assert r.program.ops[0][2][3] == "d2d_mix"
    # an op cut by the window counts by its share inside
    r = reading(profile(ops, host, modules), hi=525.0, op_names=op_names)
    assert np.isclose(ps.sync_device_share(r), 100.0 * 125 / 425)


def test_hlo_op_names_reads_a_real_profile(tmp_path):
    """The wire-format reader against an ``.xplane.pb`` this JAX writes:
    each program's instructions with their ``op_name``."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("d2d_mix"):
            y = jnp.sin(x) @ x
        with jax.named_scope("global_agg"):
            return y.sum(0)

    x = jnp.ones((16, 16))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    with open(path, "rb") as f:
        names = ps.hlo_op_names(f.read())
    (program,) = [p for p in names if p.startswith("jit_step(")]
    scopes = {ps.scope_of(n) for n in names[program].values()}
    assert {"d2d_mix", "global_agg"} <= scopes


def test_step_host_ms_reads_steps_that_did_work():
    ops = [ev("%a = f32[] add()", 0.0, 100.0),
           ev("%a = f32[] add()", 150.0, 100.0),
           ev("%a = f32[] add()", 300.0, 100.0)]
    step = "repro.sched.step"
    host = {MAIN: [ev("bench.window", 0.0, 1000.0),
                   ev(step, 10.0, 100.0, decode_steps=1, prefill_chunks=0),
                   ev(step, 120.0, 60.0, decode_steps=2, prefill_chunks=0),
                   ev(step, 200.0, 50.0, decode_steps=2, prefill_chunks=0),
                   ev(step, 260.0, 80.0, decode_steps=2, prefill_chunks=1)]}
    r = reading(profile(ops, host))
    # the first step has none before it; the third did no work;
    # idle: 120-150 = 30 and 260-300 = 40
    assert np.isclose(ps.step_host_ms(r), 35e-6)
    # the chat mix's SSM scheduler counts prefills the same way
    r = reading(profile(ops, {MAIN: [
        ev("bench.window", 0.0, 1000.0),
        ev(step, 10.0, 100.0, decode_steps=1, prefills=0),
        ev(step, 120.0, 60.0, decode_steps=1, prefills=1)]}))
    assert np.isclose(ps.step_host_ms(r), 30e-6)


def test_queue_wait_p95_pairs_submit_and_admission_by_rid():
    host = {MAIN: [ev("bench.window", 100.0, 900.0),
                   ev("repro.sched.submit", 50.0, 0.0, rid=0),   # before
                   ev("repro.sched.admitted", 120.0, 0.0, rid=0, slot=0),
                   ev("repro.sched.submit", 200.0, 0.0, rid=1),
                   ev("repro.sched.admitted", 210.0, 0.0, rid=1, slot=1),
                   ev("repro.sched.submit", 300.0, 0.0, rid=2),
                   ev("repro.sched.admitted", 500.0, 0.0, rid=2, slot=0),
                   ev("repro.sched.submit", 900.0, 0.0, rid=3)]}
    ops = [ev("%a = f32[] add()", 100.0, 10.0)]
    r = reading(profile(ops, host), lo=100.0, hi=1000.0)
    # waits 10, 200, and 100 (never admitted: waits to the trace's end)
    want = np.percentile([10.0, 200.0, 100.0], 95) * 1e-6
    assert np.isclose(ps.queue_wait_ms_p95(r), want)


def test_a_program_without_spans_reads_nothing():
    ops = [ev("%a = f32[] add()", 0.0, 10.0)]
    r = reading(profile(ops, {MAIN: [ev("bench.window", 0.0, 100.0)]}),
                hi=100.0)
    for read in (ps.sync_device_share, ps.host_ms_per_interval,
                 ps.step_host_ms, ps.queue_wait_ms_p95):
        assert read(r) is None
    assert trace.breakdown(r.trace, r.lo, r.hi)["idle_gaps"][0][0] \
        == "bench.window"


def test_no_profile_reads_nothing(monkeypatch, tmp_path):
    from bench import harness
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "none")
    r = NS(trace=trace.Trace(), lo=0.0, hi=1.0)
    assert ps.program(r) is None and ps.step_host_ms(r) is None


def test_traced_run_reads_the_program_on_the_cpu(monkeypatch, capsys):
    """A whole ``--trace 1`` run of the small chat cell on the host: the
    program's spans are read (the CPU trace has no device ops, so only
    the queue wait, which needs none, is non-null) and nothing raises."""
    import json

    import jax

    from bench import harness, peaks, run
    from bench.tests import small
    cell = small.chat_cell()
    monkeypatch.setattr(harness, "load_cell", lambda spec, name: cell)
    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(peaks, "peaks_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    assert run.main(["--workload", cell.name, "--seed", str(2**33 + 7),
                     "--seconds", "2", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metrics"]["queue_wait_ms_p95.chat"]["value"] >= 0.0
    assert "step_host_ms.chat" not in line["metrics"]
