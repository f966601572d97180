"""The reduction from profiler trace to metrics (``bench/trace.py``),
checked on synthetic events and on a recorded excerpt: the first 40 ms
of a traced window of ``starcoder2-3b.serve.decode_long`` on one TPU v5e
(device ops of the ``XLA Ops`` line and the benchmark's host spans)."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np

from bench import layer_readers, trace

EXCERPT = Path(__file__).parent / "data" / "decode_long_trace_excerpt.json"


def profile(planes):
    """A stand-in for ``jax.profiler.ProfileData`` from plain lists."""
    return NS(planes=[NS(name=p["name"], lines=[
        NS(name=l["name"], events=[NS(name=n, start_ns=s, duration_ns=d)
                                   for n, s, d in l["events"]])
        for l in p["lines"]]) for p in planes])


def recorded():
    d = json.loads(EXCERPT.read_text())
    return trace.from_profile(profile(d["planes"])), d["window_ns"]


def brute_busy(ops, lo, hi, step=100.0):
    grid = np.zeros(int((hi - lo) / step), bool)
    for s, e, _ in ops:
        a, b = int(max(s - lo, 0) / step), int(min(e - lo, hi - lo) / step)
        grid[a:b] = True
    return grid.sum() * step


def test_recorded_excerpt_busy_and_idle():
    tr, (lo, hi) = recorded()
    ops = tr.ops[0]
    assert len(ops) > 300 and trace.window(tr, "bench.window")
    busy = trace.busy_ns(tr, lo, hi)
    assert abs(busy - brute_busy(ops, lo, hi)) < 1e-3 * (hi - lo)
    assert abs(trace.idle_share(tr, lo, hi) - (1 - busy / (hi - lo))) < 1e-12


def test_recorded_excerpt_self_times_tile_the_busy_time():
    tr, (lo, hi) = recorded()
    own = sum(trace.op_seconds(tr, lo, hi).values()) * 1e9
    assert abs(own - trace.busy_ns(tr, lo, hi)) < 0.01 * (hi - lo)


def test_recorded_excerpt_finds_the_paged_decode_kernel():
    from bench.harness import load_file_module, BENCH_DIR
    reader = load_file_module(BENCH_DIR / "metrics" /
                              "paged_decode_roofline.py", "roofline")
    tr, (lo, hi) = recorded()
    k = trace.op_seconds(tr, lo, hi, match=reader.is_kernel)
    assert k and all("custom-call" in trace.short_name(n) for n in k)


def test_synthetic_nesting_gaps_and_names():
    ops = [(0.0, 100.0, "%while.1 = (s32[]) while(%t), body=%b"),
           (10.0, 40.0, "%fusion.2 = f32[4]{0} fusion(%a), kind=kLoop"),
           (50.0, 60.0, "%copy.3 = f32[4]{0} copy(%x)"),
           (150.0, 170.0, "%fusion.2 = f32[4]{0} fusion(%a), kind=kLoop")]
    tr = trace.Trace(ops={0: ops},
                     spans=[(0.0, 200.0, "bench.window"),
                            (100.0, 145.0, "bench.wait"),
                            (145.0, 200.0, "bench.tick")])
    own = trace.op_seconds(tr, 0.0, 200.0)
    assert np.isclose(own[ops[0][2]] * 1e9, 60.0)      # 100 - 30 - 10
    assert np.isclose(own[ops[1][2]] * 1e9, 50.0)      # both fusion.2 runs
    assert trace.short_name(ops[0][2]) == "%while.1 (while)"
    assert trace.short_name(ops[2][2]) == "%copy.3 (copy)"
    assert np.isclose(trace.idle_share(tr, 0.0, 200.0), 0.4)
    b = trace.breakdown(tr, 0.0, 200.0)
    assert [n for n, _ in b["idle_gaps"]] == ["bench.wait", "bench.tick"]
    assert np.allclose([s for _, s in b["idle_gaps"]], [50e-9, 30e-9])
    r = NS(readings={"ticks": [(145e-9, 200e-9, True),
                               (100e-9, 145e-9, False)]},
           trace=tr, lo=0.0, hi=200.0)
    assert np.isclose(layer_readers.host_ms_per_tick(r), 35e-6)
