"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Loads the cell's configuration, traffic and limits by the names in
``BENCHMARK.json``, makes weights and inputs from ``--seed``, warms up
every shape the window uses (set-up), measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints
one JSON object as its last line of standard output. With ``--trace 0``
its metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of (at most the first
``harness.TRACE_WINDOW_S`` seconds of) the window. Each number compared
is printed beside its limit as the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def program_path() -> None:
    """Make the system under test importable from the checkout."""
    src = str(harness.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


class Reading:
    """What a per-layer metric reader is handed."""

    def __init__(self, cell, outcome, trace, lo, hi, peaks, window_s):
        self.config = cell.config
        self.readings = outcome.readings
        self.trace = trace
        self.lo, self.hi = lo, hi          # traced window, trace clock
        self.peaks = peaks
        self.window_s = window_s
        self.flops = harness.flops_module(cell.config)


def per_layer(cell, outcome, profiler, devices):
    """(metrics, device fields, breakdown) of a traced run."""
    from bench import trace as tr
    from bench.peaks import peaks_for
    t = tr.load(profiler.path)
    win = tr.window(t, "bench.window")
    if win is None:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = win
    window_s = (hi - lo) * 1e-9
    r = Reading(cell, outcome, t, lo, hi, peaks_for(devices[0].device_kind),
                window_s)
    metrics = {}
    for m in cell.per_layer:
        value = harness.metric_reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"busy_s": tr.busy_ns(t, lo, hi) * 1e-9, "window_s": window_s}
    return metrics, device, tr.breakdown(t, lo, hi)


def main(argv=None) -> int:
    args = parse(argv)
    spec = harness.benchmark_spec()
    cell = harness.load_cell(spec, args.workload)
    program_path()
    devices = harness.require_devices(cell.chips)
    cache = harness.init_compile_cache()
    clock = harness.CompileClock()
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, harness.TRACE_WINDOW_S)
    ctx = harness.Context(
        cell=cell, seed=args.seed, seconds=seconds, devices=devices,
        clock=clock, t_start=T_START,
        profiler=harness.Profiler() if args.trace else None)
    ctx.note(workload=cell.name, seed=args.seed, seconds=seconds,
             compile_cache=cache, device_kind=devices[0].device_kind)
    import importlib
    runner = importlib.import_module(
        f"bench.runners.{cell.traffic['runner']}")
    outcome = runner.run(ctx)

    breakdown = None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    if args.trace:
        metrics, dev_fields, breakdown = per_layer(
            cell, outcome, ctx.profiler, devices)
        device.update(dev_fields)
        ctx.profiler.cleanup()
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": harness.finite(float(v)), "unit": units[k]}
                   for k, v in outcome.metrics.items() if k in units}
        missing = set(units) - set(metrics)
        if missing:
            raise RuntimeError(f"runner gave no {sorted(missing)}")
    harness.emit_info(ctx.info)

    correct = all(c.ok for c in outcome.checks) and outcome.failed == 0 \
        and all(math.isfinite(m["value"]) for m in metrics.values())
    checks = {c.name: {"value": c.value, "limit": c.limit}
              for c in outcome.checks}
    for c in outcome.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.NoChip as e:
        sys.exit(e.code)
