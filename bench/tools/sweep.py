"""Find an open-loop cell's knee: the highest mean rate at which the
queue does not grow over the window. One process builds and warms the
cell's server once, then drives one window per rate (no output check).

    python3 -m bench.tools.sweep --workload <cell> --seed <n> \\
        --seconds <s> --rates 8,12,16

Prints one JSON line per rate: the end-to-end tails, the tokens per
second served, the queue left at the window's close, and the p95 time
to first token of the requests due in the window's last quarter.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args(argv)

    from bench import generate, harness, run
    from bench.runners import serve_open
    from bench.runners.serving import Server
    cell = harness.load_cell(harness.benchmark_spec(), a.workload)
    run.program_path()
    devices = harness.require_devices(cell.chips)
    harness.init_compile_cache()
    ctx = harness.Context(cell=cell, seed=a.seed, seconds=a.seconds,
                          devices=devices,
                          clock=harness.CompileClock(),
                          t_start=time.perf_counter())
    srv = Server(ctx)
    srv.warm_up()
    for k, rate in enumerate(float(r) for r in a.rates.split(",")):
        arrivals = dict(cell.traffic["arrivals"], mean_rps=rate)
        reqs = generate.open_loop(arrivals, a.seed + k, a.seconds, srv.vocab)
        srv.live.clear()
        srv.done.clear()
        srv.ticks.clear()
        timing = serve_open.drive(srv, reqs, a.seconds)
        s = serve_open.summarise(srv, reqs, a.seconds, timing["late_s"])
        last = [g for g in reqs if g.due >= 0.75 * a.seconds]
        tail = serve_open.summarise(srv, last, a.seconds, [0.0])
        print(json.dumps({"rate_rps": rate, **s,
                          "queue_at_close": timing["queue_at_close"],
                          "drain_s": timing["drain_s"],
                          "ttft_p95_ms_last_quarter": tail["ttft_p95_ms"]},
                         default=harness.finite), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
