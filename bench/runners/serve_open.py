"""Open-loop serving runner: requests arrive on the mix's schedule
(``generate.open_loop``) whether or not the server keeps up.

The window lasts ``--seconds``. Each request due in it is submitted at
the first tick boundary after its due time; when nothing is outstanding
the runner sleeps until the next due time rather than spin ``step``.
After the window no request is added, and the scheduler runs on until
every request due in the window has finished (at most ``drain_s``), so
a request that answers late is timed late, not dropped.

End-to-end: ``ttft_p95_ms``, first token minus due time over every
request due in the window (one with no first token counts as infinite).
Printed on an earlier line only: tokens per second, the offered load
below the knee, and the 50th, 90th, 95th and 99th percentiles of the
gaps between tokens of a request whose later token falls inside the
window.
"""
from __future__ import annotations

import math
import time

from bench import generate, harness
from bench.runners.serving import Server

DRAIN_S = 60.0


def drive(srv: Server, reqs: list, seconds: float, profiler=None) -> dict:
    """One window over ``reqs``; returns its timings."""
    late, i = [], 0
    if profiler is not None:
        profiler.start()
    with harness.annotate("bench.window"):
        srv.t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - srv.t0
            while i < len(reqs) and reqs[i].due <= now:
                srv.submit(reqs[i])
                late.append(now - reqs[i].due)
                i += 1
            if now >= seconds and i == len(reqs):
                break
            if srv.sched.outstanding:
                srv.tick()
            elif i < len(reqs):
                with harness.annotate("bench.wait"):
                    time.sleep(max(0.0, reqs[i].due - now))
            else:
                with harness.annotate("bench.wait"):
                    time.sleep(max(0.0, seconds - now))
    if profiler is not None:
        profiler.stop()
    queue_at_close = len(srv.sched.queue)
    t_drain = time.perf_counter()
    while srv.sched.outstanding and time.perf_counter() - t_drain < DRAIN_S:
        srv.tick()
    return {"late_s": late, "queue_at_close": queue_at_close,
            "drain_s": time.perf_counter() - t_drain}


def summarise(srv: Server, reqs: list, seconds: float, late: list) -> dict:
    ttft = []
    for g in reqs:
        tr = srv.done.get(g.rid) or srv.live.get(g.rid)
        ttft.append(1e3 * (tr.stamps[0] - g.due) if tr and tr.stamps
                    else math.inf)
    itl = srv.itl_ms(seconds)
    tokens = srv.token_times(seconds)
    return {
        "ttft_p95_ms": harness.percentile(ttft, 95),
        "itl_p90_ms": harness.percentile(itl, 90),
        "itl_p95_ms": harness.percentile(itl, 95),
        "itl_p99_ms": harness.percentile(itl, 99),
        "ttft_p50_ms": harness.percentile(ttft, 50),
        "itl_p50_ms": harness.percentile(itl, 50),
        "requests": len(reqs), "itl_gaps": len(itl),
        "unanswered": sum(1 for x in ttft if x == math.inf),
        "tokens_per_s_offered_load": len(tokens) / seconds,
        "generator_late_ms_p50": 1e3 * harness.percentile(late, 50),
        "generator_late_ms_max": 1e3 * max(late, default=0.0),
    }


def run(ctx: harness.Context) -> harness.Outcome:
    mix = ctx.cell.traffic
    srv = Server(ctx)
    srv.warm_up()
    reqs = generate.open_loop(mix["arrivals"], ctx.seed, ctx.seconds,
                              srv.vocab)
    compiles = ctx.clock.count
    timing = drive(srv, reqs, ctx.seconds, ctx.profiler)
    peak = harness.peak_bytes(ctx.devices)
    summary = summarise(srv, reqs, ctx.seconds, timing["late_s"])
    unfinished = len(srv.live)
    ctx.note(**summary, drain_s=timing["drain_s"],
             queue_at_close=timing["queue_at_close"],
             compiles_in_window=ctx.clock.count - compiles,
             compile_s_total=ctx.clock.total, unfinished=unfinished)
    readings = srv.readings(ctx.seconds)
    checks = srv.check(ctx.cell.limits["limits"])
    return harness.Outcome(
        metrics={"ttft_p95_ms": summary["ttft_p95_ms"],
                 "setup_s": srv.t0 - ctx.t_start},
        attempted=len(reqs), failed=summary["unanswered"] + unfinished,
        checks=checks, readings=readings, memory_peak_bytes=peak)
