"""Closed-loop serving runner: ``clients`` sessions, each sending its
next request as soon as its previous one finishes
(``generate.ClosedLoop``).

Set-up starts every session: each client's first request is submitted
and the scheduler runs until all of them are decoding, so the window
opens in steady state. The window lasts ``--seconds``; finished
requests are replaced at once. At the close no request is added and
those in flight are left (they are neither late nor failed).

End-to-end: ``serve_tokens_per_s``, output tokens emitted in the window
over its length. The gaps between tokens (whose later token falls in
the window; 50th, 90th, 95th and 99th percentiles) and the time to
first token are printed on an earlier line only: the p95 gap moves with
the seed's sizes (PERF.md), and the time to first token is closed-loop
queueing.
"""
from __future__ import annotations

import time

from bench import generate, harness
from bench.runners.serving import Server


def start_sessions(srv: Server, mix: dict, seed: int) -> "Sessions":
    """Send every client's first request and run until all of them are
    decoding."""
    sess = Sessions(srv, generate.ClosedLoop(mix["clients"], seed,
                                             srv.vocab))
    srv.t0 = time.perf_counter()
    for c in range(mix["clients"]["clients"]):
        sess.send(c, 0.0)
    while any(not t.stamps for t in srv.live.values()):
        srv.tick()
    return sess


class Sessions:
    def __init__(self, srv: Server, loop: generate.ClosedLoop):
        self.srv, self.loop = srv, loop
        self.client_of: dict = {}
        self.answered: set = set()

    def send(self, client: int, now: float) -> None:
        g = self.loop.next(client, now)
        self.client_of[g.rid] = client
        self.srv.submit(g)

    def drive(self, seconds: float, profiler=None) -> None:
        """The window: replace each finished request at once."""
        srv = self.srv
        self.answered |= set(srv.done)
        if profiler is not None:
            profiler.start()
        with harness.annotate("bench.window"):
            srv.restart_clock()
            while True:
                now = time.perf_counter() - srv.t0
                if now >= seconds:
                    break
                for rid in [r for r in srv.done if r not in self.answered]:
                    self.answered.add(rid)
                    self.send(self.client_of[rid], now)
                srv.tick()
        if profiler is not None:
            profiler.stop()


def run(ctx: harness.Context) -> harness.Outcome:
    mix = ctx.cell.traffic
    srv = Server(ctx)
    srv.warm_up()
    sess = start_sessions(srv, mix, ctx.seed)
    compiles = ctx.clock.count
    sess.drive(ctx.seconds, ctx.profiler)
    peak = harness.peak_bytes(ctx.devices)
    end = ctx.seconds
    tokens = srv.token_times(end)
    itl = srv.itl_ms(end)
    in_window = [t for t in list(srv.done.values()) + list(srv.live.values())
                 if t.gen.due >= 0.0 and t.stamps]
    ttft = [1e3 * (t.stamps[0] - t.gen.due) for t in in_window]
    sched = srv.sched
    ctx.note(serve_tokens_per_s=len(tokens) / end,
             itl_p50_ms=harness.percentile(itl, 50),
             itl_p90_ms=harness.percentile(itl, 90),
             itl_p95_ms=harness.percentile(itl, 95),
             itl_p99_ms=harness.percentile(itl, 99), itl_gaps=len(itl),
             ttft_p50_ms=harness.percentile(ttft, 50),
             ttft_p95_ms=harness.percentile(ttft, 95),
             requests_started_in_window=len(in_window),
             requests_finished=len(srv.done),
             prefix_hit_rate=sched.prefix_hit_rate,
             page_deferrals=sched.page_deferrals,
             compiles_in_window=ctx.clock.count - compiles,
             compile_s_total=ctx.clock.total)
    readings = srv.readings(end)
    checks = srv.check(ctx.cell.limits["limits"])
    return harness.Outcome(
        metrics={"serve_tokens_per_s": len(tokens) / end,
                 "setup_s": srv.t0 - ctx.t_start},
        attempted=sess.loop.next_rid, failed=0, checks=checks,
        readings=readings, memory_peak_bytes=peak)
