"""Reductions that more than one per-layer metric reads (each metric
file under ``bench/metrics/`` names one of them for its cells)."""
from __future__ import annotations

from bench import trace


def host_ms_per_tick(r):
    """Mean, over the scheduler ticks of the traced window that ran a
    decode step or a prefill chunk, of the part of the tick (one
    ``PagedContinuousScheduler.step`` call) during which the device ran
    nothing, in milliseconds."""
    ticks = [(a, b) for a, b, work in r.readings.get("ticks", []) if work]
    if not ticks or not r.trace.ops:
        return None
    spans = [(r.lo + a * 1e9, r.lo + b * 1e9) for a, b in ticks]
    idle = trace.idle_inside(r.trace, spans, r.lo, r.hi)
    return 1e-6 * sum(idle) / len(idle) if idle else None


def serve_mfu(r):
    """The operations of the prompts prefilled and the tokens decoded in
    the traced window (matmuls plus attention over the live context,
    ``bench/flops``), over the window and the chip's bf16 peak, in %."""
    work = [f for t, f in r.readings.get("work", []) if t < r.window_s]
    if not work:
        return None
    return 100.0 * sum(work) / r.window_s / r.peaks["bf16_flops"]


def idle_share(r):
    """1 - (union of device-op intervals) / traced window, in %."""
    share = trace.idle_share(r.trace, r.lo, r.hi)
    return None if share is None else 100.0 * share
