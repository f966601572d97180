"""Reduction of a profiler trace to what per-layer metrics read.

A ``--trace 1`` run writes one ``.xplane.pb``. From it this module
takes

* the device operations: every event on an ``XLA Ops`` line of a
  ``/device:TPU:<n>`` plane, as ``(start_ns, end_ns, name)``;
* the benchmark's own host spans: every event on a host plane whose
  name starts with ``bench.`` (the harness wraps its calls into the
  program in ``jax.profiler.TraceAnnotation``s of that prefix);

and reduces them: the union of busy intervals (so overlapping ops are
counted once), the idle share of a window, time per op name, and the
longest idle gaps, each named by the innermost benchmark span that
covers it. Both kinds of event are on the profiler's one clock.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)   # device id -> [(s, e, name)]
    spans: list = field(default_factory=list)  # [(s, e, name)]

    @property
    def devices(self) -> list:
        return sorted(self.ops)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = int(plane.name[len(DEVICE_PLANE_PREFIX):].split()[0])
            ops = tr.ops.setdefault(dev, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    ops.append((s, s + float(ev.duration_ns), ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = float(ev.start_ns)
                        tr.spans.append((s, s + float(ev.duration_ns),
                                         ev.name))
    for ops in tr.ops.values():
        ops.sort()
    tr.spans.sort()
    return tr


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals, lo: float = float("-inf"),
          hi: float = float("inf")) -> list:
    """Merged, clipped [s, e) intervals, sorted."""
    out = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, lo: float, hi: float) -> float:
    """Length of [lo, hi) that merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def window(tr: Trace, name: str):
    """(start, end) of the first host span called ``name``."""
    for s, e, n in tr.spans:
        if n == name:
            return s, e
    return None


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Device-busy time in [lo, hi), averaged over the traced devices."""
    if not tr.ops:
        return 0.0
    return sum(covered(union(ops, lo, hi), lo, hi)
               for ops in tr.ops.values()) / len(tr.ops)


def idle_share(tr: Trace, lo: float, hi: float):
    """1 - busy / window, over [lo, hi); None without device events."""
    if not tr.ops or hi <= lo or not any(tr.ops.values()):
        return None
    return 1.0 - busy_ns(tr, lo, hi) / (hi - lo)


_KIND = re.compile(r" ([a-z][a-z0-9_\-.]*)\(")


def short_name(name: str) -> str:
    """``%fusion.12 (fusion)`` from an op event's full HLO text."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = _KIND.search(rest)
    return f"{head} ({m.group(1)})" if m else head


def self_times(ops) -> list:
    """[(start, end, name, self ns)]: an op's time less the time of the
    ops nested inside it (a ``while`` and the body ops it runs share
    one line of the trace)."""
    out, stack = [], []         # stack of indices into out
    for s, e, n in ops:         # sorted by start
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = out[stack[-1]]
            p[3] -= min(e, p[1]) - s
        out.append([s, e, n, e - s])
        stack.append(len(out) - 1)
    return out


def op_seconds(tr: Trace, lo: float, hi: float, match=None) -> dict:
    """{op name: device self-seconds in [lo, hi)} summed over devices,
    for ops whose name ``match(name)`` accepts (all when None). An op
    cut by the window counts by the share of it inside."""
    out: dict = {}
    for ops in tr.ops.values():
        for s, e, n, own in self_times(ops):
            if e <= lo or s >= hi or (match is not None and not match(n)):
                continue
            part = (min(e, hi) - max(s, lo)) / (e - s) if e > s else 1.0
            out[n] = out.get(n, 0.0) + own * part * 1e-9
    return out


def gaps(tr: Trace, lo: float, hi: float) -> list:
    """Idle gaps of device 0's busy union inside [lo, hi):
    [(start, end)], longest first."""
    if not tr.ops:
        return []
    merged = union(tr.ops[tr.devices[0]], lo, hi)
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    out.sort(key=lambda g: g[0] - g[1])
    return out


def enclosing_span(tr: Trace, t: float) -> str:
    """Name of the innermost benchmark span covering time ``t``."""
    best, best_len = "(no span)", float("inf")
    for s, e, n in tr.spans:
        if s <= t < e and e - s < best_len:
            best, best_len = n, e - s
    return best


def idle_inside(tr: Trace, spans, lo: float, hi: float) -> list:
    """For each host span [s, e) clipped to [lo, hi): the nanoseconds of
    it during which no device op ran."""
    if not tr.ops:
        return []
    merged = union(tr.ops[tr.devices[0]], lo, hi)
    out = []
    for s, e in spans:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((e - s) - covered(merged, s, e))
    return out


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device ops that took most
    time, and the longest idle gaps named by the host span around them."""
    by_name: dict = {}
    for n, sec in op_seconds(tr, lo, hi).items():
        by_name[short_name(n)] = by_name.get(short_name(n), 0.0) + sec
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])
    idle = [[enclosing_span(tr, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps(tr, lo, hi)[:top]]
    return {"device_ops": [[n, s] for n, s in ops[:top]],
            "idle_gaps": idle}
