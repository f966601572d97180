"""Span-based tracer with zero-dep Chrome-trace export (DESIGN.md §13).

The span hierarchy mirrors the paper's two timescales:

  training:  run > round > interval > {batch, resolve, dispatch,
             loss_read}
  serving:   run > step > {admission, prefill_chunk, sample, readback,
             emit, decode_step}

A span's name carries its layer: ``train.interval`` is the span
``interval`` of the layer ``train``.

Every span, instant and counter enters the profiler's clock: it is a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` whose arguments
are its args (a counter is a zero-length ``repro.counter.<name>``). With
no profiler session running that costs about a microsecond; under one,
the program's spans lie on the host lines of the device trace, beside
the device ops they dispatch. This holds for the disabled sink
(``NULL_OBS``) too.

:class:`Tracer` additionally records spans host-side
(``time.perf_counter``-clocked, ts/dur in microseconds) into a flat
event list, exported as Chrome trace JSON — open ``trace.json`` in
``chrome://tracing`` or https://ui.perfetto.dev. No external
dependencies.
"""
from __future__ import annotations

import json
import threading
from contextlib import nullcontext
import time
from pathlib import Path
from typing import Any, Callable, Optional

from jax.profiler import TraceAnnotation

#: prefix of every annotation the program enters in a profile
PREFIX = "repro."

# Chrome trace event phases used here: X = complete span, i = instant,
# C = counter, M = metadata (process/thread names)
_PID = 1


def annotate(name: str, **args: Any) -> TraceAnnotation:
    """The profiler's view of an event: ``repro.<name>`` with ``args``."""
    return TraceAnnotation(PREFIX + name, **_clean(args))


def profiling() -> bool:
    """Is a profiler session recording this process?"""
    return TraceAnnotation.is_enabled()


def mark(name: str, **args: Any) -> None:
    """A zero-length annotation: an instant or a counter sample. Nothing
    is built when no profiler session runs."""
    if profiling():
        with annotate(name, **args):
            pass


def _clean(args: dict) -> dict:
    out = {}
    for k, v in args.items():
        if hasattr(v, "tolist"):
            v = v.tolist()
        elif hasattr(v, "__float__") and not isinstance(v, (int, bool)):
            v = float(v)
        out[k] = v
    return out


class Annotated:
    """One span on the profiler's clock; the context manager
    ``NULL_OBS.span`` and :meth:`Tracer.span` return.

    ``counters``, when given, is called once as the span ends, and its
    ``{name: int}`` joins the span's args (so a count is read after the
    work it counts). It is not called unless something records it: a
    profiler session, or ``record`` (the tracer's callback, which gets
    the span's start and end on ``time.perf_counter`` and the counts).
    """

    __slots__ = ("_ann", "_counters", "_value", "_record", "_t0")

    def __init__(self, name: str, args: dict, counters: Optional[Callable],
                 value: Any, record: Optional[Callable] = None):
        self._ann = annotate(name, **args)
        self._counters = counters
        self._value = value
        self._record = record

    def __enter__(self):
        self._ann.__enter__()
        if self._record is not None:
            self._t0 = time.perf_counter()
        return self._value

    def __exit__(self, *exc) -> None:
        counts = None
        if self._counters is not None and (self._record is not None
                                           or profiling()):
            counts = self._counters()
            self._ann.set_metadata(**counts)
        self._ann.__exit__(*exc)
        if self._record is not None:
            self._record(self._t0, time.perf_counter(), counts)


def span(name: str, value: Any = None, counters: Optional[Callable] = None,
         **args: Any):
    """A span that only the profiler records (``NULL_OBS.span``)."""
    if not profiling():
        return nullcontext(value)
    return Annotated(name, args, counters, value)


class Tracer:
    """Collects spans/instants/counters; exports Chrome trace JSON.

    Every span, instant and counter is also annotated for the profiler
    (module docstring). A span's layer (``train`` of ``train.interval``)
    becomes its Chrome event's ``cat``, the rest its ``name``; a span
    with ``counters`` also samples them on the counter track.
    """

    def __init__(self):
        self._events: list[dict] = []
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._tids: dict[int, int] = {}

    # ------------------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _us(self, t: float) -> float:
        return (t - self._t0) * 1e6

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            if ident not in self._tids:
                self._tids[ident] = len(self._tids)
            return self._tids[ident]

    # ------------------------------------------------------------------
    def span(self, name: str, counters: Optional[Callable] = None,
             **args: Any) -> Annotated:
        """One complete ('X') event; nests by call structure."""
        return self.open(name, args, counters, self)

    def open(self, name: str, args: dict, counters: Optional[Callable],
             value: Any) -> Annotated:
        """:meth:`span`, entering as ``value``."""
        tid = self._tid()
        cat, _, short = name.rpartition(".")

        def record(t0: float, t1: float, counts: Optional[dict]) -> None:
            ts, dur = self._us(t0), (t1 - t0) * 1e6
            ev = {"name": short, "cat": cat or "span", "ph": "X",
                  "pid": _PID, "tid": tid, "ts": ts, "dur": dur,
                  "args": _clean({**args, **(counts or {})})}
            with self._lock:
                self._events.append(ev)
            if counts:
                self._sample(short, ts + dur, counts)

        return Annotated(name, args, counters, value, record)

    def instant(self, name: str, cat: str = "event", **args: Any) -> None:
        mark(name, **args)
        layer, _, short = name.rpartition(".")
        tid = self._tid()   # resolve BEFORE locking (the lock is not
        with self._lock:    # reentrant; _tid takes it too)
            self._events.append({
                "name": short, "cat": layer or cat, "ph": "i", "s": "t",
                "pid": _PID, "tid": tid, "ts": self._now_us(),
                "args": _clean(args)})

    def counter(self, name: str, **values: float) -> None:
        """One 'C' sample — renders as a stacked counter track."""
        mark("counter." + name, **values)
        self._sample(name, self._now_us(), values)

    def _sample(self, name: str, ts: float, values: dict) -> None:
        with self._lock:
            self._events.append({
                "name": name, "ph": "C", "pid": _PID, "ts": ts,
                "args": {k: float(v) for k, v in values.items()}})

    # ------------------------------------------------------------------
    def export(self, path: str, process_name: str = "repro") -> str:
        """Write the Chrome trace JSON (idempotent full rewrite)."""
        with self._lock:
            events = list(self._events)
            tids = dict(self._tids)
        meta = [{"name": "process_name", "ph": "M", "pid": _PID,
                 "args": {"name": process_name}}]
        for ident, tid in tids.items():
            meta.append({"name": "thread_name", "ph": "M", "pid": _PID,
                         "tid": tid, "args": {"name": f"host-{tid}"}})
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        tmp = str(p) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        Path(tmp).replace(p)
        return str(p)

    @property
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)


def validate_chrome_trace(doc: dict) -> list[str]:
    """Schema check for an exported trace — returns a list of problems
    (empty = valid). Used by tests and the CI obs-smoke job."""
    problems = []
    if "traceEvents" not in doc:
        return ["missing traceEvents"]
    for i, ev in enumerate(doc["traceEvents"]):
        for key in ("name", "ph", "pid"):
            if key not in ev:
                problems.append(f"event {i} missing {key!r}")
        if ev.get("ph") == "X":
            if "ts" not in ev or "dur" not in ev:
                problems.append(f"span {i} ({ev.get('name')}) missing "
                                "ts/dur")
            elif ev["dur"] < 0:
                problems.append(f"span {i} negative dur")
    return problems


def profiler_trace(trace_dir: Optional[str]):
    """``jax.profiler.trace`` context under ``<trace_dir>/jax_profile``
    (a no-op without a trace dir). A profiler that fails to start
    raises: a profiled run never goes on without its device trace."""
    from contextlib import nullcontext
    if not trace_dir:
        return nullcontext()
    import jax
    return jax.profiler.trace(str(Path(trace_dir) / "jax_profile"))


__all__ = ["Annotated", "PREFIX", "Tracer", "annotate", "mark",
           "profiler_trace", "profiling", "span", "validate_chrome_trace"]
