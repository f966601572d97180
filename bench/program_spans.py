"""What the program under test records in a profile, and the per-layer
metrics that read it.

The program (``repro.obs``) enters every span, instant and counter as a
``jax.profiler.TraceAnnotation`` named ``repro.<layer>.<name>`` whose
arguments are the event's args, and names the parts of its compiled
TT-HF step with ``jax.named_scope`` (``local_sgd``, ``d2d_mix``,
``global_agg``, ``local_sgd_mix``), which reach each device op's
``op_name``. From the ``.xplane.pb`` of a ``--trace 1`` run this module
takes

* every host event whose name starts with ``repro.``, with its args,
  its start and end, and the host line it ran on;
* the host line that holds ``bench.window``: the harness's main thread,
  which dispatches to the device (the trainer's prefetch worker runs on
  another line);
* each device op's scope: the innermost of the step's scopes in the
  ``op_name`` of the HLO instruction the op ran. On the TPU the op
  events carry no ``op_name``: the profile keeps each program's
  optimized HLO (an ``HloProto``) in the stats of the ``/host:metadata``
  plane, which ``ProfileData`` does not expose, so it is read from the
  file here; the program an op belongs to is the ``XLA Modules`` event
  it starts in.

Loading it also adds the program's spans on the dispatching line to the
benchmark trace's spans, so that the result line's ``breakdown`` names
an idle gap by the innermost span around it, of the benchmark or of
the program. A program that records none of this (an older one) leaves
every reading here at None and the breakdown as it was.
"""
from __future__ import annotations

import bisect
import glob
import sys
from dataclasses import dataclass, field
from typing import Optional

from bench import harness, trace

PREFIX = "repro."
WINDOW = "bench.window"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# the interval step's scopes (repro.core.distributed)
SCOPES = ("local_sgd", "d2d_mix", "global_agg", "local_sgd_mix")
SYNC_SCOPES = ("d2d_mix", "global_agg")


@dataclass
class Program:
    # [(start, end, name, args, line)], sorted; line = (plane, index)
    events: list = field(default_factory=list)
    window_line: Optional[tuple] = None
    # device id -> [(start, end, op name, scope or None)], sorted
    ops: dict = field(default_factory=dict)

    def spans(self, name: str, line: Optional[tuple] = None) -> list:
        return [e for e in self.events
                if e[2] == name and (line is None or e[4] == line)]

    def dispatch_spans(self) -> list:
        """The program's spans of non-zero length on the line that
        dispatches to the device."""
        return [e for e in self.events
                if e[4] == self.window_line and e[1] > e[0]]


def _stats(ev) -> dict:
    return dict(getattr(ev, "stats", None) or ())


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost step scope named in an ``op_name`` path."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return None


def instruction(op: str) -> str:
    """``fusion.12`` of an op event's ``%fusion.12 = f32[4] fusion(...)``."""
    return op[1:].split(" ", 1)[0] if op.startswith("%") else op


def from_profile(pd, op_names: Optional[dict] = None) -> Program:
    """``op_names``: {program: {HLO instruction: op_name}} (``hlo_op_
    names``); each device op takes the scope of its instruction in the
    program whose ``XLA Modules`` event it starts in."""
    prog = Program()
    op_names = dict(op_names or {})
    # a program is also found by its name alone (``jit_step`` of
    # ``jit_step(2692881832611909650)``) where that name is unique
    bases: dict = {}
    for key, names in list(op_names.items()):
        bases.setdefault(key.split("(")[0], []).append(names)
    for base, found in bases.items():
        if len(found) == 1:
            op_names.setdefault(base, found[0])
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            dev = int(plane.name[len(trace.DEVICE_PLANE_PREFIX):].split()[0])
            ops, modules = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules += [(float(ev.start_ns), ev.name)
                                for ev in line.events]
                elif line.name == trace.OPS_LINE:
                    ops += [(float(ev.start_ns), float(ev.duration_ns),
                             ev.name) for ev in line.events]
            modules.sort()
            starts = [m[0] for m in modules]
            out = prog.ops.setdefault(dev, [])
            for s, d, name in ops:
                i = bisect.bisect_right(starts, s) - 1
                module = modules[i][1] if i >= 0 else ""
                names = op_names.get(module) or op_names.get(
                    module.split("(")[0], {})
                out.append((s, s + d, name,
                            scope_of(names.get(instruction(name)))))
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                key = (plane.name, i)
                for ev in line.events:
                    if ev.name == WINDOW:
                        prog.window_line = key
                    elif ev.name.startswith(PREFIX):
                        s = float(ev.start_ns)
                        prog.events.append((s, s + float(ev.duration_ns),
                                            ev.name, _stats(ev), key))
    prog.events.sort(key=lambda e: e[:3])
    for ops in prog.ops.values():
        ops.sort(key=lambda o: o[:3])
    return prog


# ---------------------------------------------------------------------------
# the programs' HLO, read from the raw profile (protobuf wire format)
# ---------------------------------------------------------------------------

def _varint(buf: bytes, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes, pos: int = 0, end: Optional[int] = None):
    """(field number, value) of the protobuf message in ``buf[pos:end]``;
    a length-delimited value comes as its (start, stop) offsets."""
    end = len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif wire in (1, 5):
            value, pos = None, pos + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, value


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _hlo_op_names(buf: bytes, span) -> dict:
    """{instruction: op_name} of one ``HloProto`` (hlo_module = 1 >
    computations = 3 > instructions = 2 > name = 1, metadata = 7 >
    op_name = 2)."""
    out = {}
    for f, module in _fields(buf, *span):
        if f != 1:
            continue
        for f, comp in _fields(buf, *module):
            if f != 3:
                continue
            for f, ins in _fields(buf, *comp):
                if f != 2:
                    continue
                name = op_name = None
                for f, v in _fields(buf, *ins):
                    if f == 1:
                        name = _str(buf, v)
                    elif f == 7:
                        for g, w in _fields(buf, *v):
                            if g == 2:
                                op_name = _str(buf, w)
                if name and op_name:
                    out[name] = op_name
    return out


def hlo_op_names(xspace: bytes) -> dict:
    """{program: {HLO instruction: op_name}} from a serialized
    ``XSpace``: the ``/host:metadata`` plane's event metadata (planes =
    1 > name = 2, event_metadata = 4, stat_metadata = 5), whose ``Hlo
    Proto`` stat holds each program's optimized HLO."""
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name, entries, stat_names = None, [], {}
        for f, v in _fields(xspace, *plane):
            if f == 2:
                name = _str(xspace, v)
                if name != METADATA_PLANE:
                    break
            elif f == 4:
                entries.append(v)
            elif f == 5:           # map entry: key = 1, XStatMetadata = 2
                for g, w in _fields(xspace, *v):
                    if g == 2:
                        meta = dict(_fields(xspace, *w))
                        if 2 in meta:
                            stat_names[meta.get(1, 0)] = _str(xspace,
                                                              meta[2])
        if name != METADATA_PLANE:
            continue
        out = {}
        for entry in entries:      # map entry: key = 1, XEventMetadata = 2
            for g, meta in _fields(xspace, *entry):
                if g != 2:
                    continue
                program, hlo = None, None
                for h, v in _fields(xspace, *meta):
                    if h == 2:
                        program = _str(xspace, v)
                    elif h == 5:   # XStat: metadata_id = 1, bytes = 6
                        stat = dict(_fields(xspace, *v))
                        if stat_names.get(stat.get(1)) == HLO_STAT \
                                and 6 in stat:
                            hlo = stat[6]
                if program and hlo:
                    out[program] = _hlo_op_names(xspace, hlo)
        return out
    return {}


def _profile_path() -> Optional[str]:
    found = glob.glob(str(harness.TRACE_DIR / "**" / "*.xplane.pb"),
                      recursive=True)
    return found[0] if found else None


def attach(tr: trace.Trace, prog: Program) -> None:
    """Add the program's dispatching-line spans to ``tr.spans`` (once)."""
    if getattr(tr, "program_attached", False):
        return
    tr.program_attached = True
    tr.spans.extend((s, e, n) for s, e, n, _, _ in prog.dispatch_spans())
    tr.spans.sort()


def program(r) -> Optional[Program]:
    """The program's events of a traced run (read once per run, kept on
    the reading ``r``); None when the run left no profile or the
    program recorded nothing."""
    prog = getattr(r, "program", None)
    if prog is None:
        path = _profile_path()
        if path is None:
            return None
        try:
            from jax.profiler import ProfileData
            with open(path, "rb") as f:
                op_names = hlo_op_names(f.read())
            prog = from_profile(ProfileData.from_file(path), op_names)
        except Exception as e:  # noqa: BLE001 — a reading, never a failure
            print(f"bench: program spans unreadable: {e!r}",
                  file=sys.stderr)
            prog = Program()
        r.program = prog
    attach(r.trace, prog)
    if not prog.events and not any(o[3] for ops in prog.ops.values()
                                   for o in ops):
        return None
    return prog


# ---------------------------------------------------------------------------
# reductions the per-layer metrics read
# ---------------------------------------------------------------------------

def _started_in(spans, lo: float, hi: float) -> list:
    return [(s, e) for s, e, *_ in spans if lo <= s < hi]


def idle_ms_mean(r, spans) -> Optional[float]:
    """Mean over ``spans`` starting in the traced window (clipped to it)
    of the time in them when no device op ran, in milliseconds."""
    idle = trace.idle_inside(r.trace, _started_in(spans, r.lo, r.hi),
                             r.lo, r.hi)
    return 1e-6 * sum(idle) / len(idle) if idle else None


def sync_device_share(r) -> Optional[float]:
    """Device self-time of the ops under ``d2d_mix`` or ``global_agg``
    over device-busy time, in the traced window, in %."""
    prog = program(r)
    if prog is None or not prog.ops:
        return None
    if not any(o[3] for ops in prog.ops.values() for o in ops):
        return None
    sync = 0.0
    for ops in prog.ops.values():
        timed = trace.self_times([o[:3] for o in ops])
        for (s, e, _, own), (_, _, _, scope) in zip(timed, ops):
            if scope not in SYNC_SCOPES or e <= r.lo or s >= r.hi:
                continue
            part = (min(e, r.hi) - max(s, r.lo)) / (e - s) if e > s else 1.0
            sync += own * part
    busy = trace.busy_ns(r.trace, r.lo, r.hi) * len(prog.ops)
    return 100.0 * sync / busy if busy > 0 else None


def host_ms_per_interval(r) -> Optional[float]:
    """Mean over the trainer's ``repro.train.interval`` spans of the
    time in them when no device op ran, in milliseconds."""
    prog = program(r)
    if prog is None:
        return None
    return idle_ms_mean(r, prog.spans("repro.train.interval",
                                      prog.window_line))


def step_host_ms(r) -> Optional[float]:
    """The in-program twin of ``host_ms_per_tick``: mean over the
    scheduler's ``repro.sched.step`` spans whose counters show a decode
    step or a prefill chunk, of the time in them when no device op ran,
    in milliseconds. A step's counters are read as it ends, so it did
    work when they differ from the step before it (the first step of
    the trace, with none before it, is left out)."""
    prog = program(r)
    if prog is None:
        return None
    steps = prog.spans("repro.sched.step", prog.window_line)

    def work(args):
        return (args.get("decode_steps"),
                args.get("prefill_chunks", args.get("prefills")))

    busy = [b for a, b in zip(steps, steps[1:]) if work(b[3]) != work(a[3])]
    return idle_ms_mean(r, busy)


def queue_wait_ms_p95(r) -> Optional[float]:
    """p95, over the requests submitted in the traced window, of their
    ``repro.sched.admitted`` minus their ``repro.sched.submit``; a
    request not admitted by the trace's end waits until that end. In
    milliseconds."""
    prog = program(r)
    if prog is None:
        return None
    admitted: dict = {}
    for s, _, _, args, _ in prog.spans("repro.sched.admitted"):
        admitted.setdefault(args.get("rid"), s)
    end = max([r.hi] + [e[1] for e in prog.events])
    waits = [admitted.get(args.get("rid"), end) - s
             for s, _, _, args, _ in prog.spans("repro.sched.submit")
             if r.lo <= s < r.hi]
    if not waits:
        return None
    return 1e-6 * harness.percentile(waits, 95)
