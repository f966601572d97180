"""Device time under the scopes of the ``mamba_hybrid`` kind's serving
programs (granite-4.0-h-small), and the per-layer metrics that read it.

The program names the parts of its prefill-chunk and decode programs
with ``jax.named_scope``: ``ssm_mixer`` (a Mamba-2 block), ``attn_mixer``
(an attention block), and inside each layer's expert FFN ``moe_router``,
``moe_experts`` and ``shared_expert``. Each device op takes the
innermost of these scopes in the ``op_name`` of its HLO instruction,
read from the profile's optimized HLO as ``bench/program_spans.py``
reads the TT-HF step's scopes (``hlo_op_names``); an op outside them
has none. A program that names none of them (an older one) reads None.
"""
from __future__ import annotations

import bisect
import sys
from typing import Optional

from bench import program_spans, trace

SCOPES = ("ssm_mixer", "attn_mixer", "moe_router", "moe_experts",
          "shared_expert")
SSM_SCOPES = ("ssm_mixer",)
MOE_SCOPES = ("moe_router", "moe_experts", "shared_expert")


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost of :data:`SCOPES` named in an ``op_name`` path."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return None


def scoped_ops(pd, op_names: dict) -> dict:
    """{device: [(start, end, op name, scope or None)]}, sorted; each op
    looked up in the program whose ``XLA Modules`` event it starts in
    (by full name, or by its base name where that is unique)."""
    by_base: dict = {}
    for key, names in op_names.items():
        by_base.setdefault(key.split("(")[0], []).append(names)
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        dev = int(plane.name[len(trace.DEVICE_PLANE_PREFIX):].split()[0])
        ops, modules = [], []
        for line in plane.lines:
            if line.name == program_spans.MODULES_LINE:
                modules += [(float(e.start_ns), e.name) for e in line.events]
            elif line.name == trace.OPS_LINE:
                ops += [(float(e.start_ns), float(e.duration_ns), e.name)
                        for e in line.events]
        modules.sort()
        starts = [m[0] for m in modules]
        rows = []
        for s, d, name in ops:
            i = bisect.bisect_right(starts, s) - 1
            module = modules[i][1] if i >= 0 else ""
            names = op_names.get(module)
            if names is None:
                found = by_base.get(module.split("(")[0], [])
                names = found[0] if len(found) == 1 else {}
            rows.append((s, s + d, name,
                         scope_of(names.get(program_spans.instruction(name)))))
        out[dev] = sorted(rows, key=lambda o: o[:3])
    return out


def ops_of(r) -> dict:
    """The traced run's scoped device ops (read once, kept on ``r``)."""
    ops = getattr(r, "hybrid_ops", None)
    if ops is None:
        ops = {}
        path = program_spans._profile_path()
        if path is not None:
            try:
                from jax.profiler import ProfileData
                with open(path, "rb") as f:
                    names = program_spans.hlo_op_names(f.read())
                ops = scoped_ops(ProfileData.from_file(path), names)
            except Exception as e:  # noqa: BLE001 — a reading, never a failure
                print(f"bench: hybrid scopes unreadable: {e!r}",
                      file=sys.stderr)
        r.hybrid_ops = ops
    return ops


def scope_share(r, scopes) -> Optional[float]:
    """Device self-time of the ops under ``scopes`` over device-busy
    time, in the traced window [r.lo, r.hi), in %; None when no op of
    the window carries any of :data:`SCOPES`."""
    ops = ops_of(r)
    if not any(o[3] for dev in ops.values() for o in dev
               if o[1] > r.lo and o[0] < r.hi):
        return None
    part = 0.0
    for dev in ops.values():
        timed = trace.self_times([o[:3] for o in dev])
        for (s, e, _, own), (_, _, _, scope) in zip(timed, dev):
            if scope not in scopes or e <= r.lo or s >= r.hi:
                continue
            inside = (min(e, r.hi) - max(s, r.lo)) / (e - s) if e > s else 1.0
            part += own * inside
    busy = trace.busy_ns(r.trace, r.lo, r.hi) * len(ops)
    return 100.0 * part / busy if busy > 0 else None


def ssm_device_share(r) -> Optional[float]:
    return scope_share(r, SSM_SCOPES)


def moe_device_share(r) -> Optional[float]:
    return scope_share(r, MOE_SCOPES)
