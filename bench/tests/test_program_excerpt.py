"""The program's spans and op scopes on a recorded chip trace: two
slices of ``--trace 1`` runs on one TPU v5e with the program's
``repro.*`` host spans (``data/program_trace_excerpt.json``):
``mamba2-370m.tthf.d2d_every_step`` around an interval boundary (the
interval's last mixing and its aggregation, the host gap, the next
dispatch), kept with its ``XLA Modules`` events and the ``op_name`` of
each HLO instruction in it (read from the profile's ``Hlo Proto``
stats), and ``starcoder2-3b.serve.decode_long`` around a decode step.
The first excerpt (``decode_long_trace_excerpt.json``) stays as it
was."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace as NS

from bench import program_spans as ps
from bench import trace
from bench.harness import BENCH_DIR, load_file_module

EXCERPT = Path(__file__).parent / "data" / "program_trace_excerpt.json"


def recorded(key):
    d = json.loads(EXCERPT.read_text())[key]
    pd = NS(planes=[NS(name=p["name"], lines=[
        NS(name=l["name"], events=[
            NS(name=n, start_ns=s, duration_ns=dur, stats=list(st.items()))
            for n, s, dur, st in l["events"]])
        for l in p["lines"]]) for p in d["planes"]])
    lo, hi = d["window_ns"]
    r = NS(trace=trace.from_profile(pd), lo=lo, hi=hi)
    r.program = ps.from_profile(pd, d["op_names"])
    return r


def test_code_excerpt_finds_the_named_paged_decode_kernel():
    reader = load_file_module(BENCH_DIR / "metrics" /
                              "paged_decode_roofline.py", "roofline")
    r = recorded("code")
    k = trace.op_seconds(r.trace, r.lo, r.hi, match=reader.is_kernel)
    named = sum(sec for n, sec in k.items()
                if trace.short_name(n).startswith("%paged_decode"))
    # the rest are the pool's AllocateBuffer custom-calls (1 ns here)
    assert named > 0.999 * sum(k.values())
    top = trace.breakdown(r.trace, r.lo, r.hi)["device_ops"]
    assert top[0][0].startswith("%paged_decode")


def test_tthf_excerpt_finds_the_step_scopes():
    r = recorded("tthf")
    scopes = {o[3] for o in r.program.ops[0]}
    assert {"local_sgd", "d2d_mix", "global_agg"} <= scopes
    assert 0.0 < ps.sync_device_share(r) < 100.0


def test_excerpt_gaps_are_named_by_program_spans():
    for key in ("tthf", "code"):
        r = recorded(key)
        ps.program(r)
        names = [n for n, _ in trace.breakdown(r.trace, r.lo,
                                               r.hi)["idle_gaps"]]
        assert names and names[0].startswith("repro."), (key, names)
