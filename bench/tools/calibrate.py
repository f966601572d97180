"""Readings that a cell's limits are set from, in one process on the
chip at the cell's own size.

    python3 -m bench.tools.calibrate --workload <cell> --seeds a,b,... \\
        --control-seeds x,y,z [--seconds s]

For every seed in ``--seeds`` it prints the numbers the cell's output
check compares, read from the program (the lower readings). For every
seed in ``--control-seeds`` it also prints the same numbers read from
the controls, the plain reference put in the program's place:
``control_bf16``, stored and computed in bfloat16, the step below the
float32 parameters a training cell holds; ``control_fp8``, with every
matmul operand rounded to float8, the step below the bfloat16 operands
of the program's DEFAULT-precision matmuls, which is what a serving
cell holds; and, for a training cell, the reference with each planted
fault (``half_batch``, ``no_mix``; ``frozen`` reads 1 by construction
and is printed for completeness). The benchmark's own runs never run
this.

Training cells need no window. Serving cells drive a window of
``--seconds`` at the cell's own load, as a run does, and compare the
same sample of finished requests.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def emit(**kw) -> None:
    from bench import harness
    print(json.dumps(kw, default=harness._json_default), flush=True)


def training(cell, devices, seeds, control):
    import jax.numpy as jnp
    from bench import compare, harness, tthf_reference
    from bench.runners import train
    job, cfg = cell.traffic, cell.config
    ref = harness.reference_module(cfg)
    steps = job["checked_steps"]
    for seed in seeds:
        ctx = harness.Context(cell=cell, seed=seed, seconds=0.0,
                              devices=devices, clock=harness.CompileClock(),
                              t_start=time.perf_counter())
        tr, init, params0 = train.setup(ctx)
        got = train.checked_steps(tr, params0, steps)
        del tr, params0
        harness.free_device_memory()
        names = train.leaf_names(init)
        p0 = init(harness.seed_key(seed))
        want = tthf_reference.run(ref, cfg["model"], job, seed, p0, steps)
        r = compare.training(got, want, names)
        emit(seed=seed, side="program", **r["values"], **r["info"])
        if seed not in control:
            continue
        variants = {"control_fp8": {"precision": "fp8"},
                    "control_bf16": {"dtype": jnp.bfloat16,
                                     "precision": None},
                    "fault_half_batch": {"fault": "half_batch"},
                    "fault_no_mix": {"fault": "no_mix"},
                    "fault_frozen": {"fault": "frozen"}}
        for side, kw in variants.items():
            alt = tthf_reference.run(ref, cfg["model"], job, seed, p0, steps,
                                     **kw)
            r = compare.training(alt, want, names)
            emit(seed=seed, side=side, **r["values"], **r["info"])


def serving(cell, devices, seeds, control, seconds):
    """One server per seed, freed before the reference runs, as in a run
    (a large model's reference does not fit beside the scheduler)."""
    import jax.numpy as jnp
    import numpy as np
    from bench import compare, generate, harness
    from bench.runners import serve_closed, serve_open
    from bench.runners.serving import Server
    for seed in seeds:
        ctx = harness.Context(cell=cell, seed=seed, seconds=seconds,
                              devices=devices,
                              clock=harness.CompileClock(),
                              t_start=time.perf_counter())
        srv = Server(ctx)
        srv.warm_up()
        if cell.traffic["runner"] == "serve_closed":
            serve_closed.start_sessions(srv, cell.traffic, seed).drive(
                seconds)
        else:
            serve_open.drive(srv, generate.open_loop(
                cell.traffic["arrivals"], seed, seconds, srv.vocab), seconds)
        sample = srv.sample()
        srv.free()
        params = srv.init(harness.seed_key(seed))
        prog, ctrl = [], {}
        for t in sample:
            rows = srv.reference_rows(params, t)
            out = np.asarray(t.req.out_tokens)
            prog.append(compare.served_gap(rows, out))
            if seed in control:
                for name, kw in (("control_fp8", {"precision": "fp8"}),
                                 ("control_bf16", {"dtype": jnp.bfloat16,
                                                   "precision": None})):
                    low = srv.reference_rows(params, t, **kw)
                    ctrl.setdefault(name, []).append(
                        compare.served_gap(rows, low.argmax(-1)))
        emit(seed=seed, side="program", served_logit_gap=max(prog),
             gaps=prog, requests=len(sample),
             tokens=sum(len(t.req.out_tokens) for t in sample))
        for name, gaps in ctrl.items():
            emit(seed=seed, side=name, served_logit_gap=max(gaps), gaps=gaps)
        del srv, params
        harness.free_device_memory()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    a = ap.parse_args(argv)
    from bench import harness, run
    cell = harness.load_cell(harness.benchmark_spec(), a.workload)
    run.program_path()
    devices = harness.require_devices(cell.chips)
    harness.init_compile_cache()
    seeds = [int(s) for s in a.seeds.split(",")]
    control = {int(s) for s in a.control_seeds.split(",") if s}
    seeds += [s for s in sorted(control) if s not in seeds]
    if cell.traffic["runner"] == "train":
        training(cell, devices, seeds, control)
    else:
        serving(cell, devices, seeds, control, a.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
