"""Benchmark harness entrypoint — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Default scale "ci" fits
this CPU box; ``--scale paper`` runs the Sec.-IV configuration
(125 devices / 25 clusters / Fashion-synth 70k).

  PYTHONPATH=src python -m benchmarks.run [--scale ci] [--only fig4,...]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = ("fig4_gamma", "fig5_tau", "fig6_energy", "theory_bound",
          "kernel_bench", "scale_sync", "topology_ablation", "roofline",
          "dynamics_bench", "hierarchy_bench", "rounds_bench",
          "serving_bench", "obs_overhead", "control_bench")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["ci", "paper"], default="ci")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="write a run manifest (config hash, git SHA, "
                         "mesh) into this dir before sweeping")
    ap.add_argument("--profile", action="store_true",
                    help="wrap the sweep in jax.profiler.trace "
                         "(requires --trace-dir)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()

    chosen = (args.only.split(",") if args.only else SUITES)
    if args.trace_dir:
        from repro.obs.manifest import write_manifest
        write_manifest(args.trace_dir,
                       config={"scale": args.scale, "seed": args.seed,
                               "suites": list(chosen)},
                       extra={"run": "benchmarks"})
    if args.profile and args.trace_dir:
        from repro.obs.trace import profiler_trace
        prof = profiler_trace(args.trace_dir)
    else:
        from contextlib import nullcontext
        prof = nullcontext()
    print("name,us_per_call,derived")
    rc = 0
    with prof:
        for suite in chosen:
            mod_name = suite if suite in SUITES else f"{suite}"
            try:
                mod = __import__(f"benchmarks.{mod_name}",
                                 fromlist=["run"])
                t0 = time.time()
                rows = mod.run(scale=args.scale, seed=args.seed)
                for row in rows:
                    print(row.csv())
                print(f"_suite/{suite},{(time.time()-t0)*1e6:.0f},ok",
                      flush=True)
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                rc = 1
                print(f"_suite/{suite},0,ERROR:{type(e).__name__}:{e}")
                traceback.print_exc(file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
