"""Training driver.

Two modes:
* ``--mode sim``   — the paper's Algorithm 1 on the federated image task
                     (Sec. IV experimental setup; runs on this CPU box).
* ``--mode scale`` — TT-HF as the sync strategy for a model-zoo arch
                     (``--arch``), on whatever devices exist (use the
                     dry-run for the production mesh).

Examples:
  python -m repro.launch.train --mode sim --model svm --steps 200
  python -m repro.launch.train --mode scale --arch qwen1.5-0.5b \
      --reduced --steps 2 --sync tthf
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np


def build_program(args, tau: int):
    """The declarative round program (DESIGN.md §10): ONE object
    declares the scenario — optional netsim dynamics, optional fog
    hierarchy, optional control policy (DESIGN.md §16) — and both
    trainers resolve it, instead of each mode threading per-scenario
    knobs through per-scenario loops."""
    from repro.rounds import RoundProgram

    dynamics = hierarchy = control = None
    if args.scenario:
        from repro.netsim import scenarios
        dynamics = scenarios.get(args.scenario, seed=args.seed)
    if args.hierarchy:
        from repro.hierarchy import presets
        hierarchy = presets.get(args.hierarchy, tau=tau)
    if getattr(args, "control", None) and args.control != "static":
        from repro.control import get_policy
        control = get_policy(args.control)
    return RoundProgram(dynamics=dynamics, hierarchy=hierarchy,
                        control=control)


def run_sim(args):
    import jax
    from repro.configs import TopologyConfig, TTHFConfig
    from repro.core import TTHFTrainer, make_baseline_config
    from repro.data import fashion_synth, partition_noniid_labels
    from repro.models import make_sim_model

    x, y = fashion_synth(num_points=args.points, seed=args.seed)
    data = partition_noniid_labels(x, y, num_devices=args.devices,
                                   labels_per_device=3, seed=args.seed)
    topo = TopologyConfig(num_devices=args.devices,
                          num_clusters=args.clusters,
                          graph="geometric", seed=args.seed)
    model = make_sim_model(args.model, data.feature_dim, data.num_classes,
                           hidden=args.hidden)
    if args.baseline:
        algo = make_baseline_config(args.baseline, args.tau)
        algo = dataclasses.replace(algo, constant_lr=args.lr)
    else:
        algo = TTHFConfig(tau=args.tau, consensus_every=args.consensus_every,
                          gamma_d2d=args.gamma, constant_lr=args.lr,
                          phi=args.phi)
    tr = TTHFTrainer(model, data, topo, algo, batch_size=args.batch,
                     program=build_program(args, algo.tau))
    # observability (repro.obs §13): --trace-dir turns on spans +
    # theory-bound telemetry + manifest; --profile adds jax.profiler
    from repro.obs.sink import make_obs
    obs = make_obs(args.trace_dir, profile=args.profile,
                   run_name="train-sim",
                   config={"args": vars(args), "algo": algo, "topo": topo},
                   extra={"mode": "sim", "model": args.model})
    t0 = time.time()
    try:
        st, hist = tr.run(steps=args.steps, seed=args.seed,
                          eval_every=args.eval_every, obs=obs)
    finally:
        obs.close()
    dt = time.time() - t0
    by_level = "".join(f" L{l}={n}" for l, n in
                       sorted(tr.ledger.uplinks_by_level.items()))
    print(f"steps={args.steps} wall={dt:.1f}s "
          f"final_loss={hist.global_loss[-1]:.4f} "
          f"final_acc={hist.global_acc[-1]:.4f} "
          f"uplinks={tr.ledger.uplinks}{by_level} "
          f"d2d_msgs={tr.ledger.d2d_msgs}")
    if args.out:
        json.dump({k: np.asarray(v).tolist()
                   for k, v in hist.as_arrays().items()},
                  open(args.out, "w"))
    return 0


def run_scale(args):
    from repro.configs import get_arch
    from repro.core.distributed import TTHFScaleConfig
    from repro.train import ScaleTrainer, TrainerConfig

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    # consensus_every must divide tau (static event calendar): snap to
    # the nearest divisor <= requested
    ce = max(1, min(args.consensus_every, args.tau))
    while args.tau % ce:
        ce -= 1
    scale = TTHFScaleConfig(replicas=args.replicas,
                            cluster_size=args.cluster_size,
                            tau=args.tau,
                            consensus_every=ce,
                            gamma_d2d=args.gamma, lr=args.lr,
                            consensus_mode=args.consensus_mode)
    # every scenario — flat, dynamic, hierarchical — is the same
    # ScaleTrainer loop over a resolved round program
    tr = ScaleTrainer(
        cfg, scale,
        TrainerConfig(batch_per_replica=args.batch, seq_len=args.seq,
                      intervals=args.steps, eval_every=0,
                      seed=args.seed, trace_dir=args.trace_dir,
                      profile=args.profile),
        sync=args.sync, program=build_program(args, args.tau))
    t0 = time.time()
    try:
        tr.init().run()
    finally:
        tr.close()
    by_level = "".join(f" L{l}={n}" for l, n in
                       sorted(tr.ledger.uplinks_by_level.items()))
    print(f"intervals={tr.interval} wall={time.time() - t0:.1f}s "
          f"uplinks={tr.ledger.uplinks}{by_level} "
          f"d2d_msgs={tr.ledger.d2d_msgs} (tau={scale.tau} local steps "
          f"per interval, sync={args.sync})")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["sim", "scale"], default="sim")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--tau", type=int, default=20)
    ap.add_argument("--gamma", type=int, default=2)
    ap.add_argument("--consensus-every", type=int, default=5)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace-dir", default=None,
                    help="observability dir (repro.obs): Chrome trace, "
                         "metrics.jsonl telemetry, run manifest")
    ap.add_argument("--profile", action="store_true",
                    help="also wrap the run in jax.profiler.trace "
                         "(written under <trace-dir>/jax_profile)")
    ap.add_argument("--scenario", default=None,
                    help="netsim dynamics scenario (see repro.netsim."
                         "scenarios; e.g. markov_links, device_churn)")
    ap.add_argument("--control", default="static",
                    choices=["static", "remark1", "connectivity"],
                    help="online control plane (DESIGN.md §16): static "
                         "= off (historical path), remark1 = adaptive "
                         "Γ under the Theorem-2 envelope, connectivity "
                         "= measured-λ Γ + τ retuning + biased "
                         "sampling")
    ap.add_argument("--hierarchy", default=None,
                    help="fog-hierarchy preset (see repro.hierarchy."
                         "presets; e.g. fog3, fog4, fog3_sampled)")
    # sim
    ap.add_argument("--model", choices=["svm", "nn"], default="svm")
    ap.add_argument("--devices", type=int, default=125)
    ap.add_argument("--clusters", type=int, default=25)
    ap.add_argument("--points", type=int, default=12_500)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--phi", type=float, default=1.0)
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--baseline", choices=["centralized", "fedavg"],
                    default=None)
    # scale
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--cluster-size", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--sync", choices=["tthf", "star", "local"],
                    default="tthf")
    ap.add_argument("--consensus-mode", choices=["fused", "rounds"],
                    default="fused")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    return run_sim(args) if args.mode == "sim" else run_scale(args)


if __name__ == "__main__":
    import sys
    sys.exit(main())
