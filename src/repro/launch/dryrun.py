"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes, print memory/cost analyses, and dump roofline terms.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh pod --out benchmarks/results
  python -m repro.launch.dryrun --all --mesh multipod   # 2x16x16
  python -m repro.launch.dryrun --serve --arch llama4_maverick_400b_a17b \
      --mesh multipod --out benchmarks/results   # sharded serving pair

Each combo can also be run in a fresh subprocess (--subprocess) so one
failure/compile-OOM cannot take down the sweep; that is how
``benchmarks/roofline.py`` drives it.

Importing this module is side-effect free. XLA is configured by
``main()`` AFTER argparse and BEFORE the first jax import — the host
placeholder device count must match the requested mesh, and flags are
frozen once jax initializes, so every jax/repro import in this file
lives inside a function.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback

# (arch, shape) combos that are intentionally skipped, with reasons
# (see DESIGN.md §6).
SKIPS: dict[tuple[str, str], str] = {
    ("whisper-small", "long_500k"):
        "encoder-decoder ASR: 524k-token decode is not meaningful for a "
        "1500-frame/448-token enc-dec model (DESIGN.md §6).",
}

# pods per multi-pod mesh variant (absent key = single pod)
MESH_PODS = {"multipod": 2, "multipod10k": 40}


def configure_xla(args) -> None:
    """Pin the CPU platform and set XLA_FLAGS from the parsed args.
    Must run before jax init.

    The dry-run compiles against host placeholder devices, so it sets
    ``JAX_PLATFORMS=cpu`` itself: on a host with a TPU neither it nor
    its ``--subprocess`` children (which inherit the env) touch the chip.

    Device count: 512 for pod/multipod, 10,240 for the scale-out
    lowering check (--mesh multipod10k = 40 pods x 256).

    XLA's while-loop LICM hoists dtype converts of the remat residual
    stack OUT of the backward loop, materializing a full fp32 copy of
    the per-layer activations (2-30 GB) — disable it for TRAINING
    dry-runs. For SERVING dry-runs (--serve, or a decode/prefill
    --shape) LICM must stay ON: it hoists the (loop-invariant) K/V
    gathers out of the flash kv scan; without it every block re-gathers
    the full cache.
    """
    ndev = 10_240 if args.mesh == "multipod10k" else 512
    flags = (os.environ.get("XLA_FLAGS", "")
             + f" --xla_force_host_platform_device_count={ndev}")
    is_train = (not args.serve
                and (args.all or args.shape in (None, "train_4k")
                     or args.sync != "baseline"))
    if is_train:
        flags += " --xla_disable_hlo_passes=while-loop-invariant-code-motion"
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"


def build_tthf_program(model, shape, mesh, sync: str, consensus_mode: str,
                       tau: int = 8, consensus_every: int = 4,
                       gamma: int = 2, fused_interval: bool = False,
                       donate: bool = True):
    """Lower one full TT-HF interval (Algorithm 1 lines 4-15) on the
    production mesh: replicas = pod*data slices, clusters = data-blocks
    (multi-pod: cluster == pod). Used by the §Perf paper-technique
    hillclimb (--sync tthf-fused / tthf-rounds / tthf-fused-interval /
    star / local). ``fused_interval`` lowers the flat (R, rows, 128) carrier
    step (DESIGN.md §12); ``donate=False`` keeps the param input buffer
    alive, for the donated-vs-undonated memory_analysis delta."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import (
        TTHFScaleConfig, make_tthf_train_step, tthf_shardings)
    from repro.launch.steps import param_dtype_for

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    # giant models: replica = one whole pod (FSDP inside), clusters of
    # pods; otherwise replica = one data rank, clusters = pods
    pod_granular = model.cfg.param_count() > 5e10 and "pod" in sizes
    if pod_granular:
        R = sizes["pod"]
        cluster = R
    else:
        R = sizes.get("pod", 1) * sizes.get("data", 1)
        cluster = sizes.get("data", R)      # multipod: cluster == pod
    scale = TTHFScaleConfig(
        replicas=R, cluster_size=cluster, tau=tau,
        consensus_every=consensus_every, gamma_d2d=gamma,
        consensus_mode=consensus_mode, lr=1e-2, graph="ring",
        granularity="pod" if pod_granular else "dp")
    step, net = make_tthf_train_step(model, scale, dtype=jnp.bfloat16,
                                     sync=sync,
                                     fused_interval=fused_interval,
                                     param_dtype=param_dtype_for(model.cfg))
    p_abs, p_sh, b_sh = tthf_shardings(
        model, scale, mesh, param_dtype=param_dtype_for(model.cfg))
    if fused_interval:
        # the flat (R, rows, LANE) carrier: replicas over the replica
        # axes, rows over model ranks (rows pad to ROW_ALIGN = 128, so
        # 16 always divides)
        spec = step.spec
        p_abs = spec.abstract(R)
        rows = (("pod",) if pod_granular
                else ("pod", "data") if "pod" in sizes else ("data",))
        p_sh = NamedSharding(mesh, P(rows, "model", None))
    b = max(1, shape.global_batch // R)
    if pod_granular:
        # giant-model TT-HF: per-replica microbatch reduced 4x (the
        # interval still sees tau microbatches; remat stack must fit
        # next to the FSDP'd weights)
        b = max(1, b // 4)
    tb = jax.ShapeDtypeStruct((tau, R, b, shape.seq_len), jnp.int32)
    batch = {"tokens": tb, "labels": tb}
    repl = NamedSharding(mesh, P())
    fn = jax.jit(step,
                 in_shardings=(p_sh, {"tokens": b_sh, "labels": b_sh},
                               repl, repl),
                 out_shardings=(p_sh, repl),
                 donate_argnums=(0,) if donate else ())
    picks = jax.ShapeDtypeStruct((net.num_clusters,), jnp.int32)
    return fn, (p_abs, batch, picks, jax.ShapeDtypeStruct((), jnp.int32))


def run_one(arch: str, shape_name: str, mesh_name: str,
            verbose: bool = True, sync: str = "baseline",
            tau: int = 8, consensus_every: int = 4,
            donation_check: bool = False) -> dict:
    import jax

    from repro.configs import get_arch, get_shape
    from repro.launch.analysis import analyze, model_flops_for
    from repro.launch.mesh import chips_in, make_production_mesh
    from repro.launch.steps import build_program
    from repro.models import build_model

    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": SKIPS[(arch, shape_name)]}

    mesh = make_production_mesh(multi_pod=mesh_name in MESH_PODS,
                                pods=MESH_PODS.get(mesh_name, 2))
    model = build_model(cfg)
    t0 = time.time()
    rules_override = None
    if os.environ.get("RP_MOE_EP"):
        from repro.launch.steps import TRAIN_RULES
        rules_override = TRAIN_RULES.with_overrides(
            embed_fsdp=None, expert_ffn=("pod", "data"))
    with mesh:
        if sync == "baseline":
            fn, args = build_program(model, shape, mesh,
                                     rules_override=rules_override)
        else:
            mode = "fused" if "fused" in sync else "rounds"
            base = "tthf" if sync.startswith("tthf") else sync
            fn, args = build_tthf_program(
                model, shape, mesh, base, mode, tau=tau,
                consensus_every=consensus_every,
                fused_interval=(sync == "tthf-fused-interval"))
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print("  memory_analysis:", mem)
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        print("  cost_analysis: flops=%.3e bytes=%.3e" %
              (cost.get("flops", 0), cost.get("bytes accessed", 0)))

    roof = analyze(compiled, arch=arch, shape=shape, mesh_name=mesh_name,
                   chips=chips_in(mesh),
                   model_flops_total=model_flops_for(cfg, shape))
    rec = roof.to_dict()
    rec.update(status="ok", lower_s=t_lower, compile_s=t_compile,
               arg_bytes=float(getattr(mem, "argument_size_in_bytes", 0)),
               out_bytes=float(getattr(mem, "output_size_in_bytes", 0)),
               temp_bytes=float(getattr(mem, "temp_size_in_bytes", 0)),
               alias_bytes=float(getattr(mem, "alias_size_in_bytes", 0)))
    if donation_check and sync != "baseline":
        # the donation contract's memory claim, measured: recompile the
        # same interval step WITHOUT donate_argnums and compare live
        # param HBM (donated aliases the output onto the input buffer,
        # so the undonated/donated ratio approaches 2x for the params)
        with mesh:
            fn2, args2 = build_tthf_program(
                model, shape, mesh,
                "tthf" if sync.startswith("tthf") else sync,
                "fused" if "fused" in sync else "rounds", tau=tau,
                consensus_every=consensus_every,
                fused_interval=(sync == "tthf-fused-interval"),
                donate=False)
            mem2 = fn2.lower(*args2).compile().memory_analysis()

        def _live(m, alias):
            return float(getattr(m, "argument_size_in_bytes", 0)
                         + getattr(m, "output_size_in_bytes", 0)) - alias
        alias = float(getattr(mem, "alias_size_in_bytes", 0))
        live_d = _live(mem, alias)
        live_u = _live(mem2, float(getattr(mem2, "alias_size_in_bytes", 0)))
        rec["donation"] = {
            "alias_bytes": alias, "live_arg_out_donated": live_d,
            "live_arg_out_undonated": live_u,
            "param_hbm_ratio": live_u / max(live_d, 1.0)}
        if verbose:
            print(f"  donation: alias {alias:.3e}B  live arg+out "
                  f"{live_u:.3e}B -> {live_d:.3e}B "
                  f"({rec['donation']['param_hbm_ratio']:.2f}x)")
    if verbose:
        print(f"  roofline: compute {roof.compute_s*1e3:.2f}ms "
              f"memory {roof.memory_s*1e3:.2f}ms "
              f"collective {roof.collective_s*1e3:.2f}ms "
              f"-> dominant: {roof.dominant} "
              f"(fraction {rec['roofline_fraction']:.3f})")
    return rec


def run_serve_one(arch: str, mesh_name: str, *, slots: int = 8,
                  max_prompt: int = 1024, max_total: int = 2048,
                  paged: bool = False, page_size: int = 64,
                  verbose: bool = True) -> dict:
    """Lower + compile the sharded continuous-batching serving pair
    (admission prefill-splice and per-slot decode, exactly what
    ``ContinuousScheduler`` runs) on a production mesh — the served-
    model analogue of the training dry-run (ISSUE 8 / DESIGN.md §14).
    With ``paged``, lowers the paged admission/decode pair instead
    (chunked prefill into pages + page-map decode, what
    ``PagedContinuousScheduler`` runs — DESIGN.md §15)."""
    from repro.configs import get_arch
    from repro.launch.mesh import chips_in, make_production_mesh
    from repro.launch.steps import build_paged_serve_program, \
        build_serve_program
    from repro.models import build_model

    cfg = get_arch(arch)
    mesh = make_production_mesh(multi_pod=mesh_name in MESH_PODS,
                                pods=MESH_PODS.get(mesh_name, 2))
    model = build_model(cfg)
    if paged:
        programs = build_paged_serve_program(
            model, mesh, slots=slots, max_prompt=max_prompt,
            max_total=max_total, page_size=page_size)
    else:
        programs = build_serve_program(model, mesh, slots=slots,
                                       max_prompt=max_prompt,
                                       max_total=max_total)
    rec = {"arch": arch, "shape": "serve", "mesh": mesh_name,
           "status": "ok", "chips": chips_in(mesh), "slots": slots,
           "max_prompt": max_prompt, "max_total": max_total,
           "paged": paged, "programs": {}}
    if paged:
        rec["page_size"] = page_size
    for name, (fn, args) in programs.items():
        t0 = time.time()
        with mesh:
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        prec = {
            "lower_s": t_lower, "compile_s": t_compile,
            "flops": float(cost.get("flops", 0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0)),
            "arg_bytes": float(getattr(mem, "argument_size_in_bytes", 0)),
            "out_bytes": float(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": float(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": float(getattr(mem, "alias_size_in_bytes", 0)),
        }
        rec["programs"][name] = prec
        if verbose:
            print(f"[serve {arch} x {mesh_name}] {name}: "
                  f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
            print(f"  flops={prec['flops']:.3e} "
                  f"bytes={prec['bytes_accessed']:.3e} "
                  f"temp={prec['temp_bytes']:.3e}B "
                  f"alias={prec['alias_bytes']:.3e}B")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "multipod10k"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="JSON output path or dir")
    ap.add_argument("--subprocess", action="store_true",
                    help="run each combo in a fresh interpreter")
    ap.add_argument("--sync", default="baseline",
                    choices=["baseline", "star", "local",
                             "tthf-fused", "tthf-rounds",
                             "tthf-fused-interval"],
                    help="lower the TT-HF interval step instead of the "
                         "standard train/serve step (train_4k only); "
                         "tthf-fused-interval = the flat (R, rows, 128) "
                         "carrier step (DESIGN.md §12)")
    ap.add_argument("--tau", type=int, default=8)
    ap.add_argument("--consensus-every", type=int, default=4)
    ap.add_argument("--donation-check", action="store_true",
                    help="also compile the interval step WITHOUT buffer "
                         "donation and record the live-param-HBM delta")
    ap.add_argument("--pair-schedule", action="store_true",
                    help="enable the pair-scheduled flash attention "
                         "(skips fully-masked blocks; §Perf)")
    ap.add_argument("--moe-ep", action="store_true",
                    help="expert weights stay put (expert_ffn sharded "
                         "over data, no FSDP gathers); tokens move (§Perf)")
    ap.add_argument("--serve", action="store_true",
                    help="lower the sharded serving pair (admission "
                         "prefill-splice + per-slot decode) instead of a "
                         "train/serve step shape")
    ap.add_argument("--slots", type=int, default=8,
                    help="serve mode: continuous-batching slot count")
    ap.add_argument("--max-prompt", type=int, default=1024,
                    help="serve mode: admission prompt length")
    ap.add_argument("--max-total", type=int, default=2048,
                    help="serve mode: per-slot cache length")
    ap.add_argument("--paged", action="store_true",
                    help="serve mode: lower the PAGED admission/decode "
                         "pair (chunked prefill + page-map decode, "
                         "DESIGN.md §15) instead of the ring pair")
    ap.add_argument("--page-size", type=int, default=64,
                    help="serve mode: tokens per cache page (--paged)")
    args = ap.parse_args(argv)

    configure_xla(args)
    # ^ MUST precede any jax import/init: the dry-run builds the
    #   production 512-chip mesh out of host placeholder devices.

    if args.pair_schedule:
        from repro.models import attention as _attn
        _attn.PAIR_SCHEDULE = True
    if args.moe_ep:
        os.environ["RP_MOE_EP"] = "1"

    if args.serve:
        if not args.arch:
            ap.error("--serve requires --arch")
        try:
            rec = run_serve_one(args.arch, args.mesh, slots=args.slots,
                                max_prompt=args.max_prompt,
                                max_total=args.max_total,
                                paged=args.paged,
                                page_size=args.page_size,
                                verbose=args.out != "-")
        except Exception as e:  # noqa: BLE001 — report, don't crash
            rec = {"arch": args.arch, "shape": "serve", "mesh": args.mesh,
                   "status": "error", "error":
                   f"{type(e).__name__}: {e}\n"
                   + traceback.format_exc()[-1500:]}
        print(f"== serve {args.arch} x {args.mesh}: {rec['status']}",
              file=sys.stderr)
        if args.out == "-":
            print(json.dumps(rec))
        elif args.out:
            import pathlib
            p = pathlib.Path(args.out)
            if p.is_dir():
                p.mkdir(parents=True, exist_ok=True)
                tag = "_paged" if args.paged else ""
                fname = p / f"dryrun_serve{tag}_{args.mesh}.json"
            else:
                fname = p
            fname.write_text(json.dumps(rec, indent=1))
            print(f"wrote {fname}", file=sys.stderr)
        return 1 if rec["status"] == "error" else 0

    from repro.configs import ARCHS, INPUT_SHAPES
    combos = ([(a, s) for a in ARCHS for s in INPUT_SHAPES]
              if args.all else [(args.arch, args.shape)])

    records = []
    for arch, shape in combos:
        if args.subprocess:
            out = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun",
                 "--arch", arch, "--shape", shape, "--mesh", args.mesh,
                 "--out", "-"],
                capture_output=True, text=True, timeout=3600)
            try:
                rec = json.loads(out.stdout.splitlines()[-1])
            except Exception:
                rec = {"arch": arch, "shape": shape, "mesh": args.mesh,
                       "status": "error",
                       "error": (out.stderr or out.stdout)[-2000:]}
        else:
            try:
                rec = run_one(arch, shape, args.mesh,
                              verbose=args.out != "-", sync=args.sync,
                              tau=args.tau,
                              consensus_every=args.consensus_every,
                              donation_check=args.donation_check)
                rec["sync"] = args.sync
                rec["tau"] = args.tau
            except Exception as e:  # noqa: BLE001 — sweep must continue
                rec = {"arch": arch, "shape": shape, "mesh": args.mesh,
                       "status": "error", "error":
                       f"{type(e).__name__}: {e}\n"
                       + traceback.format_exc()[-1500:]}
        records.append(rec)
        status = rec["status"]
        print(f"== {arch} x {shape} x {args.mesh}: {status}",
              file=sys.stderr)

    if args.out == "-":
        print(json.dumps(records[0] if len(records) == 1 else records))
    elif args.out:
        import pathlib
        p = pathlib.Path(args.out)
        if p.is_dir() or args.all:
            p.mkdir(parents=True, exist_ok=True)
            fname = p / f"dryrun_{args.mesh}.json"
        else:
            fname = p
        fname.write_text(json.dumps(records, indent=1))
        print(f"wrote {fname}", file=sys.stderr)

    n_bad = sum(r["status"] == "error" for r in records)
    return 1 if n_bad else 0


if __name__ == "__main__":
    sys.exit(main())
