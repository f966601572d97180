"""Scale-mode sync-strategy comparison: TT-HF vs star (FedAvg) vs
local-only, on a reduced model-zoo arch — validates that the paper's
technique transfers to the transformer training path, and compares the
consensus backends of the unified engine (``core/mixing.py``): the
paper-faithful ``rounds`` (-> reference) sequential exchanges, the
``masked_loop`` bounded loop, and the beyond-paper ``fused``
(-> fused_power) build-time V^Gamma variant (identical losses, fewer
collectives).

Raw-speed rows (DESIGN.md §12): ``tthf_fused_interval`` times the flat
(R, rows, 128) carrier step with donated buffers, and the ``trainer_straight``
vs ``trainer_fast`` pair times the full ScaleTrainer loop with every
speed knob off vs on (donation + fused interval + prefetch) — the
trajectories are bitwise identical, only the clock moves.

Timing discipline: every row runs ONE excluded warmup interval (jit
compilation used to land in interval 0 and dominate the mean) and
fences with ``block_until_ready`` on both sides of the timed loop.
Per-row timings are appended to the
``benchmarks/results/BENCH_scale_sync.json`` trajectory.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import Row, append_trajectory


def _prev_tthf_fused_us(out_dir: str = "benchmarks/results"):
    """us/interval of the last PRE-§12 ``tthf_fused`` row (a record
    with no ``tthf_fused_interval`` row). Those records had no warmup
    exclusion, so interval 0 includes jit compile time — that row is
    what this run's warmup-excluded fast path is compared against in
    the claims; later §12-era records would only measure run-to-run
    noise."""
    path = os.path.join(out_dir, "BENCH_scale_sync.json")
    try:
        with open(path) as f:
            hist = json.load(f)
    except (OSError, ValueError):
        return None
    for rec in reversed(hist):
        names = {row.get("name") for row in rec.get("rows", [])}
        if "scale_sync/tthf_fused_interval" in names:
            continue
        for row in rec.get("rows", []):
            if row.get("name") == "scale_sync/tthf_fused":
                return float(row["us_per_call"])
    return None


def run(scale: str = "ci", seed: int = 0) -> list[Row]:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core.distributed import (
        TTHFScaleConfig, make_tthf_train_step, stack_replicas)
    from repro.models import build_model
    from repro.train import ScaleTrainer, TrainerConfig

    cfg = get_arch("qwen1.5-0.5b").reduced(num_layers=2, d_model=128,
                                           d_ff=256, vocab_size=512)
    model = build_model(cfg)
    R, s, tau = 4, 2, 4
    intervals = 4 if scale == "ci" else 12
    key = jax.random.PRNGKey(seed)
    toks = jax.random.randint(key, (tau, R, 2, 64), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    # the same pick sequence for every mode (drawn once, outside timing)
    kk = jax.random.PRNGKey(seed + 1)
    picks_per_interval = []
    for _ in range(intervals):
        kk, kp = jax.random.split(kk)
        picks_per_interval.append(kp)

    def timed_intervals(step, params0, num_clusters):
        """(losses, us/interval): one EXCLUDED warmup interval (compile
        + first execute, on copies so a donating step cannot invalidate
        params0), then the timed loop fenced with block_until_ready."""
        picks = [jax.random.randint(k, (num_clusters,), 0, s)
                 for k in picks_per_interval]
        warm = step(jax.tree.map(jnp.copy, params0), batch, picks[0],
                    jnp.asarray(0))
        jax.block_until_ready(warm)
        p = params0
        jax.block_until_ready((p, batch))
        losses = []
        t0 = time.perf_counter()
        for i in range(intervals):
            p, loss = step(p, batch, picks[i], jnp.asarray(i))
            losses.append(loss)
        jax.block_until_ready((p, losses))
        us = (time.perf_counter() - t0) / intervals * 1e6
        return [float(x) for x in losses], us

    rows = []
    losses_by_mode = {}
    us_by_mode = {}
    for sync, cmode in (("tthf", "fused"), ("tthf", "rounds"),
                        ("tthf", "masked_loop"),
                        ("star", "fused"), ("local", "fused")):
        scale_cfg = TTHFScaleConfig(replicas=R, cluster_size=s, tau=tau,
                                    consensus_every=2, gamma_d2d=2,
                                    lr=0.05, consensus_mode=cmode)
        step, net = make_tthf_train_step(model, scale_cfg,
                                         dtype=jnp.float32, sync=sync)
        params = stack_replicas(model.init(jax.random.PRNGKey(0)), R)
        losses, us = timed_intervals(jax.jit(step), params,
                                     net.num_clusters)
        name = f"{sync}_{cmode}" if sync == "tthf" else sync
        losses_by_mode[name] = losses
        us_by_mode[name] = us
        rows.append(Row(f"scale_sync/{name}", us,
                        f"loss0={losses[0]:.4f};lossN={losses[-1]:.4f}"))

    # the §12 fast path: flat (R, rows, 128) carrier + donated param buffer
    # (bitwise the tthf_fused trajectory — asserted in claims below)
    scale_cfg = TTHFScaleConfig(replicas=R, cluster_size=s, tau=tau,
                                consensus_every=2, gamma_d2d=2, lr=0.05,
                                consensus_mode="fused")
    step, net = make_tthf_train_step(model, scale_cfg, dtype=jnp.float32,
                                     sync="tthf", fused_interval=True)
    flat0 = step.spec.flatten(
        stack_replicas(model.init(jax.random.PRNGKey(0)), R))
    losses, us = timed_intervals(jax.jit(step, donate_argnums=(0,)),
                                 flat0, net.num_clusters)
    losses_by_mode["tthf_fused_interval"] = losses
    us_by_mode["tthf_fused_interval"] = us
    rows.append(Row("scale_sync/tthf_fused_interval", us,
                    f"loss0={losses[0]:.4f};lossN={losses[-1]:.4f}"))

    # full trainer loop, speed knobs off vs on (donate + fused interval
    # + prefetch). Same seeds -> the two runs must land on bitwise-
    # identical params; only the wall clock may differ.
    def make_trainer(fast: bool) -> ScaleTrainer:
        return ScaleTrainer(
            cfg,
            TTHFScaleConfig(replicas=R, cluster_size=s, tau=tau,
                            consensus_every=2, gamma_d2d=2, lr=0.05,
                            consensus_mode="fused"),
            TrainerConfig(batch_per_replica=2, seq_len=64, eval_every=0,
                          dtype="float32", seed=seed, donate=fast,
                          fused_interval=fast, prefetch=fast))

    t_us, final = {}, {}
    for label, fast in (("trainer_straight", False), ("trainer_fast",
                                                      True)):
        tr = make_trainer(fast).init()
        tr.run(1)                          # warmup interval (excluded)
        jax.block_until_ready(tr.params)
        t0 = time.perf_counter()
        tr.run(intervals)
        jax.block_until_ready(tr.params)
        t_us[label] = (time.perf_counter() - t0) / intervals * 1e6
        final[label] = (tr._spec.unflatten(tr.params)
                        if tr._spec is not None else tr.params)
        rows.append(Row(f"scale_sync/{label}", t_us[label],
                        f"intervals={intervals};"
                        f"donate={fast};fused={fast};prefetch={fast}"))

    fast_bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(final["trainer_straight"]),
                        jax.tree.leaves(final["trainer_fast"])))
    fast_speedup = t_us["trainer_straight"] / t_us["trainer_fast"]

    # fused == rounds (same math)
    d = max(abs(a - b) for a, b in zip(losses_by_mode["tthf_fused"],
                                       losses_by_mode["tthf_rounds"]))
    d_loop = max(abs(a - b)
                 for a, b in zip(losses_by_mode["tthf_fused"],
                                 losses_by_mode["tthf_masked_loop"]))
    d_flat = max(abs(a - b)
                 for a, b in zip(losses_by_mode["tthf_fused"],
                                 losses_by_mode["tthf_fused_interval"]))
    prev = _prev_tthf_fused_us()
    vs_prev = (prev / us_by_mode["tthf_fused_interval"]
               if prev else float("nan"))
    rows.append(Row("scale_sync/claims", 0.0,
                    f"fused_equals_rounds={d < 1e-4};"
                    f"fused_equals_masked_loop={d_loop < 1e-4};"
                    f"fused_interval_bitwise={d_flat == 0.0};"
                    f"fast_params_bitwise={fast_bitwise};"
                    f"fast_trainer_speedup={fast_speedup:.2f}x;"
                    f"fused_interval_vs_prev_fused_row={vs_prev:.2f}x;"
                    f"tthf_trains={losses_by_mode['tthf_fused'][-1] < losses_by_mode['tthf_fused'][0]}"))
    append_trajectory("scale_sync", rows, scale)
    return rows
