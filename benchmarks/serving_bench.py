"""Wave vs continuous batching under a Poisson arrival trace.

One reduced arch per family (dense / moe / ssm / hybrid) serves the
same seeded trace through both schedulers; the derived column records
decode steps, generated tokens, slot utilization, and wall-clock tok/s.
Continuous batching should finish the trace in fewer decode steps —
freed slots are re-prefilled while the rest of the batch keeps
decoding, instead of idling until the wave drains.

  PYTHONPATH=src python -m benchmarks.serving_bench
  PYTHONPATH=src python -m benchmarks.serving_bench --sharded
  PYTHONPATH=src python -m benchmarks.serving_bench --memory-ceiling

``--sharded`` additionally times the continuous scheduler on a
(data=2, model=4) mesh of 8 simulated host devices against the same
single-device trace (DESIGN.md §14). It runs in a subprocess because
the forced device count must be set before jax initializes.

``--memory-ceiling`` (DESIGN.md §15) serves the same shared-prefix
Poisson trace under a CAPPED cache byte budget through the ring
(continuous) and paged schedulers, recording requests-served-per-GB
within a fixed step horizon plus the paged prefix-hit-rate; a second
uncapped pass compares TTFT on the templated trace, attributing it to
queueing vs chunked prefill.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import Row, append_trajectory

ARCH_BY_KIND = {
    "dense": "qwen1.5-0.5b",
    "moe": "llama4-scout-17b-a16e",
    "ssm": "mamba2-370m",
    "hybrid": "recurrentgemma-9b",
}


def _reduced_cfg(name):
    from repro.configs import get_arch
    cfg = get_arch(name).reduced(num_layers=2, d_model=128, d_ff=256,
                                 vocab_size=256)
    if cfg.kind == "hybrid":
        cfg = dataclasses.replace(cfg, attention_window=16)
    if cfg.moe_num_experts:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    return cfg


def _trace(rng, n_req, max_prompt, gap):
    """Poisson arrivals with mixed prompt lengths and budgets."""
    from repro.serving import Request
    arrivals, step = [], 0
    for rid in range(n_req):
        plen = int(rng.integers(max(2, max_prompt // 4), max_prompt + 1))
        prompt = rng.integers(1, 250, size=plen).astype(np.int32)
        arrivals.append((step, Request(rid=rid, prompt=prompt,
                                       max_new=int(rng.integers(4, 13)))))
        step += int(rng.poisson(gap))
    return arrivals


def run(scale: str = "ci", seed: int = 0):
    import jax
    from repro.models import build_model
    from repro.serving import make_scheduler, run_trace

    n_req = 12 if scale == "ci" else 48
    slots, max_prompt, max_total = 4, 16, 48
    rows = []
    for kind, name in ARCH_BY_KIND.items():
        cfg = _reduced_cfg(name)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        per_sched = {}
        for sname in ("wave", "continuous"):
            rng = np.random.default_rng(seed)     # identical trace
            arrivals = _trace(rng, n_req, max_prompt, gap=1.0)
            sched = make_scheduler(sname, model, slots=slots,
                                   max_prompt=max_prompt,
                                   max_total=max_total, temperature=0.0,
                                   seed=seed)
            t0 = time.time()
            stats = run_trace(sched, params, arrivals)
            dt = time.time() - t0
            assert stats.requests_done == n_req, (kind, sname, stats)
            per_sched[sname] = stats
            # per-request latency percentiles (step-clock ticks) from
            # the retirement records the scheduler now keeps
            ql = np.array([r.queue_latency for r in stats.records])
            tt = np.array([r.ttft for r in stats.records if r.ttft >= 0])
            pf = np.array([r.prefill_latency for r in stats.records
                           if r.ttft >= 0])
            rows.append(Row(
                f"serving/{kind}/{sname}", dt * 1e6 / max(
                    stats.decode_steps, 1),
                f"decode_steps={stats.decode_steps};"
                f"toks={stats.tokens_generated};"
                f"util={stats.utilization:.3f};"
                f"tok_per_step={stats.tokens_generated / max(stats.decode_steps, 1):.2f};"
                f"tok_s={stats.tokens_generated / max(dt, 1e-9):.1f};"
                f"queue_p50={np.percentile(ql, 50):.0f};"
                f"queue_p95={np.percentile(ql, 95):.0f};"
                f"prefill_p50={np.percentile(pf, 50):.0f};"
                f"ttft_p50={np.percentile(tt, 50):.0f};"
                f"ttft_p95={np.percentile(tt, 95):.0f}"))
        w, c = per_sched["wave"], per_sched["continuous"]
        rows.append(Row(
            f"serving/{kind}/speedup", 0.0,
            f"steps_wave={w.decode_steps};steps_cont={c.decode_steps};"
            f"step_ratio={w.decode_steps / max(c.decode_steps, 1):.2f}"))
    append_trajectory("serving", rows, scale)
    return rows


def _shared_prefix_trace(rng, n_req, template, max_prompt, gap):
    """Poisson arrivals whose prompts all start with one fixed
    ``template``-token prefix (the prefix-sharing regime: after the
    first admission the trie serves the template pages to everyone)."""
    from repro.serving import Request
    tmpl = rng.integers(1, 250, size=template).astype(np.int32)
    arrivals, step = [], 0
    for rid in range(n_req):
        tail = rng.integers(
            1, 250, size=int(rng.integers(4, max_prompt - template + 1)))
        prompt = np.concatenate([tmpl, tail]).astype(np.int32)
        arrivals.append((step, Request(rid=rid, prompt=prompt,
                                       max_new=int(rng.integers(4, 13)))))
        step += int(rng.poisson(gap))
    return arrivals


def _lat(stats):
    """(queue_p50, prefill_p50, ttft_p50, mean_chunks) from records —
    TTFT = queue_latency + prefill_latency, so the pair attributes it
    to queueing vs (chunked) prefill."""
    recs = [r for r in stats.records if r.ttft >= 0]
    if not recs:
        return -1.0, -1.0, -1.0, 0.0
    q = float(np.percentile([r.queue_latency for r in recs], 50))
    p = float(np.percentile([r.prefill_latency for r in recs], 50))
    t = float(np.percentile([r.ttft for r in recs], 50))
    ch = float(np.mean([r.prefill_chunks for r in recs]))
    return q, p, t, ch


def run_memory_ceiling(scale: str = "ci", seed: int = 0):
    """Ring vs paged under one capped cache byte budget (DESIGN.md §15).

    Both schedulers get the SAME cache bytes: the ring spends them on
    ``ring_slots`` fixed (max_total)-token lanes; the paged pool spends
    them on pages that prefix sharing and per-request page counts keep
    mostly full. Within a fixed step horizon the paged scheduler must
    serve strictly more requests per GB on the shared-prefix trace.
    """
    import warnings

    import jax
    from repro.models import build_model
    from repro.serving import make_scheduler, run_trace

    n_req = 16 if scale == "ci" else 64
    horizon = 60 if scale == "ci" else 240
    page_size, template = 8, 8
    slots, max_prompt, max_total = 4, 16, 48
    ring_slots = 2
    cfg = _reduced_cfg(ARCH_BY_KIND["dense"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    # the capped budget: bytes for ring_slots full-length ring lanes
    # (f32 cache: layers * K/V * kv_heads * head_dim * 4B per token)
    tok_bytes = cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 4
    budget_tokens = ring_slots * max_total
    budget_gb = budget_tokens * tok_bytes / 1e9
    cache_pages = budget_tokens // page_size + 1    # same bytes, paged

    rows, done = [], {}
    for sname in ("continuous", "paged"):
        rng = np.random.default_rng(seed)           # identical trace
        arrivals = _shared_prefix_trace(rng, n_req, template,
                                        max_prompt, gap=1.0)
        kw = dict(max_prompt=max_prompt, max_total=max_total,
                  temperature=0.0, seed=seed)
        if sname == "paged":
            sched = make_scheduler("paged", model, slots=slots,
                                   page_size=page_size,
                                   cache_pages=cache_pages, **kw)
        else:
            sched = make_scheduler("continuous", model,
                                   slots=ring_slots, **kw)
        t0 = time.time()
        with warnings.catch_warnings():
            # the horizon intentionally truncates the trace
            warnings.simplefilter("ignore", RuntimeWarning)
            stats = run_trace(sched, params, arrivals, max_steps=horizon)
        dt = time.time() - t0
        done[sname] = stats.requests_done
        q50, p50, t50, chunks = _lat(stats)
        extra = ""
        if sname == "paged":
            reused = sum(r.prefix_pages_reused for r in stats.records)
            extra = (f";prefix_hit_rate={sched.prefix_hit_rate:.2f};"
                     f"pages_reused={reused};"
                     f"deferrals={sched.page_deferrals};"
                     f"mean_chunks={chunks:.1f}")
        rows.append(Row(
            f"serving/memceil/{sname}",
            dt * 1e6 / max(stats.decode_steps, 1),
            f"budget_mb={budget_gb * 1e3:.2f};"
            f"done_at_h{horizon}={stats.requests_done};"
            f"requests_per_gb={stats.requests_done / budget_gb:.0f};"
            f"toks={stats.tokens_generated};"
            f"queue_p50={q50:.0f};prefill_p50={p50:.0f};"
            f"ttft_p50={t50:.0f}" + extra))
    assert done["paged"] > done["continuous"], (
        "paged must serve strictly more requests per GB than ring "
        f"under the capped budget: {done}")
    rows.append(Row(
        "serving/memceil/gain", 0.0,
        f"ring_done={done['continuous']};paged_done={done['paged']};"
        f"ratio={done['paged'] / max(done['continuous'], 1):.2f}"))

    # --- uncapped templated-prefix pass: TTFT must not regress --------
    ttft = {}
    for sname in ("continuous", "paged"):
        rng = np.random.default_rng(seed)
        arrivals = _shared_prefix_trace(rng, n_req, template,
                                        max_prompt, gap=1.0)
        kw = dict(slots=slots, max_prompt=max_prompt,
                  max_total=max_total, temperature=0.0, seed=seed)
        if sname == "paged":
            sched = make_scheduler("paged", model, page_size=page_size,
                                   **kw)
        else:
            sched = make_scheduler("continuous", model, **kw)
        stats = run_trace(sched, params, arrivals)
        assert stats.requests_done == n_req
        q50, p50, t50, chunks = _lat(stats)
        ttft[sname] = t50
        extra = ""
        if sname == "paged":
            reused = sum(r.prefix_pages_reused for r in stats.records)
            assert reused > 0, "templated trace must reuse prefix pages"
            extra = (f";pages_reused={reused};"
                     f"prefix_hit_rate={sched.prefix_hit_rate:.2f};"
                     f"mean_chunks={chunks:.1f}")
        rows.append(Row(
            f"serving/ttft_template/{sname}", 0.0,
            f"queue_p50={q50:.0f};prefill_p50={p50:.0f};"
            f"ttft_p50={t50:.0f}" + extra))
    assert ttft["paged"] <= ttft["continuous"], (
        "paged TTFT regressed vs ring on short templated prompts", ttft)
    append_trajectory("serving", rows, scale)
    return rows


SHARDED_KINDS = ("dense", "ssm")
SHARDED_MESH = "2x4"        # data=2, model=4 over 8 forced host devices
SHARDED_NDEV = 8


def _run_sharded_child(scale: str, seed: int):
    """Child entry: runs under XLA_FLAGS forcing 8 host devices. Times
    the same continuous-batching trace single-device and on the
    (data, model) mesh, printing one JSON line the parent parses."""
    import jax
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model
    from repro.serving import make_scheduler, run_trace, shard_params

    n_req = 12 if scale == "ci" else 48
    slots, max_prompt, max_total = 4, 16, 48
    out = []
    for kind in SHARDED_KINDS:
        cfg = _reduced_cfg(ARCH_BY_KIND[kind])
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        for spec in (None, SHARDED_MESH):
            mesh = make_serve_mesh(spec) if spec else None
            p = shard_params(params, model, mesh) if mesh else params
            rng = np.random.default_rng(seed)     # identical trace
            arrivals = _trace(rng, n_req, max_prompt, gap=1.0)
            sched = make_scheduler("continuous", model, slots=slots,
                                   max_prompt=max_prompt,
                                   max_total=max_total, temperature=0.0,
                                   seed=seed, mesh=mesh)
            t0 = time.time()
            stats = run_trace(sched, p, arrivals)
            dt = time.time() - t0
            assert stats.requests_done == n_req, (kind, spec, stats)
            out.append({
                "kind": kind, "mesh": spec or "single",
                "devices": 1 if mesh is None else int(mesh.devices.size),
                "wall_s": dt, "decode_steps": stats.decode_steps,
                "tokens": stats.tokens_generated,
                "util": stats.utilization})
    print(json.dumps(out))


def run_sharded(scale: str = "ci", seed: int = 0):
    """Parent entry for ``--sharded``: fork a child with the forced
    host device count, parse its JSON, append rows to BENCH_serving.

    Forced host devices are CPU devices: on a TPU host the child would
    time the CPU under the serving benchmark's name, so this refuses to
    run there (the sharded check of record on the chip is
    ``chip_smoke.py --chips 4``)."""
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "serving_bench --sharded simulates devices on the host CPU; "
            "on a TPU host run `python chip_smoke.py --chips 4` instead")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count="
                        + str(SHARDED_NDEV))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.serving_bench",
         "--child-sharded", "--scale", scale, "--seed", str(seed)],
        capture_output=True, text=True, timeout=1800, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"sharded child failed:\n{out.stderr[-2000:]}")
    recs = json.loads(out.stdout.splitlines()[-1])
    rows = []
    for r in recs:
        rows.append(Row(
            f"serving/sharded/{r['kind']}/{r['mesh']}",
            r["wall_s"] * 1e6 / max(r["decode_steps"], 1),
            f"devices={r['devices']};decode_steps={r['decode_steps']};"
            f"toks={r['tokens']};util={r['util']:.3f};"
            f"tok_s={r['tokens'] / max(r['wall_s'], 1e-9):.1f}"))
    append_trajectory("serving", rows, scale)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--sharded", action="store_true",
                    help="also bench the continuous scheduler on a "
                         f"{SHARDED_MESH} mesh of {SHARDED_NDEV} forced "
                         "host devices (subprocess)")
    ap.add_argument("--child-sharded", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--memory-ceiling", action="store_true",
                    help="ring vs paged under one capped cache byte "
                         "budget on a shared-prefix trace (requests/GB, "
                         "prefix hit rate, TTFT attribution)")
    ap.add_argument("--scale", default="ci", choices=["ci", "full"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.child_sharded:
        _run_sharded_child(args.scale, args.seed)
    elif args.sharded:
        for row in run_sharded(args.scale, args.seed):
            print(row.csv())
    elif args.memory_ceiling:
        for row in run_memory_ceiling(args.scale, args.seed):
            print(row.csv())
    else:
        for row in run(args.scale, args.seed):
            print(row.csv())
