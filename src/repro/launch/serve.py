"""Serving driver: batched prefill + decode of a model-zoo arch.

Four modes:
  direct      — one fixed batch, joint prefill, lockstep decode
  wave        — BatchScheduler: admit a wave, drain, admit the next
  continuous  — ContinuousScheduler: per-slot admission/retirement
  paged       — PagedContinuousScheduler: block/page KV cache with
                prefix sharing + chunked prefill (DESIGN.md §15);
                tune with --page-size/--cache-pages/--prefill-chunk,
                exercise prefix sharing with --prefix-template

Multi-device: ``--mesh host|data|AxB`` serves sharded over this
process's devices (params tensor-parallel over ``model``, cache leaves
along heads/experts, slots over ``data`` — DESIGN.md §14).
``--host-devices N`` forces N simulated host devices (must be the
FIRST jax configuration of the process; it sets XLA_FLAGS before jax
initializes).

Example (CPU, reduced config):
  python -m repro.launch.serve --arch mamba2-370m --reduced \
      --batch 4 --prompt-len 64 --gen 16
  python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --scheduler continuous --requests 12 --gen 16 \
      --host-devices 8 --mesh host
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np


def _run_scheduler(args, cfg, model, params, mesh):
    import jax.numpy as jnp
    from repro.obs.sink import make_obs
    from repro.serving import Request, make_scheduler, run_trace

    rng = np.random.default_rng(args.seed)
    obs = make_obs(args.trace_dir, profile=args.profile,
                   run_name="serve",
                   config={"args": vars(args)},
                   extra={"arch": cfg.name, "scheduler": args.scheduler,
                          "mesh": args.mesh or "single",
                          "devices": 1 if mesh is None
                          else int(mesh.devices.size)})
    cache_dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[
        args.cache_dtype]
    kw = dict(slots=args.batch, max_prompt=args.prompt_len,
              max_total=args.prompt_len + args.gen,
              temperature=args.temperature, seed=args.seed,
              cache_dtype=cache_dtype, obs=obs, mesh=mesh)
    if args.scheduler == "paged":
        kw["page_size"] = args.page_size
        if args.cache_pages:
            kw["cache_pages"] = args.cache_pages
        if args.prefill_chunk:
            kw["prefill_chunk"] = args.prefill_chunk
    sched = make_scheduler(args.scheduler, model, **kw)
    arrivals = []
    step = 0
    tmpl = None
    if args.prefix_template:
        # shared template prefix across every prompt — the prefix-
        # sharing trace: after the first admission the trie serves the
        # template's full pages to everyone else
        tmpl = rng.integers(1, cfg.vocab_size,
                            size=args.prefix_template).astype(np.int32)
    for rid in range(args.requests):
        plen = int(rng.integers(max(1, args.prompt_len // 4),
                                args.prompt_len + 1))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).astype(np.int32)
        if tmpl is not None:
            prompt = np.concatenate(
                [tmpl, prompt])[:args.prompt_len].astype(np.int32)
        arrivals.append((step, Request(rid=rid, prompt=prompt,
                                       max_new=args.gen)))
        step += int(rng.poisson(args.arrival_gap))
    t0 = time.time()
    try:
        stats = run_trace(sched, params, arrivals)
        if obs.enabled:
            # one JSONL record per retired request — queue latency and
            # TTFT in step-clock ticks, same stream as everything else
            for r in stats.records:
                obs.emit("request", r.retire, rid=r.rid,
                         submit=r.submit, admit=r.admit,
                         first_token=r.first_token,
                         queue_latency=r.queue_latency, ttft=r.ttft,
                         decode=r.decode, budget=r.budget,
                         prefill_chunks=r.prefill_chunks,
                         prefix_pages_reused=r.prefix_pages_reused)
    finally:
        obs.close()
    dt = time.time() - t0
    ndev = 1 if mesh is None else int(mesh.devices.size)
    print(f"arch={cfg.name} scheduler={args.scheduler} slots={args.batch} "
          f"requests={args.requests} devices={ndev}")
    print(f"done={stats.requests_done} prefills={stats.prefills} "
          f"decode_steps={stats.decode_steps} "
          f"tokens={stats.tokens_generated} "
          f"util={stats.utilization:.2f} "
          f"({stats.tokens_generated / max(dt, 1e-9):.1f} tok/s)")
    if stats.records:
        ql = np.array([r.queue_latency for r in stats.records])
        tt = np.array([r.ttft for r in stats.records if r.ttft >= 0])
        if len(tt):
            print(f"queue latency (steps): p50={np.percentile(ql, 50):.0f} "
                  f"p95={np.percentile(ql, 95):.0f}  "
                  f"ttft: p50={np.percentile(tt, 50):.0f} "
                  f"p95={np.percentile(tt, 95):.0f}")
    if args.scheduler == "paged":
        reused = sum(r.prefix_pages_reused for r in stats.records)
        print(f"pages: size={sched.page_size} pool={sched.cache_pages} "
              f"free={sched.table.num_free} "
              f"prefix_hit_rate={sched.prefix_hit_rate:.2f} "
              f"pages_reused={reused} "
              f"deferrals={sched.page_deferrals}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--scheduler", default="direct",
                    choices=["direct", "wave", "continuous", "paged"],
                    help="direct: one fixed batch; wave/continuous/"
                         "paged: request schedulers over --requests "
                         "arrivals")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of requests for scheduler modes")
    ap.add_argument("--cache-dtype", default="f32",
                    choices=["f32", "bf16"],
                    help="KV/state cache storage dtype (compute stays "
                         "f32)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged scheduler: tokens per cache page")
    ap.add_argument("--cache-pages", type=int, default=0,
                    help="paged scheduler: total page-pool size incl. "
                         "the dummy page (0 = ring-equivalent capacity); "
                         "smaller pools trade capacity for deferrals")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="paged scheduler: prefill chunk length in "
                         "tokens, page-size multiple (0 = one-shot)")
    ap.add_argument("--prefix-template", type=int, default=0,
                    help="share a random N-token template prefix across "
                         "all prompts (prefix-sharing trace)")
    ap.add_argument("--arrival-gap", type=float, default=2.0,
                    help="mean Poisson inter-arrival gap (decode steps)")
    ap.add_argument("--mesh", default=None,
                    help="serve sharded over this process's devices: "
                         "'host' (all tensor-parallel), 'data' (all "
                         "data-parallel), or 'AxB' (data x model)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N simulated host devices (sets XLA_FLAGS "
                         "before jax initializes)")
    ap.add_argument("--trace-dir", default=None,
                    help="observability dir (repro.obs): Chrome trace, "
                         "per-request latency JSONL, run manifest")
    ap.add_argument("--profile", action="store_true",
                    help="also wrap the run in jax.profiler.trace")
    args = ap.parse_args(argv)

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.host_devices}")

    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.launch.compile_cache import init_compile_cache
    from repro.models import build_model
    from repro.serving import sample_tokens, serve_shardings, shard_params

    init_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    key = jax.random.PRNGKey(args.seed + 1)

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(args.mesh)
        params = shard_params(params, model, mesh)

    if args.scheduler != "direct":
        return _run_scheduler(args, cfg, model, params, mesh)

    B, T = args.batch, args.prompt_len
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.kind == "vlm":
        batch["patches"] = jax.random.normal(
            key, (B, cfg.enc_seq_len, cfg.d_model)) * 0.1
    if cfg.kind in ("encdec", "audio"):
        batch["frames"] = jax.random.normal(
            key, (B, cfg.enc_seq_len, cfg.d_model)) * 0.1

    total = T + args.gen + (cfg.enc_seq_len if cfg.kind == "vlm" else 0)
    jit_kw_pf, jit_kw_dec = {}, {}
    from contextlib import nullcontext
    ctx = nullcontext() if mesh is None else mesh
    if mesh is not None:
        sh = serve_shardings(model, mesh, slots=B, max_total=total,
                             dtype=jnp.float32)
        jit_kw_pf = {"out_shardings": (sh.logits, sh.cache,
                                       sh.replicated)}
        jit_kw_dec = {"out_shardings": (sh.logits, sh.cache)}
    t0 = time.time()
    prefill = jax.jit(lambda p, b: model.prefill(
        p, b, dtype=jnp.float32, cache_dtype=jnp.float32,
        cache_len=total), **jit_kw_pf)
    with ctx:
        logits, cache, pos = prefill(params, batch)
    t_prefill = time.time() - t0
    decode = jax.jit(lambda p, t, c, s: model.decode_step(
        p, t, c, s, dtype=jnp.float32), donate_argnums=(2,), **jit_kw_dec)

    out_tokens = []
    t0 = time.time()
    for i in range(args.gen):
        key, ks = jax.random.split(key)
        tok = sample_tokens(logits, temperature=args.temperature, key=ks)
        out_tokens.append(np.asarray(tok)[:, 0])
        with ctx:
            logits, cache = decode(params, tok, cache, pos)
        pos = pos + 1
    t_decode = time.time() - t0

    gen = np.stack(out_tokens, axis=1)
    ndev = 1 if mesh is None else int(mesh.devices.size)
    print(f"arch={cfg.name} B={B} prompt={T} gen={args.gen} "
          f"devices={ndev}")
    print(f"prefill: {t_prefill:.2f}s  decode: {t_decode:.2f}s "
          f"({args.gen * B / max(t_decode, 1e-9):.1f} tok/s)")
    print("sampled token ids (first row):", gen[0].tolist())
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
