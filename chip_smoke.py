#!/usr/bin/env python3
"""On-chip smoke run of the system's two hot paths at mamba2-370m's
published widths (``configs/mamba2_370m.py``), with random weights.

  python chip_smoke.py              # one chip: train, serve, kernel
  python chip_smoke.py --chips 4    # four chips: sharded serving only

One chip:

* **train** — ``ScaleTrainer`` (``launch/train.py --mode scale``),
  ``sync="tthf"``, 4 replicas in 2 D2D clusters with a sampled global
  aggregation, 2 aggregation intervals; once per-leaf and once with
  ``fused_interval=True`` (which turns on the ``fused_consensus_sgd``
  Mosaic kernel). Losses must be finite and the two runs' global
  models must agree within ``PARAM_TOL``. Four replicas of all 48
  layers do not fit one chip's HBM, so training keeps
  ``TRAIN_LAYERS`` of them (printed; no width is cut).
* **serve** — the trained global model behind
  ``PagedContinuousScheduler`` (``launch/serve.py --scheduler paged``)
  and ``ContinuousScheduler`` on one arrival trace at temperature 0:
  every request completes and the two token streams are equal.
* **kernel** — ``kernels/paged_attn.paged_decode`` at qwen1.5-0.5b's
  attention widths against the jnp gather path of
  ``models/attention.py``, within ``KERNEL_TOL``.

Four chips (``--chips 4``, nothing else runs): the same trace, on all
48 layers, through the continuous scheduler on one device, on a 4x1
data mesh and on a
2x2 (data, model) mesh; data-parallel tokens must equal the single
device's bitwise, and teacher-forced tensor-parallel logits must agree
within ``TP_TOL`` (the checks of ``tests/test_serving_sharded.py``),
all at ``Precision.HIGHEST`` (see :func:`sharded`).

Each phase prints one JSON line of findings: compile seconds, smoke
timings (not benchmark metrics), ``peak_bytes_in_use``, losses and
parity numbers. The last line, printed only when every phase passed,
is ``{"ok": true, "device": {"platform", "kind", "count"}}``. Without
a TPU the script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ARCH = "mamba2-370m"
SEED = 0
# TT-HF layout: 4 replicas in 2 D2D clusters of 2, one D2D event after
# every local step, a sampled global aggregation closing each interval
REPLICAS, CLUSTER, TAU, CONSENSUS_EVERY, GAMMA = 4, 2, 2, 1, 2
BATCH, SEQ, INTERVALS, LR = 1, 512, 2, 1e-2
# depth cut for training only (widths untouched): 4 replicas of the
# 48-layer model need 18.44 GiB in the per-leaf interval step and
# 16.47 GiB fused, against 15.75 GiB of v5e HBM (memory_analysis() of
# the step compiled for a described v5e); 36 layers need 14.53 / 12.94
TRAIN_LAYERS = 36
PARAM_TOL = 1e-6        # fused_consensus_sgd's f32 contract
# serving trace: 4 slots, 8 requests, prompts of 32-128 tokens
SLOTS, REQUESTS, MIN_PROMPT, MAX_PROMPT, NEW_TOKENS = 4, 8, 32, 128, 32
KERNEL_TOL = 1e-5
TP_TOL = 1e-4


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


FAILED: list = []       # failed checks; later phases still run


def expect(ok: bool, msg: str) -> None:
    if not ok:
        print(f"chip_smoke: check failed: {msg}", file=sys.stderr,
              flush=True)
        FAILED.append(msg)


class CompileClock:
    """Seconds XLA spent compiling, read as deltas around each phase."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.total = 0.0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self._event:
            self.total += secs


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train(cfg, fused: bool, clock, dev):
    """Two TT-HF intervals; returns the global model (replica 0) as a
    host pytree, and the trainer's model."""
    import jax
    import numpy as np
    from repro.core.distributed import FlatParamSpec, TTHFScaleConfig
    from repro.train import ScaleTrainer, TrainerConfig

    scale = TTHFScaleConfig(replicas=REPLICAS, cluster_size=CLUSTER,
                            tau=TAU, consensus_every=CONSENSUS_EVERY,
                            gamma_d2d=GAMMA, lr=LR, seed=SEED)
    tcfg = TrainerConfig(batch_per_replica=BATCH, seq_len=SEQ,
                         intervals=INTERVALS, eval_every=0, seed=SEED,
                         fused_interval=fused)
    c0 = clock.total
    tr = ScaleTrainer(cfg, scale, tcfg, sync="tthf")
    tr.init()
    jax.block_until_ready(tr.params)
    losses, seconds = [], []
    for _ in range(INTERVALS):
        t0 = time.perf_counter()
        tr.run(1)
        jax.block_until_ready(tr.params)
        seconds.append(time.perf_counter() - t0)
        losses.append(tr.metrics.last("train_loss"))
    if fused:
        spec = FlatParamSpec.for_model(tr.model)
        params = spec.unflatten_one(tr.params[0])
    else:
        params = jax.tree.map(lambda l: l[0], tr.params)
    params = jax.tree.map(np.asarray, params)
    model = tr.model
    emit("train", mode="fused_interval" if fused else "per_leaf",
         layers=cfg.num_layers, replicas=REPLICAS, clusters=CLUSTER,
         tau=TAU, uplinks=tr.ledger.uplinks, d2d_msgs=tr.ledger.d2d_msgs,
         compile_s=clock.total - c0, interval_s_smoke=seconds,
         losses=losses, peak_bytes_in_use=peak_bytes(dev))
    expect(all(math.isfinite(l) for l in losses),
           f"train ({'fused' if fused else 'per-leaf'}): non-finite loss "
           f"{losses}")
    del tr
    gc.collect()
    return params, model


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def arrivals(cfg):
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(SEED)
    out, step = [], 0
    for rid in range(REQUESTS):
        plen = int(rng.integers(MIN_PROMPT, MAX_PROMPT + 1))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).astype(np.int32)
        out.append((step, Request(rid=rid, prompt=prompt,
                                  max_new=NEW_TOKENS)))
        step += int(rng.poisson(2.0))
    return out


def serve_tokens(model, params, kind: str, clock, mesh=None):
    """One trace through a scheduler: ({rid: tokens}, findings)."""
    from repro.serving import make_scheduler, run_trace, shard_params
    if mesh is not None:
        params = shard_params(params, model, mesh)
    trace = arrivals(model.cfg)
    c0 = clock.total
    sched = make_scheduler(kind, model, slots=SLOTS, max_prompt=MAX_PROMPT,
                           max_total=MAX_PROMPT + NEW_TOKENS,
                           temperature=0.0, seed=SEED, mesh=mesh)
    t0 = time.perf_counter()
    stats = run_trace(sched, params, trace)     # ends on a host read
    wall = time.perf_counter() - t0
    expect(stats.requests_done == REQUESTS,
           f"serve {kind}: {stats.requests_done}/{REQUESTS} requests "
           "completed")
    tokens = {req.rid: list(req.out_tokens) for _, req in trace}
    return tokens, {"scheduler": kind, "compile_s": clock.total - c0,
                    "wall_s_smoke": wall, "decode_steps": stats.decode_steps,
                    "tokens": stats.tokens_generated}


def first_divergence(a: dict, b: dict) -> dict:
    """{rid: first token index where two streams differ}."""
    out = {}
    for rid in a:
        diff = [i for i, (x, y) in enumerate(zip(a[rid], b[rid])) if x != y]
        if diff or len(a[rid]) != len(b[rid]):
            out[rid] = diff[0] if diff else min(len(a[rid]), len(b[rid]))
    return out


def serve(model, params, clock, dev):
    paged, f_paged = serve_tokens(model, params, "paged", clock)
    cont, f_cont = serve_tokens(model, params, "continuous", clock)
    same = sum(paged[r] == cont[r] for r in paged)
    emit("serve", paged=f_paged, continuous=f_cont,
         requests_equal=f"{same}/{len(paged)}",
         first_divergence=first_divergence(paged, cont),
         peak_bytes_in_use=peak_bytes(dev))
    expect(paged == cont, f"serve: paged tokens differ from continuous "
           f"in {len(paged) - same} of {len(paged)} requests")


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def kernel(clock, dev):
    """paged_decode at qwen1.5-0.5b's attention widths vs the jnp
    gather path, on a full random page pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch
    from repro.models.attention import init_attention, paged_decode_attention
    from repro.models.common import split_tree

    cfg = get_arch("qwen1.5-0.5b")
    B, ps, P = SLOTS, 16, 16
    K, hd = cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    p, _ = split_tree(init_attention(ks[0], cfg))
    num_pages = B * P + 1
    cache = {"k": jax.random.normal(ks[1], (1, num_pages, ps, K, hd)),
             "v": jax.random.normal(ks[2], (1, num_pages, ps, K, hd))}
    perm = np.random.default_rng(SEED).permutation(num_pages - 1) + 1
    page_map = jnp.asarray(perm.reshape(B, P), jnp.int32)
    pos = jnp.asarray([37, 100, P * ps - 1, 5], jnp.int32)
    x = jax.random.normal(ks[3], (B, 1, cfg.d_model))

    def run(use_kernel, precision):
        # f32 matmuls at DEFAULT precision are one bf16 MXU pass on a
        # TPU, and the two paths need not round alike: the parity check
        # runs both at HIGHEST, the DEFAULT gap is reported alongside
        with jax.default_matmul_precision(precision):
            f = jax.jit(lambda x, c, pos, pm: paged_decode_attention(
                p, cfg, x, c, pos, pm, use_kernel=use_kernel, layer=0)[0])
            return np.asarray(f(x, cache, pos, page_map))

    c0 = clock.total
    err = {prec: float(np.max(np.abs(run(True, prec) - run(False, prec))))
           for prec in ("highest", "default")}
    emit("kernel", kernel="paged_decode", kv_heads=K, head_dim=hd,
         page_size=ps, max_abs_diff=err["highest"], tol=KERNEL_TOL,
         max_abs_diff_default_precision=err["default"],
         compile_s=clock.total - c0, peak_bytes_in_use=peak_bytes(dev))
    expect(err["highest"] <= KERNEL_TOL,
           f"kernel: paged_decode differs from the gather path by "
           f"{err['highest']}")


# ---------------------------------------------------------------------------
# four chips: sharded serving
# ---------------------------------------------------------------------------

def tp_logits(model, params, mesh):
    """Teacher-forced prefill + decode logits, (B, 1 + G, V)."""
    from contextlib import nullcontext
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import serve_shardings, shard_params

    B, T, G = SLOTS, MAX_PROMPT, 4
    cfg = model.cfg
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    forced = jax.random.randint(jax.random.PRNGKey(2), (G, B, 1), 0,
                                cfg.vocab_size)
    ctx, kw_pf, kw_dec = nullcontext(), {}, {}
    if mesh is not None:
        sh = serve_shardings(model, mesh, slots=B, max_total=T + G,
                             dtype=jnp.float32)
        ctx, params = mesh, shard_params(params, model, mesh)
        kw_pf = {"out_shardings": (sh.logits, sh.cache, sh.replicated)}
        kw_dec = {"out_shardings": (sh.logits, sh.cache)}
    pf = jax.jit(lambda p, b: model.prefill(
        p, b, dtype=jnp.float32, cache_dtype=jnp.float32,
        cache_len=T + G), **kw_pf)
    dec = jax.jit(lambda p, t, c, s: model.decode_step(
        p, t, c, s, dtype=jnp.float32), **kw_dec)
    with ctx:
        lg, cache, pos = pf(params, {"tokens": tokens})
    outs = [np.asarray(lg)]
    for i in range(G):
        with ctx:
            lg, cache = dec(params, forced[i], cache, pos)
        pos = pos + 1
        outs.append(np.asarray(lg))
    return np.concatenate(outs, axis=1)


def sharded(cfg, clock, devs):
    """Called at Precision.HIGHEST, the f32 the CPU tests compare in: at
    DEFAULT an f32 matmul is one bf16 pass on a TPU, and the sharded
    and single-device programs, which sum in different orders and pick
    different dot strategies per shape, need not round alike."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    single, f_single = serve_tokens(model, params, "continuous", clock)
    data_mesh = make_serve_mesh("data")
    data, f_data = serve_tokens(model, params, "continuous", clock,
                                mesh=data_mesh)
    tp_mesh = make_serve_mesh("2x2")
    tp, f_tp = serve_tokens(model, params, "continuous", clock,
                            mesh=tp_mesh)
    c0 = clock.total
    ref = tp_logits(model, params, None)
    err = float(np.max(np.abs(ref - tp_logits(model, params, tp_mesh))))
    # reported only: the bitwise check of record is on the tokens
    err_data = float(np.max(np.abs(
        ref - tp_logits(model, params, data_mesh))))
    same_tp = sum(single[r] == tp[r] for r in single)
    emit("sharded", single=f_single, data_4x1=f_data, tp_2x2=f_tp,
         data_bitwise_equal=single == data,
         data_first_divergence=first_divergence(single, data),
         data_logits_max_abs_diff=err_data,
         tp_requests_equal=f"{same_tp}/{len(single)}",
         tp_logits_max_abs_diff=err, tp_tol=TP_TOL,
         tp_logits_compile_s=clock.total - c0,
         peak_bytes_in_use=[peak_bytes(d) for d in devs])
    expect(single == data,
           "sharded: data-parallel tokens differ from one device")
    expect(err <= TP_TOL, f"sharded: tensor-parallel logits differ by {err}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve + kernel on one chip; 4: "
                         "sharded serving on a four-chip host only")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"JAX found {len(devs)}")
    devs = devs[:args.chips]

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.configs import get_arch
    from repro.kernels.runtime import default_interpret
    from repro.launch.compile_cache import init_compile_cache

    emit("setup", device_kind=devs[0].device_kind, devices=len(devs),
         jax=jax.__version__, compile_cache=init_compile_cache())
    assert not default_interpret(), "kernels would run interpreted"
    clock = CompileClock()
    cfg = get_arch(ARCH)

    if args.chips == 4:
        with jax.default_matmul_precision("highest"):
            sharded(cfg, clock, devs)
    else:
        import numpy as np
        dev = devs[0]
        emit("train_cut", arch=ARCH, layers=TRAIN_LAYERS,
             published_layers=cfg.num_layers,
             reason="4 replicas of every layer exceed one chip's HBM")
        cfg = dataclasses.replace(cfg, num_layers=TRAIN_LAYERS)
        ref, _ = train(cfg, False, clock, dev)
        got, model = train(cfg, True, clock, dev)
        pairs = list(zip(jax.tree.leaves(got), jax.tree.leaves(ref)))
        err = max(float(np.max(np.abs(a - b))) for a, b in pairs)
        emit("train_parity", max_abs_diff=err, tol=PARAM_TOL,
             max_abs_param=max(float(np.max(np.abs(b))) for _, b in pairs),
             params=sum(a.size for a, _ in pairs))
        expect(err <= PARAM_TOL, f"train: fused_interval and per-leaf "
               f"global models differ by {err}")
        # serve the trained global model (the fused run's)
        params = jax.tree.map(jax.numpy.asarray, got)
        del ref, got, pairs
        serve(model, params, clock, dev)
        kernel(clock, dev)

    if FAILED:
        fail(f"{len(FAILED)} check(s): " + "; ".join(FAILED))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
