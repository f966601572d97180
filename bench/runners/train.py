"""Training runner: one TT-HF job through ``ScaleTrainer``.

Set-up makes the weights from the seed (one jitted call), builds the
trainer with the job's replicas, clusters, tau, consensus calendar and
Gamma, and drives the first ``checked_steps`` aggregation intervals
through ``ScaleTrainer.run`` — the window's own call and feed: the
first interval alone, the others in one call with the trainer's
prefetch on, as in the window — keeping what the output check needs:
each interval's loss and the per-leaf change of the global model after
the first and the last of them. The
same trainer then runs the window: whole intervals, as many as fill
``--seconds`` at the warm interval time, in one ``run`` call (so the
trainer's prefetch is on), ending on ``block_until_ready``.

After the window and the memory reading, the trainer is freed and the
plain reference (``bench/tthf_reference.py`` over the configuration's
reference model) replays the checked intervals from the same weights on
the same rows at float32 ``HIGHEST``.

Every row of the feed differs: uniform tokens drawn from the seed, by
replica and draw (``tthf_reference.rows``); the program receives only
those rows.
"""
from __future__ import annotations

import time

import numpy as np

from bench import compare, harness, tthf_reference


def feed(seed: int, job: dict, vocab: int):
    """replica, start -> the replica's infinite stream of microbatches."""
    def stream(replica: int, start: int = 0):
        draw = start
        while True:
            yield tthf_reference.rows(seed, replica, draw,
                                      job["batch_per_replica"],
                                      job["seq_len"], vocab)
            draw += 1
    return stream


def make_trainer(cfg: dict, job: dict, seed: int):
    from repro.configs.base import ModelConfig
    from repro.core.distributed import TTHFScaleConfig
    from repro.train import ScaleTrainer, TrainerConfig

    if job["weights"] != "metropolis":
        raise ValueError("ScaleTrainer mixes with Metropolis-Hastings "
                         "weights only")
    stream = feed(seed, job, cfg["model"]["vocab_size"])

    class FeedTrainer(ScaleTrainer):
        """The trainer, fed the benchmark's rows instead of its own
        synthetic stream (there is no evaluation stream: eval is off)."""

        def _make_gens(self, train_start: int = 0, eval_start: int = 0):
            self._gens = [stream(r, train_start)
                          for r in range(self.scale.replicas)]
            self._eval_gen = None

    scale = TTHFScaleConfig(
        replicas=job["replicas"], cluster_size=job["cluster_size"],
        tau=job["tau"], consensus_every=job["consensus_every"],
        gamma_d2d=job["gamma_d2d"], lr=job["lr"],
        sample_per_cluster=job["sample_per_cluster"], graph=job["graph"])
    tcfg = TrainerConfig(
        batch_per_replica=job["batch_per_replica"], seq_len=job["seq_len"],
        intervals=1, eval_every=0, ckpt_every=0, dtype=job["dtype"],
        seed=seed & 0x7FFFFFFF)
    return FeedTrainer(ModelConfig(**cfg["model"]), scale, tcfg,
                       sync="tthf")


def check_layout(params, model) -> None:
    """The weights the benchmark made have the program's layout."""
    import jax
    want, _ = model.abstract_params()
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise ValueError("reference weights do not match the program's "
                         "parameter layout")


def setup(ctx: harness.Context):
    """(trainer holding the seed's weights, the weight maker, weights)."""
    import jax
    import jax.numpy as jnp
    cfg, job = ctx.cell.config, ctx.cell.traffic
    ref = harness.reference_module(cfg)
    R = job["replicas"]
    init = jax.jit(lambda k: ref.init(cfg["model"], k))
    params0 = init(harness.seed_key(ctx.seed))
    tr = make_trainer(cfg, job, ctx.seed)
    check_layout(params0, tr.model)
    tr.params = jax.jit(lambda p: jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (R,) + l.shape), p))(params0)
    return tr, init, params0


def checked_steps(tr, params0, steps: int) -> dict:
    """Drive the first ``steps`` intervals through ``ScaleTrainer.run``:
    the first alone, to read the global model's change after it, and
    the rest in one ``run`` call, as the window drives its intervals
    (the prefetch worker builds each next batch while one computes).
    Returns the losses, the per-leaf change of the global model after
    the first and the last interval, and the wall time of the first
    interval and of each later one (the mean over the second call)."""
    import jax
    replica0 = jax.jit(lambda p: jax.tree.map(lambda l: l[0], p))
    t0 = time.perf_counter()
    tr.run(1)
    t1 = time.perf_counter()
    d1 = tthf_reference.leaf_norms(replica0(tr.params), params0)
    warm = [t1 - t0]
    if steps > 1:
        t0 = time.perf_counter()
        tr.run(steps - 1)
        warm.append((time.perf_counter() - t0) / (steps - 1))
    losses = list(tr.metrics._recent["train_loss"])[-steps:]
    dlast = tthf_reference.leaf_norms(replica0(tr.params), params0)
    return {"losses": losses, "d1": d1, "dlast": dlast, "warm_s": warm}


def leaf_names(init) -> list:
    import jax
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(shapes)[0]]


def run(ctx: harness.Context) -> harness.Outcome:
    import jax

    cell, seed = ctx.cell, ctx.seed
    cfg, job = cell.config, cell.traffic
    steps = job["checked_steps"]
    tokens_per_interval = (job["tau"] * job["replicas"]
                           * job["batch_per_replica"] * job["seq_len"])
    tr, init, params0 = setup(ctx)
    got = checked_steps(tr, params0, steps)
    del params0
    warm = got.pop("warm_s")
    compiles_setup = ctx.clock.count
    t_interval = warm[-1]
    n = max(2, int(round(ctx.seconds / t_interval)))

    if ctx.profiler is not None:
        ctx.profiler.start()
    with harness.annotate("bench.window"):
        t0 = time.perf_counter()
        tr.run(n)
        jax.block_until_ready(tr.params)
        t1 = time.perf_counter()
    if ctx.profiler is not None:
        ctx.profiler.stop()
    window_s = t1 - t0
    window_loss = tr.metrics.last("train_loss")
    peak = harness.peak_bytes(ctx.devices)
    ctx.note(intervals=n, interval_s_warm=warm, window_s=window_s,
             compiles_in_window=ctx.clock.count - compiles_setup,
             compile_s_total=ctx.clock.total,
             uplinks=tr.ledger.uplinks, d2d_msgs=tr.ledger.d2d_msgs,
             program_losses=got["losses"], last_window_loss=window_loss)
    del tr
    harness.free_device_memory()

    # the reference replays the checked intervals from the seed's weights
    t_ref = time.perf_counter()
    want = tthf_reference.run(harness.reference_module(cfg), cfg["model"],
                              job, seed, init(harness.seed_key(seed)), steps)
    ctx.note(reference_losses=want["losses"],
             reference_s=time.perf_counter() - t_ref)
    readings = compare.training(got, want, leaf_names(init))
    ctx.note(**readings["info"])
    checks = [harness.Check(name, readings["values"][name], limit)
              for name, limit in cell.limits["limits"].items()]
    failed = int(not all(np.isfinite(got["losses"] + [window_loss])))
    return harness.Outcome(
        metrics={"train_tokens_per_s": n * tokens_per_interval / window_s,
                 "setup_s": t0 - ctx.t_start},
        attempted=steps + n, failed=failed, checks=checks,
        readings={"tokens": n * tokens_per_interval,
                  "seq_len": job["seq_len"]},
        memory_peak_bytes=peak)
