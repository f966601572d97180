"""Scale-mode trainer: the TT-HF interval loop with evaluation,
checkpointing, and metric logging — the production loop around
`core.distributed.make_tthf_train_step`.

Handles: data sharding per replica, interval batching
(tau x R x b x T), periodic held-out eval of the *global* (sampled)
model, checkpoint save/resume, and the communication ledger. Every
scenario — static, netsim dynamics, fog hierarchy, compositions —
runs through ONE ``_interval``: the
:class:`~repro.rounds.resolver.RoundResolver` turns the declarative
:class:`~repro.rounds.program.RoundProgram` into the step's
aggregation argument, the optional consensus-matrix refresh, and one
:class:`~repro.rounds.program.Billing` record (DESIGN.md §10).
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_train_state, save_train_state
from repro.configs.base import DynamicsConfig, HierarchyConfig, ModelConfig
from repro.core.distributed import (
    TTHFScaleConfig, make_tthf_train_step, stack_replicas)
from repro.core.energy import CommLedger
from repro.core.mixing import build_mixing_plan
from repro.data.tokens import synthetic_token_batches
from repro.models import ModelApi, build_model
from repro.obs.sink import make_obs
from repro.rounds import RoundProgram, RoundResolver
from repro.train.metrics import MetricLogger
from repro.train.prefetch import PrefetchLoader

# the only dtypes the microstep math supports; anything else (a typo'd
# "float16") used to silently coerce to bfloat16
_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@dataclass
class TrainerConfig:
    batch_per_replica: int = 4
    seq_len: int = 256
    intervals: int = 10
    eval_every: int = 5
    eval_batches: int = 2
    ckpt_every: int = 0             # 0 = off
    ckpt_dir: str = "checkpoints"
    log_path: Optional[str] = None
    dtype: str = "float32"
    seed: int = 0
    # raw-speed knobs (DESIGN.md §12) — all preserve trajectories
    # bitwise; flip off to A/B against the straight-line path
    donate: bool = True             # donate params+batch buffers to the
                                    # jitted step (halves peak param HBM)
    fused_interval: bool = False    # flat (R, rows, 128) param carrier
                                    # + fused SGD+consensus block-ends
    prefetch: bool = True           # build/transfer interval k+1's
                                    # batch while interval k computes
    # observability (repro.obs, DESIGN.md §13): a trace dir turns on
    # the span tracer + theory-bound telemetry stream + run manifest;
    # profile additionally wraps the run in jax.profiler.trace
    trace_dir: Optional[str] = None
    profile: bool = False

    def __post_init__(self):
        if self.dtype not in _DTYPES:
            raise ValueError(
                f"unknown dtype {self.dtype!r}; expected one of "
                f"{sorted(_DTYPES)}")


class ScaleTrainer:
    def __init__(self, cfg: ModelConfig, scale: TTHFScaleConfig,
                 tcfg: TrainerConfig, sync: str = "tthf",
                 dynamics: Optional[DynamicsConfig] = None,
                 hierarchy: Optional[HierarchyConfig] = None,
                 program: Optional[RoundProgram] = None):
        self.cfg = cfg
        self.scale = scale
        self.tcfg = tcfg
        self.model: ModelApi = build_model(cfg)
        dtype = _DTYPES[tcfg.dtype]
        # the declarative round program (DESIGN.md §10): a static (or
        # absent) dynamics config and a flat (L = 2) hierarchy resolve
        # to the exact historical code path bit-for-bit; the
        # ``dynamics``/``hierarchy`` kwargs are sugar for a program
        if program is None:
            program = RoundProgram(dynamics=dynamics, hierarchy=hierarchy)
        else:
            assert dynamics is None and hierarchy is None, \
                "pass either program= or the dynamics=/hierarchy= sugar " \
                "kwargs, not both (the kwargs would be silently ignored)"
        self.program = program
        if program.is_hierarchical:
            assert sync == "tthf", "hierarchy implies tthf sync"
        if program.is_adaptive:
            assert sync == "tthf", \
                "the control plane retunes D2D consensus — star/local " \
                "sync has no Γ to control"
            from repro.core.mixing import canonical_backend
            assert canonical_backend(scale.consensus_mode) \
                == "fused_power", \
                "per-interval Γ retuning folds into refreshed " \
                "W = V^Γ — only the fused_power backend consumes it"
        # only a tthf step carries consensus matrices to refresh; the
        # event stream ticks once per aggregation interval and each
        # interval's matrices are fed to the (once-traced) step. The
        # control plane rides the same path: per-interval Γ retunes
        # arrive as refreshed W = V^Γ matrices
        refreshable = ((program.is_dynamic or program.is_adaptive)
                       and sync == "tthf")
        step, self.net = make_tthf_train_step(
            self.model, scale, dtype=dtype, sync=sync,
            refreshable=refreshable, hierarchy=program.hierarchy,
            fused_interval=tcfg.fused_interval)
        # fused-interval runs carry self.params as the step's flat
        # (R, rows, 128) buffer; the spec unflattens at eval/checkpoint/
        # serving
        # boundaries (checkpoints stay in the pytree format either way)
        self._spec = getattr(step, "spec", None)
        self._plan = None
        if refreshable:
            self._plan = build_mixing_plan(
                self.net, scale.gamma_d2d, backend=scale.consensus_mode)
        self._resolver = RoundResolver.for_scale(self.net, scale, program,
                                                 plan=self._plan)
        self.hierarchy = self._resolver.hierarchy
        self.tree = self._resolver.tree
        self.tvnet = self._resolver.tvnet
        # donation contract (DESIGN.md §12): once a step is dispatched,
        # the params (and batch) buffers passed in belong to XLA — the
        # trainer rebinds self.params to the output before anyone reads
        # it, and every consumer (eval/save/serving) goes through that
        # rebound value. Holders of pre-step references must copy.
        # The int32 batch can never alias the f32 outputs, so donating
        # it only frees its buffer for scratch — silence the per-compile
        # "not usable" nag about exactly that.
        if tcfg.donate:
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
        self._step = jax.jit(
            step, donate_argnums=(0, 1) if tcfg.donate else ())
        self._eval_loss = jax.jit(
            lambda p, b: self.model.loss(p, b, dtype=dtype, remat=False))
        self.ledger = CommLedger()
        self.metrics = MetricLogger(tcfg.log_path)
        # observability sink (NULL_OBS when trace_dir unset): spans,
        # theory-bound telemetry, manifest. Probes are built lazily at
        # init() (they need the materialized params) and are read-only
        # — instrumented trajectories are bitwise the uninstrumented
        # ones (tests/test_obs.py).
        self.obs = make_obs(
            tcfg.trace_dir, profile=tcfg.profile, run_name="train-scale",
            config={"model": cfg, "scale": scale, "trainer": tcfg},
            extra={"arch": cfg.name, "sync": sync})
        self._resolver.obs = self.obs
        self._obs_probe = None
        self._obs_grad_probe = None
        self._obs_gauges = None
        self._obs_gen = None        # dedicated grad-probe batch stream
        self.key = jax.random.PRNGKey(tcfg.seed)
        self._make_gens()
        # resume fidelity: batches drawn so far from every train
        # generator (identical across replicas) and from the eval
        # stream — persisted so restore-and-continue replays neither
        self._train_draws = 0
        self._eval_draws = 0
        self.params = None
        # hierarchical runs: the SERVED global model — materialized
        # only when the root tier fires (between root events replicas
        # under different fog nodes legitimately disagree)
        self._global = None
        self.interval = 0

    def _make_gens(self, train_start: int = 0, eval_start: int = 0):
        """(Re)build the token streams, optionally already seeked past
        the first ``train_start``/``eval_start`` draws — restore uses
        this for O(1) resume instead of replaying consumed batches."""
        tcfg, cfg = self.tcfg, self.cfg
        self._gens = [synthetic_token_batches(
            tcfg.batch_per_replica, tcfg.seq_len, cfg.vocab_size,
            seed=tcfg.seed, shard_id=r, start=train_start)
            for r in range(self.scale.replicas)]
        self._eval_gen = synthetic_token_batches(
            tcfg.batch_per_replica, tcfg.seq_len, cfg.vocab_size,
            seed=tcfg.seed + 10_000, shard_id=99, start=eval_start)

    # ------------------------------------------------------------------
    def init(self):
        init_params = self.model.init(jax.random.PRNGKey(self.tcfg.seed))
        self.params = stack_replicas(init_params, self.scale.replicas)
        if self._spec is not None:
            self.params = self._spec.flatten(self.params)
        # only hierarchical runs serve a root snapshot; a flat run would
        # hold a whole extra model on the device for nothing
        self._global = init_params if self.tree is not None else None
        return self

    def _build_interval_batch(self):
        """Pure batch build — no draw accounting (the prefetch worker
        calls this off-thread; draws are counted at consumption)."""
        tau, R = self.scale.tau, self.scale.replicas
        mbs = [[next(g) for _ in range(tau)] for g in self._gens]
        return {k: jnp.asarray(np.stack(
            [[mbs[r][t][k] for r in range(R)] for t in range(tau)]))
            for k in ("tokens", "labels")}

    def _interval_batch(self):
        batch = self._build_interval_batch()
        self._train_draws += self.scale.tau
        return batch

    def _replica0(self):
        """Replica 0's per-replica param pytree (either carrier)."""
        if self._spec is not None:
            return self._spec.unflatten_one(self.params[0])
        return jax.tree.map(lambda l: l[0], self.params)

    def _global_params(self):
        """The served global model. Flat runs: replica 0's copy —
        identical to all others right after the interval's aggregation
        (asserted in tests). Hierarchical runs: the root-tier snapshot
        (the initial broadcast until the root first fires — replicas
        under different fog nodes disagree between root events)."""
        if self.tree is not None:
            return self._global
        return self._replica0()

    def evaluate(self) -> float:
        g = self._global_params()
        losses = []
        for _ in range(self.tcfg.eval_batches):
            b = next(self._eval_gen)
            self._eval_draws += 1
            losses.append(float(self._eval_loss(
                g, {k: jnp.asarray(v) for k, v in b.items()})))
        return float(np.mean(losses))

    # ------------------------------------------------------------------
    # observability (DESIGN.md §13)
    # ------------------------------------------------------------------
    def _ensure_obs(self):
        from repro.obs.telemetry import (
            TheoryGauges, default_constants, make_divergence_probe,
            make_scale_grad_probe)

        if self._obs_probe is not None:
            return
        self._obs_probe = make_divergence_probe(
            self.scale.num_clusters, self.scale.cluster_size,
            self.net.varrho)
        self._obs_grad_probe = make_scale_grad_probe(
            self.model, _DTYPES[self.tcfg.dtype])
        # a dedicated probe stream: grad-norm batches never touch the
        # train/eval draws, so the data trajectory is unchanged
        self._obs_gen = synthetic_token_batches(
            self.tcfg.batch_per_replica, self.tcfg.seq_len,
            self.cfg.vocab_size, seed=self.tcfg.seed + 20_000,
            shard_id=98)
        model_dim = int(sum(np.prod(l.shape) for l in
                            jax.tree.leaves(self._replica0())))
        self._obs_gauges = TheoryGauges(
            constants=default_constants(float(np.min(self.net.varrho))),
            tau=self.scale.tau, model_dim=model_dim, lr=self.scale.lr)

    def _emit_interval_telemetry(self, loss, ledger_mark, ev=None):
        """One fenced drain per interval: block on the step's loss, run
        the jitted probe over the (donated-output) params, and emit
        measured divergence + theory gauges + comms attribution into
        the shared JSONL stream. ``self.interval`` is still the 0-based
        index of the interval that just ran."""
        obs = self.obs
        jax.block_until_ready(loss)
        aux = {k: np.asarray(v)
               for k, v in self._obs_probe(self.params).items()}
        tau = self.scale.tau
        t = (self.interval + 1) * tau
        rec = {"train_loss": float(loss), **aux}
        rec.update(self._obs_gauges.round_gauges(t, t - tau))
        if self.scale.consensus_every:
            N = self.scale.num_clusters
            if ev is not None and ev.control is not None:
                # the controller's realized (billed) per-cluster rounds
                rec["gamma_used"] = np.asarray(
                    ev.billing.consensus_gammas)
            else:
                rec["gamma_used"] = np.full((N,), self.scale.gamma_d2d)
            rec["lemma1_bound"] = self._obs_gauges.lemma1(
                self.net.lambdas, rec["gamma_used"],
                self.scale.cluster_size, aux["upsilon"])
        if ev is not None and ev.control is not None:
            # decision + measured-vs-assumed λ joined into the same row
            rec.update(ev.control.round_fields())
        obs.emit("round", self.interval + 1, **rec)
        rows = self.ledger.attribution_since(ledger_mark)
        if rows:
            up_lv, d2d_cl = {}, {}
            ups = msgs = rounds = 0
            for r in rows:
                if r["kind"] == "uplink":
                    ups += r["n"]
                    up_lv[r["level"]] = up_lv.get(r["level"], 0) + r["n"]
                elif r["kind"] == "consensus":
                    msgs += r["msgs"]
                    rounds += r["rounds"]
                    c = r["cluster"]
                    d2d_cl[c] = d2d_cl.get(c, 0) + r["msgs"]
            obs.emit("comm", self.interval + 1, uplinks=ups,
                     uplinks_by_level=up_lv, d2d_msgs=msgs,
                     d2d_rounds=rounds, d2d_msgs_by_cluster=d2d_cl,
                     event=self.ledger._event_idx)
        obs.counter("ledger", uplinks=self.ledger.uplinks,
                    d2d_msgs=self.ledger.d2d_msgs,
                    local_steps=self.ledger.local_steps)

    def _interval(self, batch, kp):
        """ONE interval for every scenario: the resolver supplies the
        step's aggregation argument (picks / (N, s) weight matrix /
        composed (R, R) device matrix — whichever form the step was
        built for), the optional per-aggregation-round consensus-matrix
        refresh, and the interval's full bill."""
        obs = self.obs
        ledger_mark = len(self.ledger.events)
        ev = self._resolver.resolve_interval(self.interval, kp)
        args = (self.params, batch, ev.agg, jnp.asarray(self.interval))
        with obs.span("interval", interval=self.interval,
                      tau=self.scale.tau):
            if ev.refresh is not None:
                self.params, loss = self._step(*args, ev.refresh)
            else:
                self.params, loss = self._step(*args)
            if obs.enabled:
                jax.block_until_ready(loss)
        if ev.root_served:
            # a live root event just broadcast the root model to every
            # replica — snapshot it as the served global model
            self._global = self._replica0()
        ev.billing.charge(self.ledger)
        if obs.enabled:
            # the jitted interval folds its consensus/aggregation
            # events into one dispatch — mark them as instants so the
            # trace still shows the two timescales
            if ev.billing.consensus_repeats and \
                    ev.billing.consensus_edges is not None:
                obs.instant("consensus_event", interval=self.interval,
                            repeats=ev.billing.consensus_repeats)
            if ev.billing.uplinks_by_level:
                obs.instant("aggregation", interval=self.interval,
                            uplinks_by_level=ev.billing.uplinks_by_level,
                            root_served=ev.root_served)
            self._emit_interval_telemetry(loss, ledger_mark, ev)
        return loss

    def save(self, path: Optional[str] = None):
        p = path or str(Path(self.tcfg.ckpt_dir)
                        / f"interval_{self.interval:06d}.npz")
        Path(p).parent.mkdir(parents=True, exist_ok=True)
        # resume fidelity: the PRNG key, the comm ledger, and the data
        # stream positions all travel with the params — a restored run
        # continues exactly where an uninterrupted one would be
        extra = {
            "key": np.asarray(self.key),
            "train_draws": np.asarray(self._train_draws),
            "eval_draws": np.asarray(self._eval_draws),
            "ledger": {k: np.asarray(v) for k, v in
                       dataclasses.asdict(self.ledger).items()
                       if not isinstance(v, (dict, list))},
            "uplinks_by_level": {
                str(k): np.asarray(v)
                for k, v in self.ledger.uplinks_by_level.items()},
        }
        if self.tree is not None:
            extra["global"] = self._global   # the served root snapshot
        # checkpoints always hold the pytree form — fused and straight
        # runs read each other's checkpoints freely
        params = (self._spec.unflatten(self.params)
                  if self._spec is not None else self.params)
        save_train_state(p, params, (), self.interval, extra=extra)
        return p

    def restore(self, path: str):
        self.params, _, self.interval, extra = restore_train_state(path)
        if self._spec is not None:
            self.params = self._spec.flatten(self.params)
        if self.tree is not None:
            # the served root snapshot (pre-hierarchy checkpoints lack
            # it: fall back to replica 0, exact from the next root on)
            self._global = extra.get("global", self._replica0())
        if "key" in extra:
            self.key = jnp.asarray(extra["key"])
            self._train_draws = int(extra["train_draws"])
            self._eval_draws = int(extra["eval_draws"])
            for k, v in extra["ledger"].items():
                setattr(self.ledger, k, type(getattr(self.ledger, k))(v))
            self.ledger.uplinks_by_level = {
                int(k): int(v)
                for k, v in extra.get("uplinks_by_level", {}).items()}
            # rebuild FRESH data streams already seeked past the
            # consumed batches (a reused trainer's generators may have
            # advanced). The seek is O(1) — the streams are
            # offset-addressable — so resume cost no longer grows with
            # training progress.
            self._make_gens(train_start=self._train_draws,
                            eval_start=self._eval_draws)
        return self

    # ------------------------------------------------------------------
    def run(self, intervals: Optional[int] = None):
        if self.params is None:
            self.init()
        obs = self.obs
        if obs.enabled:
            self._ensure_obs()
        n = intervals if intervals is not None else self.tcfg.intervals
        loader = None
        if self.tcfg.prefetch and n > 1:
            # interval k+1's batch builds/transfers while k computes;
            # draws are counted HERE per consumed batch, so a mid-run
            # checkpoint never includes the in-flight prefetched batch
            loader = PrefetchLoader(self._build_interval_batch, depth=1)
        try:
            with obs.span("run", intervals=n, tau=self.scale.tau,
                          replicas=self.scale.replicas):
                for _ in range(n):
                    with obs.span("round", interval=self.interval):
                        if loader is not None:
                            batch = loader.get()
                            self._train_draws += self.scale.tau
                        else:
                            batch = self._interval_batch()
                        self.key, kp = jax.random.split(self.key)
                        loss = self._interval(batch, kp)
                        self.interval += 1
                        logs = {"train_loss": float(loss),
                                "uplinks": self.ledger.uplinks,
                                "d2d_msgs": self.ledger.d2d_msgs}
                        if self.tcfg.eval_every and \
                                self.interval % self.tcfg.eval_every == 0:
                            with obs.span("eval", interval=self.interval):
                                logs["eval_loss"] = self.evaluate()
                            if obs.enabled:
                                b = {k: jnp.asarray(v) for k, v in
                                     next(self._obs_gen).items()}
                                logs["grad_norm"] = float(
                                    self._obs_grad_probe(
                                        self._global_params(), b))
                                obs.emit("eval", self.interval, **logs)
                        self.metrics.log(self.interval, **logs)
                        if self.tcfg.ckpt_every and \
                                self.interval % self.tcfg.ckpt_every == 0:
                            self.save()
        finally:
            if loader is not None:
                loader.close()
            obs.flush()
        return self

    def close(self):
        """Flush + close the metric and observability sinks (exports
        the Chrome trace when a trace dir is set)."""
        self.metrics.close()
        self.obs.close()
