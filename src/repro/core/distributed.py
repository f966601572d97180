"""TT-HF *scale mode*: the paper's two-timescale sync as a first-class
distributed-training strategy for the model zoo (DESIGN.md §3-4).

Mapping:
  FL device  -> model replica  = one slice of the (pod, data) axes
                (each replica holds a full copy, tensor-sharded over
                 ``model``)
  cluster    -> a contiguous block of replicas (on the multi-pod mesh a
                cluster == a pod, so D2D = intra-pod ICI and global
                aggregation = cross-pod traffic — the paper's
                cheap-links/expensive-uplink dichotomy, verbatim)
  local SGD  -> tau microsteps with NO cross-replica collective
  D2D round  -> block-diagonal mixing einsum over the replica axis
  global agg -> cluster-sampled, varrho-weighted average + broadcast

One ``train_step`` call = one full aggregation interval T_k (Algorithm 1
lines 4-15): nested scans [blocks x consensus_every microsteps] keep the
consensus events static in the HLO (aperiodicity via the *fixed* event
calendar; the Remark-1 adaptive round count is a simulation-mode
feature — scale mode takes Gamma from config).

Consensus execution dispatches through the unified engine
(:mod:`repro.core.mixing`, DESIGN.md §5).  ``consensus_mode`` is a
backend name; the legacy aliases remain the §Perf comparison axis:
  * ``rounds`` (-> ``reference``) — paper-faithful: Gamma sequential
    ``z <- V z`` products, one neighbour exchange each (what edge
    devices must do);
  * ``fused``  (-> ``fused_power``) — beyond-paper: W = V^Gamma is
    precomputed ONCE at step-build time and applied as ONE mixing
    einsum; on a TPU mesh every cluster member is reachable, so Gamma
    exchanges collapse into one collective of the same payload.
    Identical math (associativity), ~Gamma x less launch + latency
    cost.  Per-cluster aperiodic Gamma_c vectors (Remark 1) are now
    supported in scale mode — each cluster's block of W gets its own
    power.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import TopologyConfig
from repro.core.mixing import MixingPlan, build_mixing_plan, mix_blocks
from repro.core.topology import Network, build_network
from repro.dist.sharding import drop_hint_axes
from repro.hierarchy.aggregate import apply_device_matrix_pytree
from repro.models.registry import ModelApi
from repro.netsim.faults import weighted_global_pytree


@dataclass(frozen=True)
class TTHFScaleConfig:
    replicas: int = 16              # I (devices) = replica count
    cluster_size: int = 4           # s_c
    tau: int = 20                   # local interval length
    consensus_every: int = 5        # D2D event calendar
    gamma_d2d: int = 2              # rounds per event (static)
    consensus_mode: str = "fused"   # mixing backend (core/mixing.py):
                                    # fused|rounds aliases or reference|
                                    # masked_loop|pallas|fused_power
    lr: float = 1e-2
    sample_per_cluster: int = 1
    graph: str = "ring"             # TPU-native default
    granularity: str = "dp"         # dp (replica = data rank) | pod
    seed: int = 0

    @property
    def num_clusters(self) -> int:
        assert self.replicas % self.cluster_size == 0
        return self.replicas // self.cluster_size

    def network(self) -> Network:
        return build_network(TopologyConfig(
            num_devices=self.replicas, num_clusters=self.num_clusters,
            graph=self.graph, seed=self.seed))


# ---------------------------------------------------------------------------
# replica-axis consensus / aggregation (pjit-native: collectives emerge
# from the replica-axis sharding of the mixing einsum)
# ---------------------------------------------------------------------------

def consensus_event(params, net: Network, gamma, mode: str = "fused"):
    """One D2D consensus event over the replica axis.

    ``gamma`` may be a scalar or a per-cluster (N,) vector (Remark-1
    heterogeneous round counts); ``mode`` is a mixing backend name or
    one of the legacy aliases ("fused", "rounds").  Thin wrapper over
    :func:`repro.core.mixing.build_mixing_plan` — prefer building the
    plan once at step-build time (as ``make_tthf_train_step`` does)
    instead of calling this per event.
    """
    plan = build_mixing_plan(net, gamma, backend=mode)
    return plan.apply_pytree(params)


def replica_blocks(leaf: jax.Array, num_clusters: int) -> jax.Array:
    """(R, ...) -> (N, s, ...): splits only the leading replica axis,
    so no parameter is ever viewed as one long row (see
    :mod:`repro.core.mixing` for why that matters on a TPU)."""
    R = leaf.shape[0]
    return leaf.reshape((num_clusters, R // num_clusters)
                        + (leaf.shape[1:] or (1,)))


def _broadcast_replicas(w_hat: jax.Array, leaf: jax.Array) -> jax.Array:
    return jnp.broadcast_to(w_hat[None], (leaf.shape[0],) + w_hat.shape
                            ).reshape(leaf.shape)


def sampled_aggregation(params, net: Network, picks: jax.Array):
    """eq. (7): w_hat = sum_c varrho_c w_{n_c}; broadcast to all replicas.

    The static-topology path. Under netsim dynamics the aggregation is
    :func:`weighted_aggregation` instead — availability-renormalized
    per-device weights rather than one pick per cluster. Leaves are
    any (R, ...) arrays, the fused interval's flat carrier included."""
    varrho = jnp.asarray(net.varrho, jnp.float32)
    picks = picks.astype(jnp.int32)

    def one(leaf):
        z = replica_blocks(leaf, net.num_clusters)
        v = varrho.astype(leaf.dtype)
        w_hat = v[0] * jax.lax.dynamic_index_in_dim(z[0], picks[0],
                                                    keepdims=False)
        for c in range(1, z.shape[0]):
            w_hat = w_hat + v[c] * jax.lax.dynamic_index_in_dim(
                z[c], picks[c], keepdims=False)
        return _broadcast_replicas(w_hat, leaf)

    return jax.tree.map(one, params)


def weighted_aggregation(params, net: Network, weights: jax.Array):
    """Availability-aware eq. (7) over the replica axis.

    ``weights``: the (N, s) per-device aggregation weight matrix from
    :func:`repro.netsim.faults.aggregation_weights` — EVERY sampled
    replica enters the aggregate with its renormalized weight (the
    ledger's uplink count and the aggregate agree under
    ``sample_per_cluster > 1``), and a dark cluster's devices carry 0.
    The global model is broadcast to every replica (replicas are
    physical shards — scale-mode churn shapes the sync pattern, not the
    broadcast); an all-dark event (weights sum to 0) is the identity.
    """
    g = weighted_global_pytree(params, weights, net.num_clusters)
    alive = weights.sum() > 0

    def one(gl, pl):
        return jnp.where(alive, jnp.broadcast_to(gl[None], pl.shape), pl)

    return jax.tree.map(one, g, params)


def full_aggregation(params, net: Network):
    """Star/FedAvg baseline: full-participation weighted mean."""
    varrho = jnp.asarray(net.varrho, jnp.float32)

    def one(leaf):
        z = replica_blocks(leaf, net.num_clusters).mean(axis=1)
        v = varrho.astype(leaf.dtype).reshape((-1,) + (1,) * (z.ndim - 1))
        return _broadcast_replicas((v * z).sum(axis=0), leaf)

    return jax.tree.map(one, params)


# ---------------------------------------------------------------------------
# the flattened replica buffer of the fused interval (DESIGN.md §12)
# ---------------------------------------------------------------------------

LANE = 128      # TPU lane width: the carrier's minor dim
ROW_ALIGN = 128     # carrier rows pad to this, so a model axis divides


@dataclass(frozen=True)
class FlatParamSpec:
    """Layout of the flat ``(R, rows, LANE)`` replica buffer.

    The fused interval (``make_tthf_train_step(fused_interval=True)``)
    carries every replica's parameters as ONE ``(R, rows, LANE)`` array:
    each leaf fills whole LANE-wide rows (zero-padded to a LANE multiple;
    per-replica layout — shapes here exclude the leading replica axis),
    leaves packed back-to-back along the rows, and the row count padded
    to a ``ROW_ALIGN`` multiple. SGD updates and consensus mixing then
    run as single whole-buffer ops instead of per-leaf launches;
    :meth:`unflatten` is only needed at aggregation/eval boundaries.

    Why rows and not one ``(R, P)`` row per replica: on a TPU the
    replica axis of an ``(R, P)`` array sits in the tiled second-minor
    dim, so every ``(N, s, P)`` cluster view is a relayout whose
    compile time grows with P — far past any budget at 368M parameters.
    Leading-axis splits of ``(R, rows, LANE)`` are free.

    Mixing/aggregation correctness under padding: every interval op is
    elementwise linear over the replica axis, so zero pad entries stay
    zero and real entries are untouched by the packing.
    """
    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    offsets: tuple[int, ...]    # first row of each leaf
    sizes: tuple[int, ...]      # elements of each leaf
    dtype: Any
    total: int          # real elements (sum of leaf sizes)
    rows: int           # carrier rows per replica

    @classmethod
    def for_tree(cls, tree) -> "FlatParamSpec":
        """Build from a per-replica pytree of arrays/ShapeDtypeStructs
        (leaf shapes WITHOUT the replica axis)."""
        leaves, treedef = jax.tree.flatten(tree)
        assert leaves, "empty parameter pytree"
        dtypes = {jnp.dtype(l.dtype) for l in leaves}
        assert len(dtypes) == 1, \
            f"flat buffer needs a uniform param dtype, got {dtypes}"
        shapes = tuple(tuple(int(d) for d in l.shape) for l in leaves)
        sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
        leaf_rows = [-(-n // LANE) for n in sizes]
        offsets = tuple(int(o) for o in
                        np.concatenate([[0], np.cumsum(leaf_rows)[:-1]]))
        rows = -(-sum(leaf_rows) // ROW_ALIGN) * ROW_ALIGN
        return cls(treedef=treedef, shapes=shapes, offsets=offsets,
                   sizes=sizes, dtype=dtypes.pop(), total=int(sum(sizes)),
                   rows=rows)

    @classmethod
    def for_model(cls, model: ModelApi, dtype=jnp.float32) -> "FlatParamSpec":
        p_abs, _ = model.abstract_params(dtype=dtype)
        return cls.for_tree(p_abs)

    @property
    def padded(self) -> int:
        """Carrier elements per replica (``rows * LANE``)."""
        return self.rows * LANE

    # -- conversions ----------------------------------------------------
    def flatten(self, tree) -> jax.Array:
        """Replicated pytree (leaves (R, *shape)) -> flat (R, rows, LANE).

        Leaves are cast to the spec dtype (the reference microstep's
        ``g.astype(w.dtype)`` contract for gradient trees)."""
        leaves = jax.tree.flatten(tree)[0]
        R = leaves[0].shape[0]
        parts = []
        for l, n in zip(leaves, self.sizes):
            l = l.astype(self.dtype)
            r = -(-n // LANE)
            if n == r * LANE:
                parts.append(l.reshape(R, r, LANE))
            else:
                parts.append(jnp.pad(l.reshape(R, n),
                                     ((0, 0), (0, r * LANE - n))
                                     ).reshape(R, r, LANE))
        used = sum(p.shape[1] for p in parts)
        if used != self.rows:
            parts.append(jnp.zeros((R, self.rows - used, LANE), self.dtype))
        return jnp.concatenate(parts, axis=1)

    def _leaves(self, flat: jax.Array, lead: tuple):
        out = []
        for o, n, s in zip(self.offsets, self.sizes, self.shapes):
            r = -(-n // LANE)
            x = flat[..., o:o + r, :]
            if n != r * LANE:
                x = x.reshape(lead + (r * LANE,))[..., :n]
            out.append(x.reshape(lead + s))
        return jax.tree.unflatten(self.treedef, out)

    def unflatten(self, flat: jax.Array):
        """Flat (R, rows, LANE) -> replicated pytree (leaves (R, *shape))."""
        return self._leaves(flat, flat.shape[:1])

    def unflatten_one(self, row: jax.Array):
        """One replica's (rows, LANE) -> per-replica pytree."""
        return self._leaves(row, ())

    def abstract(self, replicas: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((replicas, self.rows, LANE), self.dtype)


# ---------------------------------------------------------------------------
# the TT-HF interval step
# ---------------------------------------------------------------------------

def make_tthf_train_step(model: ModelApi, scale: TTHFScaleConfig, *,
                         dtype=jnp.bfloat16, remat: bool = True,
                         sync: str = "tthf", refreshable: bool = False,
                         hierarchy=None, fused_interval: bool = False,
                         fused_kernel: Optional[bool] = None,
                         param_dtype=jnp.float32):
    """Returns step(params_R, batch, agg, step_idx, ...) -> (params_R, loss).

    params_R: every leaf has leading replica axis R.

    ``fused_interval=True`` builds the flat-buffer variant (DESIGN.md
    §12): the step carries parameters as ONE ``(R, rows, LANE)``
    array (:class:`FlatParamSpec`; the returned ``step`` exposes it as
    ``step.spec``), SGD updates and consensus mixing run as whole-buffer
    ops instead of per-leaf launches, and each consensus block's last
    SGD update fuses with the ``W = V^Gamma`` mixing product — one
    read-w/read-g/write-mixed-w parameter-stream pass
    (:mod:`repro.kernels.fused_consensus_sgd`) instead of two.
    Trajectories are BITWISE the reference path's in f32
    (``tests/test_fused_interval.py``). ``fused_kernel`` forces the
    Pallas kernel on/off for that fused block-end (None = auto: kernel
    on real TPUs, the identical-math XLA einsum off-TPU);
    ``param_dtype`` fixes the buffer dtype. Only the ``fused_power``
    ("fused") consensus backend fuses; other backends keep their exact
    per-event semantics on the flat buffer.
    batch: {"tokens": (tau, R, b, T), "labels": ...} — one aggregation
    interval's worth of microbatches.
    sync: "tthf" (Algorithm 1) | "star" (FedAvg: full participation,
    no D2D) | "local" (no sync at all — diagnostics).

    The aggregation argument ``agg`` depends on the mode — one fixed
    form per build, so each step traces exactly once:

    * default — ``picks``: (N,) int32 sampled representative per
      cluster (the historical signature, bit-for-bit preserved);
    * ``sample_per_cluster > 1`` or ``refreshable=True`` — ``agg_w``:
      the (N, s) per-device aggregation weight matrix from
      :func:`repro.netsim.faults.aggregation_weights`. All k sampled
      replicas per cluster enter the aggregate (the multi-sampling
      the ledger bills — the static path used to draw ONE device and
      bill N uplinks), dark clusters carry weight 0, and an all-dark
      event is the identity. ``refreshable=True`` (netsim dynamics)
      additionally takes ``mix_refresh``, the
      per-aggregation-round consensus matrices from
      :func:`repro.core.mixing.refresh_matrices` (stacked powers
      ``W = V^Gamma`` for the ``fused`` backend, the masked ``V``
      otherwise) — churned replicas hold their parameters through
      every consensus event of the interval;
    * ``hierarchy`` (a non-flat :class:`~repro.configs.base.
      HierarchyConfig`) — ``agg_m``: the composed (R, R) device matrix
      of a :class:`~repro.hierarchy.aggregate.HierarchyEvent`. Its
      fixed shape encodes ANY aggregation depth (hold-rows included),
      so one compilation serves every interval of an L-level run; the
      per-level weight matrices change per call, never the HLO. A flat
      (L = 2) hierarchy config is exactly TT-HF and takes the
      historical ``picks`` path. Composes with ``refreshable``
      (``mix_refresh`` stays the last argument).
    """
    net = scale.network()
    if hierarchy is not None and hierarchy.is_flat:
        hierarchy = None            # plain TT-HF: the historical path
    if hierarchy is not None:
        assert sync == "tthf", "hierarchical aggregation implies tthf sync"
        assert hierarchy.taus[0] == scale.tau, \
            f"tier-1 period {hierarchy.taus[0]} must equal the " \
            f"interval length tau={scale.tau}"
        assert hierarchy.sample[0] == scale.sample_per_cluster, \
            f"tier-1 fan-in {hierarchy.sample[0]} must equal " \
            f"sample_per_cluster={scale.sample_per_cluster}"
    assert scale.tau % scale.consensus_every == 0
    n_blocks = scale.tau // scale.consensus_every
    # one build-time plan: for fused_power this precomputes W = V^Gamma
    # exactly once (numpy) instead of re-deriving it inside the step
    plan: MixingPlan | None = None
    if sync == "tthf":
        plan = build_mixing_plan(net, scale.gamma_d2d,
                                 backend=scale.consensus_mode)

    # which mesh axes carry replicas: dp granularity -> (pod, data);
    # pod granularity (giant models: a replica needs a whole pod's HBM,
    # FSDP over `data` stays *inside* the replica) -> (pod,)
    replica_axes = (("pod",) if scale.granularity == "pod"
                    else ("pod", "data"))

    def replica_loss(p, mb):
        # the replica axes are carried by the vmap dim; model/data
        # hints still apply inside each replica
        with drop_hint_axes(replica_axes):
            return model.loss(p, mb, dtype=dtype, remat=remat)

    def microstep(params, mb, lr):
        """vmapped per-replica SGD (eq. 8-9) — zero cross-replica comms."""
        losses, grads = jax.vmap(
            lambda p, m: jax.value_and_grad(replica_loss)(p, m))(params, mb)
        # lr cast per-leaf: an f32 scalar would promote bf16 params
        params = jax.tree.map(
            lambda w, g: w - jnp.asarray(lr, w.dtype) * g.astype(w.dtype),
            params, grads)
        return params, jnp.mean(losses)

    # one aggregation form per build — the jitted step traces exactly
    # once; multi-sampling routes through the (N, s) weight form so
    # every billed uplink actually enters the aggregate
    agg_kind = ("matrix" if hierarchy is not None
                else "weights" if (refreshable or
                                   scale.sample_per_cluster > 1)
                else "picks")

    if fused_interval:
        return _make_fused_interval_step(
            model, scale, net=net, plan=plan, sync=sync,
            refreshable=refreshable, agg_kind=agg_kind,
            n_blocks=n_blocks, replica_loss=replica_loss,
            fused_kernel=fused_kernel, param_dtype=param_dtype)

    def interval(params, batch, agg, mix_refresh):
        lr = jnp.asarray(scale.lr, jnp.float32)
        # (tau, R, b, T) -> (blocks, consensus_every, R, b, T)
        def resh(x):
            return x.reshape((n_blocks, scale.consensus_every) + x.shape[1:])
        batch_b = jax.tree.map(resh, batch)

        def block(params, block_batch):
            def inner(params, mb):
                params, loss = microstep(params, mb, lr)
                return params, loss
            params, losses = jax.lax.scan(inner, params, block_batch)
            if plan is not None:
                params = plan.apply_pytree(params, refresh=mix_refresh)
            return params, jnp.mean(losses)

        params, block_losses = jax.lax.scan(block, params, batch_b)
        if sync == "tthf":
            if agg_kind == "picks":
                params = sampled_aggregation(params, net, agg)
            elif agg_kind == "weights":
                params = weighted_aggregation(params, net, agg)
            else:
                params = apply_device_matrix_pytree(params, agg)
        elif sync == "star":
            params = full_aggregation(params, net)
        return params, jnp.mean(block_losses)

    if refreshable:
        def step(params, batch, agg, step_idx, mix_refresh):
            return interval(params, batch, agg, mix_refresh)
    else:
        def step(params, batch, agg, step_idx):
            return interval(params, batch, agg, None)

    return step, net


def _make_fused_interval_step(model: ModelApi, scale: TTHFScaleConfig, *,
                              net: Network, plan: Optional[MixingPlan],
                              sync: str, refreshable: bool, agg_kind: str,
                              n_blocks: int, replica_loss,
                              fused_kernel: Optional[bool],
                              param_dtype) -> tuple[Any, Network]:
    """The ``fused_interval=True`` build — see ``make_tthf_train_step``.

    Arithmetic mirrors the reference interval exactly: grads come from
    the identical unflattened tree, the SGD update is the same
    elementwise expression on the concatenated buffer, and mixing and
    aggregation run the very functions of the per-leaf path on the
    carrier, elementwise identical — so fused and reference trajectories
    are bitwise equal in f32 (asserted in tests and in
    ``benchmarks/scale_sync.py``).
    """
    spec = FlatParamSpec.for_model(model, dtype=param_dtype)
    N = net.num_clusters
    if fused_kernel is None:
        from repro.kernels.runtime import default_interpret
        # auto: Mosaic kernel on real TPUs; off-TPU the XLA mixing below
        # IS the fused pass after fusion, and skipping pallas interpret
        # overhead keeps the CPU path fast
        fused_kernel = not default_interpret()
    if fused_kernel:
        from repro.kernels.fused_consensus_sgd import (
            fused_consensus_sgd as _fused_kernel_fn)

    def grad_flat(flat, mb):
        """Mean loss + flat (R, rows, LANE) grads; pads stay zero."""
        losses, grads = jax.vmap(
            lambda p, m: jax.value_and_grad(replica_loss)(p, m)
        )(spec.unflatten(flat), mb)
        return spec.flatten(grads), jnp.mean(losses)

    def interval(flat, batch, agg, mix_refresh):
        lr = jnp.asarray(scale.lr, jnp.float32)
        mix_active = plan is not None and not (plan.is_noop and
                                               mix_refresh is None)

        def resh(x):
            return x.reshape((n_blocks, scale.consensus_every) + x.shape[1:])
        batch_b = jax.tree.map(resh, batch)

        def sgd(flat, mb):
            """One microstep on the flat carrier — bitwise-critical.

            The update runs in the PYTREE domain and the updated tree
            reflattens (a concat XLA fuses into the update writes, so
            the carry stays one buffer with no extra HBM pass).
            Updating the flat buffer directly against flattened GRADS
            instead fuses the concat into the grad epilogue and
            re-vectorizes it — a 1-ulp drift vs the reference step on
            non-lane-aligned models.
            """
            params = spec.unflatten(flat)
            losses, grads = jax.vmap(
                lambda p, m: jax.value_and_grad(replica_loss)(p, m)
            )(params, mb)
            params = jax.tree.map(
                lambda w, g: w - jnp.asarray(lr, w.dtype)
                * g.astype(w.dtype), params, grads)
            return spec.flatten(params), jnp.mean(losses)

        # W available => the block-end collapses to ONE mixing pass
        W0 = plan.fused_w(mix_refresh) if mix_active else None
        kernel_end = fused_kernel and mix_active and W0 is not None

        def block(flat, block_batch):
            if kernel_end:
                # Pallas path: the LAST microstep's SGD update fuses
                # with the mixing product — one read-w/read-g/
                # write-mixed-w HBM pass (repro.kernels.
                # fused_consensus_sgd). The inline last-step grad can
                # re-vectorize vs the in-scan instance, so this path
                # carries the kernel tolerance contract, not the
                # bitwise one (it is auto-selected on TPUs only).
                head = jax.tree.map(lambda x: x[:-1], block_batch)
                last = jax.tree.map(lambda x: x[-1], block_batch)
                flat, head_losses = jax.lax.scan(sgd, flat, head)
                g, last_loss = grad_flat(flat, last)
                flat = _fused_kernel_fn(
                    replica_blocks(flat, N), replica_blocks(g, N),
                    W0, lr).reshape(flat.shape)
                losses = jnp.concatenate([head_losses, last_loss[None]])
                return flat, jnp.mean(losses)
            # XLA path — bitwise contract: the microstep scan matches
            # the reference structure exactly (splitting the last step
            # out of the scan compiles its grad graph in a different
            # fusion context — a 1-ulp drift on non-lane-aligned
            # models), then the block-end applies as ONE whole-buffer
            # op instead of per-leaf launches
            flat, losses = jax.lax.scan(sgd, flat, block_batch)
            if mix_active:
                z = replica_blocks(flat, N)
                # a non-fused_power backend keeps its exact per-event
                # semantics on the flat buffer
                z = (mix_blocks(W0, z) if W0 is not None
                     else plan.apply(z, refresh=mix_refresh))
                flat = z.reshape(flat.shape)
            return flat, jnp.mean(losses)

        flat, block_losses = jax.lax.scan(block, flat, batch_b)
        if sync == "tthf":
            if agg_kind == "picks":
                flat = sampled_aggregation(flat, net, agg)
            elif agg_kind == "weights":
                flat = weighted_aggregation(flat, net, agg)
            else:
                flat = apply_device_matrix_pytree(flat, agg)
        elif sync == "star":
            flat = full_aggregation(flat, net)
        return flat, jnp.mean(block_losses)

    if refreshable:
        def step(flat, batch, agg, step_idx, mix_refresh):
            return interval(flat, batch, agg, mix_refresh)
    else:
        def step(flat, batch, agg, step_idx):
            return interval(flat, batch, agg, None)

    step.spec = spec
    return step, net


# ---------------------------------------------------------------------------
# sharding plumbing
# ---------------------------------------------------------------------------

def replica_axes_tree(axes_tree):
    """Prefix every logical-axes tuple with the replica axis."""
    return jax.tree.map(lambda a: ("replica",) + tuple(a), axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


TTHF_PARAM_RULES = (
    ("replica", ("pod", "data")),
    # within-replica: tensor parallel over model ONLY (a replica must be
    # self-contained — no fsdp over the replica axes)
    ("embed", None),
    ("embed_nomodel", None),
    ("embed_fsdp", None),
    ("vocab", "model"),
    ("q_proj", "model"),
    ("kv_proj", "model"),
    ("ffn", "model"),
    ("experts", "model"),
    ("expert_ffn", None),
    ("experts_router", None),
    ("ssm_in", "model"),
    ("ssm_heads", "model"),
    ("ssm_state", None),
    ("rnn_width", "model"),
    ("rnn_width_in", None),
    ("conv_k", None),
    ("layers", None),
    ("batch", None),
)


def tthf_shardings(model: ModelApi, scale: TTHFScaleConfig, mesh: Mesh,
                   param_dtype=jnp.float32):
    """(abstract replicated params, NamedSharding tree, batch sharding).

    granularity == "pod": the replica axis maps to `pod` only and each
    replica FSDP-shards its weights over `data` — this is how the 400B
    MoE holds divergent TT-HF copies (a 16-chip replica cannot).
    """
    from repro.dist.sharding import ShardingRules
    table = dict(TTHF_PARAM_RULES)
    if scale.granularity == "pod":
        table.update(replica=("pod",), embed=("data",),
                     embed_fsdp=("data",), rnn_width_in=("data",),
                     batch="data")
    rules = ShardingRules(tuple(table.items()))
    p_abs, axes = model.abstract_params(dtype=param_dtype)
    R = scale.replicas
    p_abs_R = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((R,) + s.shape, s.dtype), p_abs)
    axes_R = replica_axes_tree(axes)
    sh = jax.tree.map(
        lambda a: NamedSharding(mesh, rules.spec(tuple(a), mesh)),
        axes_R, is_leaf=lambda x: isinstance(x, tuple))
    # batch (tau, R, b, T): replica dim on the replica axes; per-replica
    # batch on `data` at pod granularity (the table already encodes
    # both — and rules.spec drops axes the mesh lacks, so the same
    # table serves the single-pod (data, model) mesh)
    batch_spec = rules.spec((None, "replica", "batch", None), mesh)
    return p_abs_R, sh, NamedSharding(mesh, batch_spec)


def stack_replicas(params, replicas: int):
    """w_i^(0) = w_hat^(0): identical initial copies (server broadcast)."""
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (replicas,) + l.shape), params)
