"""Unified D2D consensus-mixing engine (DESIGN.md §5).

One operator, four interchangeable backends for the paper's eq. (10)
``z_c <- V_c^{Gamma_c} z_c`` applied to N stacked clusters:

=============  ============================================================
backend        execution strategy
=============  ============================================================
reference      per-round masked einsum, Python-unrolled (the oracle;
               needs concrete gamma)
masked_loop    jittable bounded ``fori_loop`` with per-cluster masking —
               works with *traced* gamma (Remark-1 adaptive rounds)
pallas         fused Gamma-round Pallas TPU kernel
               (``repro.kernels.consensus_mix``; interpret mode on CPU)
fused_power    ONE mixing pass against the stacked matrix powers
               ``W_c = V_c^{Gamma_c}`` — the scale-mode collective
               collapse; W is precomputed at plan-build time
=============  ============================================================

Every backend accepts a *vector* per-cluster ``gamma: (N,)`` (Remark 1:
aperiodic, heterogeneous round counts), including ``fused_power`` —
each cluster's block of W is raised to its own power.

Call sites (the four previously-divergent paths, now routed here):
``core/consensus.py::mix/mix_pytree`` (simulation public API),
``core/tthf.py`` (simulation trainer), ``core/distributed.py``
(TT-HF scale mode) and ``kernels/ops.py`` (kernel wrapper).

Prefer :func:`build_mixing_plan` + :meth:`MixingPlan.apply` when gamma
and the topology are known at step-build time — the plan precomputes
``W`` exactly once (numpy, exact integer powers) instead of re-deriving
it per call, and pins the dispatch statically so the jitted step closes
over constants only.

Parameters are mixed by :func:`mix_blocks`, an explicit sum over the
cluster members that keeps each leaf's trailing dims, never by an
einsum over an ``(N, s, M)`` view. Two TPU facts force this. A
DEFAULT-precision f32 matmul is one bf16 MXU pass there, which would
round every replica's parameters to bf16 at each consensus event and
erase SGD updates smaller than a bf16 ulp. And an ``(N, s, M)`` view
of a parameter with M in the millions is a relayout whose compile time
grows with M: minutes per leaf at mamba2-370m widths. The remaining
einsums over parameters (small matrix products, the ``pallas`` backend
aside) run at ``Precision.HIGHEST``; off-TPU that flag changes nothing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

BACKENDS = ("reference", "masked_loop", "pallas", "fused_power")

# scale-mode consensus_mode names kept for backward compatibility
_BACKEND_ALIASES = {
    "fused": "fused_power",     # one collective of the same payload
    "rounds": "reference",      # paper-faithful sequential exchanges
    "kernel": "pallas",
}


def canonical_backend(name: str) -> str:
    """Resolve aliases ("fused", "rounds", "kernel") to backend names."""
    backend = _BACKEND_ALIASES.get(name, name)
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown mixing backend {name!r}; expected one of "
            f"{BACKENDS} or aliases {tuple(_BACKEND_ALIASES)}")
    return backend


def _normalize_gamma(gamma: Any, num_clusters: int) -> jax.Array:
    gamma = jnp.asarray(gamma, jnp.int32)
    if gamma.ndim == 0:
        gamma = jnp.full((num_clusters,), gamma)
    if gamma.shape != (num_clusters,):
        raise ValueError(
            f"gamma must be scalar or ({num_clusters},), got {gamma.shape}")
    return gamma


def masked_consensus_matrix(V: jax.Array, device_mask: jax.Array) -> jax.Array:
    """Drop devices from a consensus-matrix stack (netsim contract).

    Zeroes the dropped devices' rows and columns and returns the
    removed mass to each row's self-loop, so the result is still
    symmetric and row-stochastic:

      * dropped device i: row becomes e_i — a consensus step leaves
        its parameters untouched;
      * active device i: v'_ii = v_ii + sum_{j dropped} v_ij — it
        mixes only among the remaining active devices.

    V: (N, s, s); device_mask: (N, s) bool/0-1. Works under jit (the
    mask may be traced) and commutes with powers: masking then raising
    to Gamma keeps dropped rows identity.
    """
    m = device_mask.astype(V.dtype)
    s = V.shape[-1]
    eye = jnp.eye(s, dtype=V.dtype)
    offdiag = V * (1.0 - eye) * m[:, :, None] * m[:, None, :]
    return offdiag + (1.0 - offdiag.sum(-1))[..., None] * eye


def matrix_powers(V: jax.Array, gamma: jax.Array) -> jax.Array:
    """In-graph stacked powers ``W_c = V_c^{gamma_c}``; (N, s, s).

    Masked bounded loop over max(gamma) — O(max_gamma * N * s^3), which
    is tiny next to the (N, s, M) mixing it replaces.  Jittable with
    traced gamma (the adaptive Remark-1 path).
    """
    N, s, _ = V.shape
    Vf = V.astype(jnp.float32)
    eye = jnp.broadcast_to(jnp.eye(s, dtype=jnp.float32), (N, s, s))

    def body(r, W):
        nxt = jnp.einsum("nij,njk->nik", Vf, W,
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        return jnp.where((r < gamma)[:, None, None], nxt, W)

    return jax.lax.fori_loop(0, jnp.max(gamma), body, eye)


def mix_blocks(W: jax.Array, z: jax.Array) -> jax.Array:
    """``z_c <- W_c z_c`` for every cluster: W (N, s, s), z (N, s, ...).

    A sum over the s members written out term by term (see the module
    docstring): exact f32 on every backend, and z keeps its trailing
    dims, so no parameter is ever viewed as one long row."""
    Wb = W.astype(z.dtype).reshape(W.shape + (1,) * (z.ndim - 2))
    out = Wb[:, :, 0] * z[:, :1]
    for j in range(1, z.shape[1]):
        out = out + Wb[:, :, j] * z[:, j:j + 1]
    return out


# ---------------------------------------------------------------------------
# backend implementations — all (N, s, ...) x (N, s, s) x (N,) -> (N, s, ...)
# ---------------------------------------------------------------------------

def _mix_reference(z, V, gamma):
    from repro.kernels import ref
    try:
        return ref.consensus_mix_ref(z, V, gamma)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError) as e:
        raise ValueError(
            "backend='reference' unrolls gamma rounds in Python and needs "
            "a concrete gamma; use 'masked_loop' (or 'pallas'/"
            "'fused_power') under jit with traced gamma") from e


def _mix_masked_loop(z, V, gamma):
    keep = (slice(None),) + (None,) * (z.ndim - 1)

    def body(r, zz):
        return jnp.where((r < gamma)[keep], mix_blocks(V, zz), zz)

    return jax.lax.fori_loop(0, jnp.max(gamma), body, z)


def _mix_pallas(z, V, gamma, blk_m=512):
    from repro.kernels import consensus_mix as _cm
    from repro.kernels import ops as kops
    N, s = z.shape[:2]
    return _cm.consensus_mix(z.reshape(N, s, -1), V, gamma, blk_m=blk_m,
                             interpret=kops.INTERPRET).reshape(z.shape)


def _mix_fused_power(z, V, gamma, W=None):
    if W is None:
        W = matrix_powers(V, gamma)
    return mix_blocks(W, z)


def mix(z: jax.Array, V: jax.Array, gamma: Any, *,
        backend: str = "masked_loop", W: Optional[jax.Array] = None,
        device_mask: Optional[jax.Array] = None,
        blk_m: int = 512) -> jax.Array:
    """Apply per-cluster consensus ``z_c <- V_c^{gamma_c} z_c``.

    z: (N, s, ...); V: (N, s, s); gamma: scalar or (N,) int32.
    ``W`` (fused_power only): precomputed stacked powers; derived
    in-graph when omitted.
    ``device_mask`` (N, s): drop devices via
    :func:`masked_consensus_matrix` before dispatch — dropped rows hold
    their values through every backend. Incompatible with a
    precomputed ``W`` (powers must be taken AFTER masking).
    """
    backend = canonical_backend(backend)
    gamma = _normalize_gamma(gamma, z.shape[0])
    if device_mask is not None:
        if W is not None:
            raise ValueError(
                "device_mask with precomputed W is ambiguous: powers "
                "must be taken after masking — pass V and let the "
                "backend derive W, or precompute W from the masked V")
        V = masked_consensus_matrix(V, device_mask)
    if backend == "reference":
        return _mix_reference(z, V, gamma)
    if backend == "masked_loop":
        return _mix_masked_loop(z, V, gamma)
    if backend == "pallas":
        return _mix_pallas(z, V, gamma, blk_m=blk_m)
    return _mix_fused_power(z, V, gamma, W=W)


def mix_pytree(params, V: jax.Array, gamma: Any, num_clusters: int, *,
               backend: str = "masked_loop",
               W: Optional[jax.Array] = None,
               device_mask: Optional[jax.Array] = None):
    """Consensus over a pytree whose leaves have leading axis I = N*s.

    Mixing is linear and elementwise across parameters, so each leaf is
    viewed (I, ...) -> (N, s, ...) and mixed independently.
    ``device_mask``: see :func:`mix` — applied once, outside the
    per-leaf loop.
    """
    if device_mask is not None:
        if W is not None:
            raise ValueError(
                "device_mask with precomputed W is ambiguous (see mix)")
        V = masked_consensus_matrix(V, device_mask)

    def one(leaf):
        I = leaf.shape[0]
        s = I // num_clusters
        z = leaf.reshape((num_clusters, s) + (leaf.shape[1:] or (1,)))
        mixed = mix(z, V.astype(z.dtype), gamma, backend=backend, W=W)
        return mixed.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree.map(one, params)


# ---------------------------------------------------------------------------
# step-build-time plans
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MixingPlan:
    """A consensus event bound to (topology, gamma, backend) at build
    time.  ``W`` is the exact stacked power for ``fused_power`` —
    computed ONCE here (numpy integer matrix powers), never re-derived
    inside the step."""
    backend: str
    num_clusters: int
    cluster_size: int
    V: jax.Array                    # (N, s, s) float32
    gamma: jax.Array                # (N,) int32
    W: Optional[jax.Array] = None   # (N, s, s) float32, fused_power only

    @property
    def is_noop(self) -> bool:
        return bool(np.all(np.asarray(self.gamma) == 0))

    def _matrices(self, refresh: Optional[jax.Array]):
        """Resolve (V, W) given an optional per-call refresh matrix.

        A refresh (from :func:`refresh_matrices`) is whatever the
        backend consumes: the stacked powers W for ``fused_power``, the
        (masked) consensus matrices V otherwise. It may be traced — the
        netsim W-refresh path jits the step once and feeds new
        matrices each aggregation round.
        """
        if refresh is None:
            return self.V, self.W
        if self.backend == "fused_power":
            return self.V, refresh
        return refresh, None

    def apply(self, z: jax.Array,
              refresh: Optional[jax.Array] = None) -> jax.Array:
        """z: (N, s, M) -> mixed (N, s, M)."""
        V, W = self._matrices(refresh)
        return mix(z, V, self.gamma, backend=self.backend, W=W)

    def fused_w(self, refresh: Optional[jax.Array] = None
                ) -> Optional[jax.Array]:
        """The stacked (N, s, s) powers if this plan applies as ONE
        matrix product (``fused_power`` backend), else None.

        The fused-interval step (``core/distributed.py``) uses this to
        route block-ends through the fused SGD+mix kernel; other
        backends fall back to :meth:`apply`.
        """
        if self.backend != "fused_power":
            return None
        return self._matrices(refresh)[1]

    def apply_pytree(self, params, refresh: Optional[jax.Array] = None):
        """params: pytree with leading replica/device axis I = N*s."""
        if self.is_noop and refresh is None:
            return params
        V, W = self._matrices(refresh)
        return mix_pytree(params, V, self.gamma, self.num_clusters,
                          backend=self.backend, W=W)


def build_mixing_plan(net, gamma: Any,
                      backend: str = "fused_power") -> MixingPlan:
    """Build a :class:`MixingPlan` from a ``Network`` (or a raw (N, s, s)
    consensus-matrix stack), concrete per-cluster gamma, and a backend.

    gamma may be a scalar or an (N,) vector (heterogeneous Remark-1
    round counts) but must be concrete — plans exist so the expensive
    derivations happen at step-build time.
    """
    backend = canonical_backend(backend)
    V = np.asarray(getattr(net, "V", net), np.float32)
    N, s, _ = V.shape
    g = np.asarray(gamma, np.int32)
    if g.ndim == 0:
        g = np.full((N,), g, np.int32)
    if g.shape != (N,):
        raise ValueError(f"gamma must be scalar or ({N},), got {g.shape}")
    if (g < 0).any():
        raise ValueError(f"gamma must be >= 0 rounds, got {g.tolist()}")
    W = None
    if backend == "fused_power":
        W = jnp.asarray(
            np.stack([np.linalg.matrix_power(V[c], int(g[c]))
                      for c in range(N)]), jnp.float32)
    return MixingPlan(backend=backend, num_clusters=N, cluster_size=s,
                      V=jnp.asarray(V), gamma=jnp.asarray(g), W=W)


def refresh_matrices(plan: MixingPlan, V: Any,
                     device_mask: Any = None,
                     gamma: Any = None) -> jax.Array:
    """Host-side per-event matrices for ``MixingPlan.apply*(refresh=)``.

    Takes the event's consensus-matrix stack (e.g. a netsim
    ``NetworkSnapshot.V``), optionally drops devices, and returns what
    the plan's backend consumes: exact numpy integer powers
    ``W = V^Gamma`` for ``fused_power``, the (masked) ``V`` itself
    otherwise. This is the scale-mode refresh path — the jitted step
    stays compiled once while the matrices change per aggregation round.

    ``gamma``: optional per-event (N,) round counts overriding the
    plan's build-time Γ — the control plane's per-interval retuning
    (DESIGN.md §16). Only ``fused_power`` folds Γ into the refreshed
    matrices; other backends read Γ from the plan traced into the
    step, so an override there would be silently ignored — refuse it.
    """
    Vn = np.asarray(V, np.float32)
    if device_mask is not None:
        Vn = np.asarray(masked_consensus_matrix(
            jnp.asarray(Vn), jnp.asarray(device_mask)), np.float32)
    if plan.backend != "fused_power":
        if gamma is not None:
            raise ValueError(
                "per-event gamma refresh needs the fused_power backend "
                f"(plan is {plan.backend!r}: gamma is traced into the "
                "step and cannot change per interval)")
        return jnp.asarray(Vn)
    g = np.asarray(plan.gamma if gamma is None else gamma, np.int32)
    return jnp.asarray(
        np.stack([np.linalg.matrix_power(Vn[c], int(g[c]))
                  for c in range(Vn.shape[0])]), jnp.float32)


__all__ = ["BACKENDS", "MixingPlan", "build_mixing_plan",
           "canonical_backend", "masked_consensus_matrix",
           "matrix_powers", "mix", "mix_blocks", "mix_pytree",
           "refresh_matrices"]
