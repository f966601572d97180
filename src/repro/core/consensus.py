"""D2D consensus operators (eq. 10) — simulation mode.

State layout: device parameters stacked on a leading axis, reshaped per
cluster to ``(N, s, M)``. One consensus *round* is the block-diagonal
product ``z <- V_c z`` applied independently per cluster; an *event*
applies ``Gamma_c`` rounds (possibly different per cluster — devices in
cluster c stop mixing after Gamma_c rounds).

Execution is delegated to the unified engine in
:mod:`repro.core.mixing` (DESIGN.md §5): the default backend is the
jittable ``masked_loop``; ``use_kernel=True`` (or ``backend="pallas"``)
routes through the fused Pallas kernel, and ``backend`` exposes the
full dispatch table (``reference``/``masked_loop``/``pallas``/
``fused_power``).  This module keeps the simulation-facing API and the
consensus *metrics* (Definitions 2-3).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import mixing


def mix_once(z: jax.Array, V: jax.Array) -> jax.Array:
    """One consensus round. z: (N, s, M); V: (N, s, s)."""
    return jnp.einsum("nij,njm->nim", V, z,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=z.dtype)


def _resolve_backend(use_kernel: bool, backend: str | None) -> str:
    if backend is not None:
        return mixing.canonical_backend(backend)
    return "pallas" if use_kernel else "masked_loop"


@partial(jax.jit, static_argnames=("backend",))
def _mix_jit(z, V, gamma, backend):
    return mixing.mix(z, V, gamma, backend=backend)


def mix(z: jax.Array, V: jax.Array, gamma: jax.Array,
        use_kernel: bool = False, backend: str | None = None) -> jax.Array:
    """Apply per-cluster consensus: z_c <- V_c^{gamma_c} z_c.

    z: (N, s, M); V: (N, s, s); gamma: scalar or (N,) int32.
    The ``reference`` backend unrolls gamma in Python, so it runs
    outside this function's jit (gamma must stay concrete).
    """
    backend = _resolve_backend(use_kernel, backend)
    if backend == "reference":
        return mixing.mix(z, V, gamma, backend=backend)
    return _mix_jit(z, V, gamma, backend)


def mix_pytree(params, V: jax.Array, gamma: jax.Array, num_clusters: int,
               use_kernel: bool = False, backend: str | None = None):
    """Consensus over a pytree whose leaves have leading axis I = N*s.

    Mixing is linear and elementwise across parameters, so each leaf is
    reshaped (I, ...) -> (N, s, M) and mixed independently.
    """
    return mixing.mix_pytree(params, V, gamma, num_clusters,
                             backend=_resolve_backend(use_kernel, backend))


def cluster_means(z: jax.Array) -> jax.Array:
    """(N, s, M) -> (N, M): the targets of perfect consensus."""
    return z.mean(axis=1)


def consensus_error(z: jax.Array) -> jax.Array:
    """Per-cluster mean squared consensus error (Definition 3):
    (1/s) sum_i ||e_i||^2 with e_i = z_i - zbar_c. Returns (N,)."""
    e = z - cluster_means(z)[:, None, :]
    return jnp.mean(jnp.sum(e * e, axis=-1), axis=1)


def divergence_upsilon(z: jax.Array) -> jax.Array:
    """Definition 2: per-cluster max elementwise spread Upsilon_c.
    z: (N, s, M) -> (N,)."""
    return jnp.max(z.max(axis=1) - z.min(axis=1), axis=-1)


def masked_divergence_upsilon(z: jax.Array, device_mask: jax.Array
                              ) -> jax.Array:
    """Definition-2 spread over the ACTIVE devices only (netsim churn).

    Dropped devices hold stale parameters that cannot take part in the
    coming consensus event, so they must not inflate the Remark-1
    round count. Clusters with < 2 active devices have zero spread.
    z: (N, s, M), device_mask: (N, s) -> (N,).
    """
    m = device_mask[..., None]
    big = jnp.finfo(z.dtype).max
    hi = jnp.max(jnp.where(m, z, -big), axis=1)
    lo = jnp.min(jnp.where(m, z, big), axis=1)
    spread = jnp.max(hi - lo, axis=-1)
    enough = jnp.sum(device_mask, axis=1) >= 2
    return jnp.where(enough, spread, 0.0)
