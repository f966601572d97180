"""Theory-bound telemetry: jitted aux probes + bound gauges.

Two halves (DESIGN.md §13):

* **Probes** — jitted, read-only functions over the (stacked) device
  parameters that compute the measured quantities the paper's analysis
  talks about: per-cluster consensus divergence Υ_c (Definition 2),
  per-cluster mean-squared consensus error (Definition 3), the
  post-mixing residual max_i‖w_i − w̄_c‖ that Lemma 1 bounds, the
  cluster dispersion A^(t), and parameter/gradient norms. Probes never
  feed back into training — an instrumented run is bitwise-identical
  to an uninstrumented one (asserted in ``tests/test_obs.py``).

* **Gauges** — host-side evaluations of ``core/theory.py`` (``sigma_t``,
  Proposition-1 ``dispersion_bound``, Lemma 1) for the same round, so
  bound-vs-actual lands in ONE JSONL record per round.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from repro.core.theory import (
    ProblemConstants, dispersion_bound, lemma1_bound, sigma_t)


# ---------------------------------------------------------------------------
# jitted probes
# ---------------------------------------------------------------------------

def make_divergence_probe(num_clusters: int, cluster_size: int,
                          varrho) -> Callable:
    """Jitted probe over a params pytree whose leaves carry a leading
    device axis I = N*s (simulation fleet, scale-mode replica stack, or
    the §12 flat (R, rows, 128) carrier — an array is a one-leaf pytree).

    Returns ``{upsilon (N,), consensus_err (N,), mix_residual (N,),
    dispersion (), param_norm ()}``; everything is computed on device
    and drained once per round by the caller.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import consensus as cns

    N, s = num_clusters, cluster_size
    v = jnp.asarray(np.asarray(varrho), jnp.float32)

    @jax.jit
    def probe(params):
        ups, errs = [], []
        sq = jnp.zeros((N, s), jnp.float32)
        disp = jnp.float32(0.0)
        pn = jnp.float32(0.0)
        for leaf in jax.tree.leaves(params):
            z = leaf.reshape(N, s, -1).astype(jnp.float32)
            ups.append(cns.divergence_upsilon(z))
            errs.append(cns.consensus_error(z))
            e = z - z.mean(axis=1, keepdims=True)
            sq = sq + jnp.sum(e * e, axis=-1)
            means = z.mean(axis=1)
            gmean = jnp.einsum("c,cm->m", v, means)
            disp = disp + jnp.sum(v * jnp.sum((means - gmean) ** 2,
                                              axis=-1))
            pn = pn + jnp.sum(z * z)
        return {
            "upsilon": jnp.max(jnp.stack(ups), axis=0),
            "consensus_err": jnp.sum(jnp.stack(errs), axis=0),
            "mix_residual": jnp.sqrt(jnp.max(sq, axis=1)),
            "dispersion": disp,
            "param_norm": jnp.sqrt(pn),
        }

    return probe


def make_sim_grad_probe(model, x, y) -> Callable:
    """Jitted ‖∇F(ŵ)‖ over the full federated dataset (sim mode)."""
    import jax
    import jax.numpy as jnp

    fx = jnp.asarray(x).reshape(-1, np.asarray(x).shape[-1])
    fy = jnp.asarray(y).reshape(-1)

    @jax.jit
    def probe(global_params):
        g = jax.grad(model.loss)(global_params, fx, fy)
        return jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2)
                            for l in jax.tree.leaves(g)))

    return probe


def make_scale_grad_probe(model, dtype) -> Callable:
    """Jitted ‖∇loss(ŵ; batch)‖ for scale mode — fed a dedicated probe
    batch stream so train/eval data draws are untouched."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(global_params, batch):
        g = jax.grad(lambda p: model.loss(p, batch, dtype=dtype,
                                          remat=False))(global_params)
        return jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2)
                            for l in jax.tree.leaves(g)))

    return probe


# ---------------------------------------------------------------------------
# theory gauges
# ---------------------------------------------------------------------------

def default_constants(varrho_min: float) -> ProblemConstants:
    """Unit-scale placeholder constants — the gauges are *relative*
    instruments unless the caller estimates (μ, β, σ, δ) for the task
    (``core/theory.py`` has the estimators)."""
    return ProblemConstants(mu=1.0, beta=1.0, sigma=1.0, delta=1.0,
                            varrho_min=float(varrho_min))


def sigma_t_general(beta: float, eta_fn: Callable[[int], float],
                    t: int, t_prev_agg: int) -> float:
    """Proposition-1 Σ_t for an arbitrary step-size sequence —
    identical recurrence to :func:`repro.core.theory.sigma_t`, which
    covers only η_j = γ/(j+α) (parity asserted in tests)."""
    total = 0.0
    for ell in range(t_prev_agg, t):
        prod = 1.0
        for j in range(ell + 1, t):
            prod *= 1.0 + 2.0 * eta_fn(j) * beta
        total += beta * eta_fn(ell) * prod
    return total


@dataclass
class TheoryGauges:
    """Per-round bound evaluations for the telemetry stream.

    Exactly one of (``gamma``, ``alpha``) — the paper's decaying
    schedule η_t = γ/(t+α) — or ``lr`` (constant step size, scale mode)
    drives the η sequence. ``phi`` sets the Remark-1 consensus target
    ε^(t) = η_t·φ used as Proposition 1's ε₀.
    """
    constants: ProblemConstants
    tau: int
    model_dim: int
    phi: float = 1.0
    gamma: Optional[float] = None
    alpha: Optional[float] = None
    lr: Optional[float] = None

    def __post_init__(self):
        decaying = self.gamma is not None and self.alpha is not None
        assert decaying != (self.lr is not None), \
            "pass gamma+alpha (decaying schedule) XOR lr (constant)"

    def eta(self, t: int) -> float:
        if self.lr is not None:
            return float(self.lr)
        return self.gamma / (t + self.alpha)

    def sigma(self, t: int, t_prev_agg: int) -> float:
        if self.lr is not None:
            return sigma_t_general(self.constants.beta,
                                   lambda j: self.lr, t, t_prev_agg)
        return sigma_t(self.constants, self.gamma, self.alpha, self.tau,
                       t, t_prev_agg)

    def round_gauges(self, t: int, t_prev_agg: int) -> dict:
        """``{sigma_t, dispersion_bound, eps0}`` for round ``t`` whose
        last aggregation was at ``t_prev_agg``."""
        k = self.constants
        eps0 = self.eta(t) * self.phi
        if self.lr is not None:
            s = self.sigma(t, t_prev_agg)
            disp = (12.0 / k.varrho_min) * s ** 2 * (
                k.sigma ** 2 / k.beta ** 2 + k.delta ** 2 / k.beta ** 2
                + eps0 ** 2)
        else:
            s = self.sigma(t, t_prev_agg)
            disp = dispersion_bound(k, self.gamma, self.alpha, self.tau,
                                    t, t_prev_agg, eps0)
        return {"sigma_t": float(s), "dispersion_bound": float(disp),
                "eps0": float(eps0)}

    def lemma1(self, lambdas, gammas, cluster_size,
               upsilons) -> np.ndarray:
        """Per-cluster Lemma-1 bounds λ_c^Γ_c · s_c · Υ_c · M on the
        post-mixing residual, from the *measured* pre-mixing Υ_c."""
        lam = np.asarray(lambdas, float)
        gam = np.asarray(gammas, int)
        ups = np.asarray(upsilons, float)
        sizes = np.broadcast_to(np.asarray(cluster_size), lam.shape)
        return np.array([
            lemma1_bound(lam[c], int(gam[c]), int(sizes[c]), ups[c],
                         self.model_dim)
            for c in range(lam.shape[0])])


__all__ = [
    "TheoryGauges", "default_constants", "make_divergence_probe",
    "make_scale_grad_probe", "make_sim_grad_probe", "sigma_t_general",
]
