"""Plain reference of the TT-HF aggregation interval (the paper's
Algorithm 1 in scale mode), for the training cells' output check.

R replicas in clusters of s: each of tau local steps is plain SGD on
every replica's own rows; after every ``consensus_every`` steps each
cluster mixes its members with ``W = V^Gamma``, where V is the
Metropolis-Hastings matrix of the cluster's D2D graph; the interval
ends with the sampled global aggregation, sum_c varrho_c w_{n_c},
broadcast to every replica. Replicas are processed one at a time in
float32 at ``HIGHEST`` (or in the control's lower precision: with
``dtype=bfloat16`` the replicas are stored, updated and mixed in
bfloat16 as well as computed in it).

The sampled member n_c is taken as each cluster's first member: the
graphs this reference accepts mix a cluster to one shared model at the
interval's last consensus event (``W`` has equal rows), so every pick
gives the same aggregate and the program's random pick needs no copy.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def ring_adjacency(s: int) -> np.ndarray:
    a = np.zeros((s, s), bool)
    for i in range(s):
        a[i, (i + 1) % s] = a[(i + 1) % s, i] = True
    np.fill_diagonal(a, False)
    return a


def metropolis(adj: np.ndarray) -> np.ndarray:
    """v_ij = 1 / (1 + max(d_i, d_j)) on edges, v_ii = 1 - sum_j v_ij."""
    deg = adj.sum(1)
    v = np.where(adj, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])),
                 0.0)
    np.fill_diagonal(v, 1.0 - v.sum(1))
    return v


def mixing_matrix(job: dict) -> np.ndarray:
    if job["graph"] != "ring" or job["weights"] != "metropolis":
        raise ValueError("the reference knows ring graphs with "
                         "Metropolis-Hastings weights only")
    W = np.linalg.matrix_power(metropolis(ring_adjacency(
        job["cluster_size"])), job["gamma_d2d"])
    if not np.allclose(W, W[:1]):
        raise ValueError("W = V^Gamma does not mix a cluster to one "
                         "model; the first-member pick would not stand "
                         "for the program's random one")
    return W


def rows(seed: int, replica: int, draw: int, batch: int, seq_len: int,
         vocab: int) -> dict:
    """The training feed: one microbatch of replica ``replica``, draw
    ``draw``; every row differs (uniform tokens over the vocabulary)."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, replica,
                                 draw])
    t = rng.integers(0, vocab, size=(batch, seq_len + 1), dtype=np.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


@jax.jit
def _leaf_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def leaf_norms(a, b) -> np.ndarray:
    """Per-leaf ||a - b||, leaves in tree order."""
    return np.asarray(_leaf_norms(a, b))


def run(ref, cfg: dict, job: dict, seed: int, params0, steps: int, *,
        dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
        fault: Optional[str] = None) -> dict:
    """``steps`` intervals from ``params0`` on the feed of ``seed``.

    Returns the interval losses and the per-leaf norms of the change of
    the global model after the first and after the last interval.
    ``fault`` plants one of the faults the check must catch:
    ``frozen`` (the state never changes), ``half_batch`` (the loss is
    the mean over the first half of each row only) or ``no_mix`` (the
    D2D exchange is left out).
    """
    R, s, tau = job["replicas"], job["cluster_size"], job["tau"]
    every, lr = job["consensus_every"], job["lr"]
    b, T, V = job["batch_per_replica"], job["seq_len"], cfg["vocab_size"]
    W = mixing_matrix(job)
    varrho = np.full((R // s,), s / R)
    keep = T // 2 if fault == "half_batch" else T

    def loss(p, tokens, labels):
        return ref.loss(p, cfg, tokens[:, :keep], labels[:, :keep],
                        dtype=dtype, precision=precision)

    grad = jax.jit(jax.value_and_grad(loss))
    sgd = jax.jit(lambda p, g: jax.tree.map(
        lambda w, gg: w - jnp.asarray(lr, w.dtype) * gg.astype(w.dtype),
        p, g))
    combine = jax.jit(lambda ws, ps: jax.tree.map(
        lambda *xs: sum(w * x for w, x in zip(ws, xs)), *ps))

    # the replicas are held, updated and mixed in ``dtype`` (the
    # bfloat16 control stores them in bfloat16 too)
    reps = [jax.tree.map(lambda x: x.astype(dtype), params0)
            for _ in range(R)]
    losses, d1 = [], None
    for k in range(steps):
        step_losses = []
        for t in range(tau):
            for r in range(R):
                mb = rows(seed, r, k * tau + t, b, T, V)
                l, g = grad(reps[r], jnp.asarray(mb["tokens"]),
                            jnp.asarray(mb["labels"]))
                step_losses.append(float(l))
                if fault != "frozen":
                    reps[r] = sgd(reps[r], g)
            if (t + 1) % every == 0 and fault not in ("no_mix", "frozen"):
                mixed = []
                for c in range(R // s):
                    members = reps[c * s:(c + 1) * s]
                    mixed += [combine([float(w) for w in W[i]], members)
                              for i in range(s)]
                reps = mixed
        glob = combine([float(v) for v in varrho],
                       [reps[c * s] for c in range(R // s)])
        reps = [glob] * R
        losses.append(float(np.mean(step_losses)))
        if k == 0:
            d1 = leaf_norms(glob, params0)
    return {"losses": losses, "d1": d1, "dlast": leaf_norms(reps[0],
                                                             params0)}
