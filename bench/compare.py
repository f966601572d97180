"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference, reduced to the numbers a cell's limits
file bounds (``bench/limits/<cell>.json``).

Training. For each checked interval, the relative gap of the loss; for
the change of the global model after the first and after the last
checked interval, the worst leaf's gap of norms,
``| ||dw_prog|| - ||dw_ref|| | / max(||dw_ref||, median leaf's)``.
A leaf whose reference change after the first interval is under a
thousandth of the median leaf's moves by round-off alone and is left
out (by that rule, never by name).

Serving. For every served token of the sampled requests, how far the
reference's logit of that token lies below the reference's best logit
at that position; the widest such gap.
"""
from __future__ import annotations

import numpy as np

NEGLIGIBLE = 1e-3       # of the median leaf's reference change


def change_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray):
    """(worst gap, index of the worst leaf)."""
    scale = np.maximum(ref, np.median(ref))
    gap = np.where(keep, np.abs(prog - ref) / scale, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def training(got: dict, want: dict, names: list) -> dict:
    lp = np.asarray(got["losses"], np.float64)
    lr = np.asarray(want["losses"], np.float64)
    loss_gaps = np.abs(lp - lr) / np.abs(lr)
    keep = want["d1"] >= NEGLIGIBLE * np.median(want["d1"])
    g1, i1 = change_gap(got["d1"], want["d1"], keep)
    gl, il = change_gap(got["dlast"], want["dlast"], keep)
    values = {"loss_gap": float(np.max(loss_gaps)),
              "change_gap.first": g1, "change_gap.last": gl}
    info = {"loss_gaps": loss_gaps.tolist(),
            "change_gap.first_leaf": names[i1],
            "change_gap.last_leaf": names[il],
            "leaves_left_out": [n for n, k in zip(names, keep) if not k]}
    return {"values": values, "info": info}


def served_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap, over positions, between the best reference logit and
    the reference logit of the token served there. ref_logits: (n, V)
    at the positions that predicted ``tokens`` (n,)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return float(np.max(best - got)) if len(tokens) else 0.0
