"""The one traffic generator. Every serving mix is a data file,
``bench/traffic/<mix>.json``, of parameters this module reads; nothing
here knows a mix by name.

Both loops give every seed the same work in another order, so that the
seed moves the arrivals and the order of lengths but not the load:

* open-loop arrivals follow a square wave of rate — ``calm_s`` seconds
  at the calm rate, then ``burst_s`` seconds at ``burst_mult`` times it,
  repeating, at a phase drawn from the seed — and each stretch of the
  wave gets the number of arrivals its rate and length call for
  (fractions carried to the next stretch), placed uniformly at random
  inside it: a Poisson process given its counts;
* the lengths of a stretch's n arrivals are the quantiles of a clipped
  lognormal (median, sigma, lo, hi) at the levels (i + u) / n, with u
  drawn from the seed, in an order drawn from the seed: every burst
  carries the whole spread of lengths, so the seed cannot load one
  burst with the longest prompts.

A window that is a whole number of periods long then offers nearly the
same requests in every run.

Token ids are uniform over [1, vocab) and drawn from the seed.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Req:
    rid: int
    due: float                  # seconds after the window opens
    prompt: np.ndarray          # int32 token ids
    max_new: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


def lognormal_set(n: int, spec: dict, rng: np.random.Generator) -> np.ndarray:
    """n lengths: lognormal quantiles at levels (i + u) / n, u drawn from
    ``rng``, clipped to [lo, hi], in an order drawn from ``rng``."""
    from statistics import NormalDist
    u = rng.uniform(0.01, 0.99)
    z = np.array([NormalDist().inv_cdf((i + u) / n) for i in range(n)])
    v = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    v = np.clip(np.rint(v), spec["lo"], spec["hi"]).astype(int)
    return rng.permutation(v)


def open_loop(p: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one open-loop window, in order of due time."""
    rng, lengths = rng_for(seed, 1), rng_for(seed, 2)
    calm_s, burst_s, mult = p["calm_s"], p["burst_s"], p["burst_mult"]
    period = calm_s + burst_s
    calm_rate = p["mean_rps"] * period / (calm_s + mult * burst_s)
    # walk the wave from t = -phase, stretch by stretch
    t, carry, out = -rng.uniform(0.0, period), 0.0, []
    while t < seconds:
        for length, rate in ((calm_s, calm_rate), (burst_s, calm_rate * mult)):
            lo, hi = max(t, 0.0), min(t + length, seconds)
            t += length
            if hi <= lo:
                continue
            want = rate * (hi - lo) + carry
            n = int(want)
            carry = want - n
            if n:
                out += zip(rng.uniform(lo, hi, size=n),
                           lognormal_set(n, p["prompt"], lengths),
                           lognormal_set(n, p["output"], lengths))
    out.sort(key=lambda a: a[0])
    return [Req(rid=i, due=float(due),
                prompt=lengths.integers(1, vocab, size=int(plen),
                                        dtype=np.int32),
                max_new=int(olen)) for i, (due, plen, olen) in enumerate(out)]


def uniform_int(rng, spec) -> int:
    return int(rng.integers(spec[0], spec[1] + 1))


class ClosedLoop:
    """``clients`` sessions. A client's first prompt has ``first_prompt``
    tokens; each later prompt repeats the first ``reuse`` tokens of that
    first prompt and adds a fresh tail of ``tail`` tokens. Outputs are
    ``output`` tokens. (Ranges are inclusive [lo, hi], drawn from the
    seed per client and request.) A client's first request stands for
    the rest of an answer already under way when the window opens: its
    output is drawn uniformly up to the length drawn for it, so that the
    sessions' phases are spread from the start."""

    def __init__(self, p: dict, seed: int, vocab: int):
        self.p, self.seed, self.vocab = p, seed, vocab
        self.count = [0] * p["clients"]
        self.base = []
        for c in range(p["clients"]):
            rng = rng_for(seed, 3, c)
            self.base.append(rng.integers(
                1, vocab, size=uniform_int(rng, p["first_prompt"]),
                dtype=np.int32))
        self.next_rid = 0

    def next(self, client: int, now: float) -> Req:
        p, k = self.p, self.count[client]
        rng = rng_for(self.seed, 4, client, k)
        if k == 0:
            prompt = self.base[client]
        else:
            keep = min(uniform_int(rng, p["reuse"]), len(self.base[client]))
            tail = rng.integers(1, self.vocab, dtype=np.int32,
                                size=uniform_int(rng, p["tail"]))
            prompt = np.concatenate([self.base[client][:keep], tail])
        self.count[client] += 1
        rid, self.next_rid = self.next_rid, self.next_rid + 1
        max_new = uniform_int(rng, p["output"])
        if k == 0:
            max_new = uniform_int(rng, (1, max_new))
        return Req(rid=rid, due=now, prompt=prompt, max_new=max_new)
