"""What the serving runners share: the model and its weights, one
``PagedContinuousScheduler`` built from the mix's settings, warm-up of
every shape the window drives, token time stamps, the per-layer
readings, and the output check against the plain reference.

A token's time is the host clock when the ``step`` that emitted it
returns (the scheduler reads the sampled ids back to the host there).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from bench import compare, generate, harness


@dataclass
class Tracked:
    req: object                 # the scheduler's Request
    gen: generate.Req
    stamps: list = field(default_factory=list)   # token times, s


class Server:
    def __init__(self, ctx: harness.Context):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        from repro.serving import PagedContinuousScheduler

        self.ctx = ctx
        cfg, mix = ctx.cell.config, ctx.cell.traffic
        self.cfg, self.mix = cfg, mix
        self.ref = harness.reference_module(cfg)
        self.model = build_model(ModelConfig(**cfg["model"]))
        self.init = jax.jit(lambda k: self.ref.init(cfg["model"], k))
        self.params = self.init(harness.seed_key(ctx.seed))
        want, _ = self.model.abstract_params()
        same = jax.tree.map(lambda a, b: (a.shape, a.dtype) == (b.shape,
                                                                b.dtype),
                            self.params, want)
        if not all(jax.tree.leaves(same)):
            raise ValueError("reference weights do not match the "
                             "program's parameter layout")
        s = mix["scheduler"]
        self.sched = PagedContinuousScheduler(
            self.model, slots=s["slots"], max_prompt=s["max_prompt"],
            max_total=s["max_total"], page_size=s["page_size"],
            prefill_chunk=s["prefill_chunk"],
            chunks_per_tick=s["chunks_per_tick"], temperature=0.0,
            seed=ctx.seed & 0x7FFFFFFF, cache_dtype=jnp.float32)
        self.vocab = cfg["model"]["vocab_size"]
        self.live: dict = {}        # rid -> Tracked, not yet finished
        self.done: dict = {}        # rid -> Tracked, finished
        self.ticks: list = []       # (start s, end s, did work)
        self.t0 = 0.0

    # -- driving the scheduler -------------------------------------------
    def submit(self, g: generate.Req) -> None:
        from repro.serving import Request
        req = Request(rid=g.rid, prompt=g.prompt, max_new=g.max_new)
        self.sched.submit(req)
        self.live[g.rid] = Tracked(req=req, gen=g)

    def tick(self) -> None:
        st = self.sched.stats
        before = (st.decode_steps,
                  sum(t.req.prefill_chunks for t in self.live.values()))
        t_a = time.perf_counter() - self.t0
        with harness.annotate("bench.tick"):
            self.sched.step(self.params)
        t = time.perf_counter() - self.t0
        after = (st.decode_steps,
                 sum(t_.req.prefill_chunks for t_ in self.live.values()))
        self.ticks.append((t_a, t, after != before))
        for rid in list(self.live):
            tr = self.live[rid]
            new = len(tr.req.out_tokens) - len(tr.stamps)
            tr.stamps.extend([t] * new)
            if tr.req.done:
                self.done[rid] = self.live.pop(rid)

    def warm_up(self) -> None:
        """Run requests that reach every shape the window uses (one
        decode step over all slots, one prefill-chunk shape, the
        sampler) until they finish; then forget them."""
        s = self.mix["scheduler"]
        rng = generate.rng_for(self.ctx.seed, 9)
        lengths = np.linspace(1, s["max_prompt"], s["slots"] + 2).astype(int)
        for i, n in enumerate(lengths):
            self.submit(generate.Req(
                rid=-1 - i, due=0.0, max_new=4,
                prompt=rng.integers(1, self.vocab, size=int(n),
                                    dtype=np.int32)))
        while self.sched.outstanding:
            self.tick()
        self.live.clear()
        self.done.clear()
        self.ticks.clear()

    # -- what the window produced -----------------------------------------
    def token_times(self, end: float) -> list:
        return [s for t in list(self.done.values()) + list(self.live.values())
                for s in t.stamps if 0.0 <= s < end]

    def itl_ms(self, end: float) -> list:
        """Gaps between consecutive tokens of a request whose later
        token falls in [0, end)."""
        out = []
        for t in list(self.done.values()) + list(self.live.values()):
            st = t.stamps
            out += [1e3 * (b - a) for a, b in zip(st, st[1:])
                    if 0.0 <= b < end]
        return out

    def restart_clock(self) -> None:
        """Open the window now: earlier stamps and ticks turn negative."""
        now = time.perf_counter()
        shift = now - self.t0
        for t in list(self.done.values()) + list(self.live.values()):
            t.stamps = [s - shift for s in t.stamps]
            t.gen.due -= shift
        self.ticks = [(a - shift, b - shift, w) for a, b, w in self.ticks]
        self.t0 = now

    def readings(self, end: float) -> dict:
        """Work and ticks in [0, end) for the per-layer metrics: the
        operations of each emitted token (a first token stands for its
        prompt's prefill) at its time, and the positions attended by
        each decode step."""
        fl = harness.flops_module(self.cfg)
        m = self.cfg["model"]
        work, decode_pos = [], []
        for t in list(self.done.values()) + list(self.live.values()):
            plen = len(t.gen.prompt)
            reused = t.req.prefix_pages_reused * self.mix["scheduler"][
                "page_size"]
            for i, s in enumerate(t.stamps):
                if not 0.0 <= s < end:
                    continue
                if i == 0:
                    work.append((s, fl.prefill_chunk_flops(
                        m, reused, plen - reused)))
                else:
                    # the decode that produced token i fed token i - 1
                    # at position plen + i - 1
                    pos = plen + i - 1
                    work.append((s, fl.decode_flops_per_token(m, pos + 1)))
                    decode_pos.append((s, pos))
        return {"work": work, "decode_pos": decode_pos,
                "ticks": [x for x in self.ticks if 0.0 <= x[0] < end]}

    # -- the output check ---------------------------------------------------
    def sample(self) -> list:
        """Finished requests to compare, drawn from the seed: the longest
        (prompt plus output), then others until the mix's token count or
        request cap is reached."""
        done = list(self.done.values())
        if not done:
            return []
        chk = self.mix["check"]
        rng = generate.rng_for(self.ctx.seed, 7)
        longest = max(done, key=lambda t: len(t.gen.prompt) + len(t.stamps))
        out, served = [longest], len(longest.req.out_tokens)
        for i in rng.permutation(len(done)):
            if served >= chk["tokens"] or len(out) >= chk["max_requests"]:
                break
            if done[i] is not longest:
                out.append(done[i])
                served += len(done[i].req.out_tokens)
        return out

    def free(self) -> None:
        """Drop the program's state before the reference runs."""
        del self.sched, self.params
        harness.free_device_memory()

    def reference_rows(self, params, t: Tracked, **kw) -> np.ndarray:
        """The reference's logits at the positions that predicted the
        served tokens of ``t``: (n_out, vocab). The sequence is padded
        to the mix's ``max_total``, so one compiled program serves all."""
        import jax
        if not hasattr(self, "_logits"):
            self._logits = {}
        key = tuple(sorted(kw.items()))
        if key not in self._logits:
            self._logits[key] = jax.jit(lambda p, x: self.ref.logits(
                p, self.cfg["model"], x, **kw))
        toks, out = np.asarray(t.gen.prompt), np.asarray(t.req.out_tokens)
        seq = np.zeros((1, self.mix["scheduler"]["max_total"]), np.int32)
        full = np.concatenate([toks, out[:-1]])
        seq[0, :len(full)] = full
        lg = np.asarray(self._logits[key](params, seq))[0]
        return lg[len(toks) - 1:len(toks) - 1 + len(out)]

    def check(self, limits: dict) -> list:
        """Free the program's state, then run the reference over the
        sample of finished requests and compare the served tokens."""
        sample = self.sample()
        self.ctx.note(requests_finished=len(self.done))
        self.free()
        if not sample:
            return [harness.Check("served_logit_gap", float("nan"),
                                  limits["served_logit_gap"])]
        params = self.init(harness.seed_key(self.ctx.seed))
        t_ref = time.perf_counter()
        gaps = [compare.served_gap(self.reference_rows(params, t),
                                   np.asarray(t.req.out_tokens))
                for t in sample]
        self.ctx.note(check_requests=len(sample),
                      check_tokens=sum(len(t.req.out_tokens) for t in sample),
                      check_gaps=gaps,
                      reference_s=time.perf_counter() - t_ref)
        return [harness.Check("served_logit_gap", float(max(gaps)),
                              limits["served_logit_gap"])]
