"""Batched request schedulers over the model zoo's prefill/decode steps.

Two admission policies, one slot-based execution model (static shapes,
a single jit signature for the process lifetime):

* :class:`BatchScheduler` — wave batching. Up to ``slots`` requests are
  packed into one fixed-shape batch, prefilled jointly, and decoded
  together; the next wave is admitted only when the batch drains, so
  early-finishing slots idle until the longest request completes.

* :class:`ContinuousScheduler` — continuous batching. Each slot is an
  independent lane over one shared cache: a freed slot is immediately
  re-prefilled (a batch-1 prefill written into the live cache along the
  batch axis via ``write_cache_slot``) while the other slots keep
  decoding. Per-slot ``pos`` vectors carry each lane's absolute
  position through ``decode_step``.

Both right-pad prompts to ``max_prompt`` and pass per-request
``lengths`` to prefill, so padded prefixes never enter attention and
per-request generation budgets are enforced without any per-step
host sync.

A scheduler owns its cache: every jitted program that takes the cache
and returns the new one donates it (and the held logits where it
returns those), so the layer loop updates it in place, and the arrays
passed in are consumed (DESIGN.md §15).
"""
from __future__ import annotations

import weakref
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.sink import NULL_OBS
from repro.serving.sampling import sample_tokens

if TYPE_CHECKING:  # annotation-only: keeps repro.serving import-cycle-free
    from repro.models import ModelApi


@dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (T,) int32
    max_new: int = 16
    out_tokens: list = field(default_factory=list)
    done: bool = False
    budget: int = 0                 # set at admission
    # lifecycle stamps in scheduler-step clock ticks (repro.obs §13);
    # -1 = never happened (e.g. first_token of a zero-budget request)
    submit_clock: int = -1
    admit_clock: int = -1
    first_token_clock: int = -1
    retire_clock: int = -1
    # paged-scheduler provenance (DESIGN.md §15): how the prompt entered
    # the cache — #prefill chunks run, #pages borrowed from the trie
    prefill_chunks: int = 0
    prefix_pages_reused: int = 0


@dataclass
class RequestRecord:
    """One retired request's latency breakdown, in step-clock ticks."""
    rid: int
    submit: int
    admit: int
    first_token: int
    retire: int
    decode: int                     # tokens generated
    budget: int
    prefill_chunks: int = 0
    prefix_pages_reused: int = 0

    @property
    def queue_latency(self) -> int:
        return self.admit - self.submit if self.admit >= 0 else -1

    @property
    def ttft(self) -> int:
        return (self.first_token - self.submit
                if self.first_token >= 0 else -1)

    @property
    def prefill_latency(self) -> int:
        """Ticks between admission and the first sampled token — the
        chunked-prefill share of TTFT (TTFT = queue_latency + this)."""
        return (self.first_token - self.admit
                if self.first_token >= 0 and self.admit >= 0 else -1)


@dataclass
class SchedulerStats:
    prefills: int = 0
    decode_steps: int = 0
    tokens_generated: int = 0
    requests_done: int = 0
    slot_steps: int = 0             # slots * decode_steps
    live_slot_steps: int = 0        # slots actually generating
    # one RequestRecord per retired request, in retirement order —
    # run_trace returns stats, so per-request latencies ride along
    # without changing any signature
    records: list = field(default_factory=list)

    @property
    def utilization(self) -> float:
        return self.live_slot_steps / max(self.slot_steps, 1)


def _serve_decode(model: ModelApi):
    """The ring-cache decode step as the jitted program
    ``serve_decode``."""
    def serve_decode(p, t, c, s):
        return model.decode_step(p, t, c, s, dtype=jnp.float32)
    return serve_decode


class _SchedulerBase:
    """Shared request plumbing: queue, slots, padding, sampling.

    With ``mesh``, the scheduler serves multi-device: params/cache/
    logits shardings are resolved once (``serving.sharding.serve_
    shardings``) and pinned as jit out_shardings, so every compiled
    entry point keeps its single process-lifetime signature (PR 5
    invariant) while the cache lives sharded across the mesh.
    """

    def __init__(self, model: ModelApi, *, slots: int = 4,
                 max_prompt: int = 64, max_total: int = 128,
                 temperature: float = 0.0, seed: int = 0,
                 cache_dtype=jnp.float32, obs=NULL_OBS, mesh=None,
                 rules=None, cache_rules=None, **shard_kw):
        assert max_prompt <= max_total
        from repro.models.layout import FRONTEND_KINDS
        if model.cfg.kind in FRONTEND_KINDS:
            raise ValueError(
                f"{type(self).__name__} serves token-only requests; "
                f"arch kind {model.cfg.kind!r} needs frontend inputs "
                "(patches/frames) that Request does not carry")
        self.model = model
        self.slots = slots
        self.max_prompt = max_prompt
        self.max_total = max_total
        self.temperature = temperature
        self.cache_dtype = cache_dtype
        self.key = jax.random.PRNGKey(seed)
        self.queue: list[Request] = []
        self.active: list[Optional[Request]] = [None] * slots
        self.stats = SchedulerStats()
        self.obs = obs
        self.mesh = mesh
        self.shardings = None
        if mesh is not None:
            from repro.serving.sharding import serve_shardings
            self.shardings = serve_shardings(
                model, mesh, slots=slots, max_total=max_total,
                dtype=cache_dtype, rules=rules, cache_rules=cache_rules,
                **shard_kw)
        # the step clock: one tick per step() call (admission attempts
        # and decode steps alike) — all Request stamps use this clock
        self.clock = 0
        # occupied slots, kept up to date at admission and free (the
        # step span's counter; no per-tick scan)
        self.live_slots = 0
        # the bf16 operand copy step() hands the programs
        # (engine.serve_operands): the copy, the precision and treedef it
        # was made for, and weak references to the params' arrays it was
        # made from
        self._held = {}
        self.operand_casts = 0
        self.operand_bytes = 0
        # program calls that consumed the cache passed in (donated)
        self.cache_donated = 0
        temperature = self.temperature

        def serve_sample(logits, key):
            return sample_tokens(logits, temperature=temperature, key=key)

        self._sample_jit = jax.jit(serve_sample)

    def _mesh_ctx(self):
        """Ambient-mesh context for jit tracing/execution: the in-model
        ``hint`` calls resolve against it; ``nullcontext`` when serving
        single-device."""
        return self.mesh if self.mesh is not None else nullcontext()

    def submit(self, req: Request) -> None:
        assert 1 <= len(req.prompt) <= self.max_prompt
        self.obs.instant("sched.submit", rid=req.rid)
        if req.submit_clock < 0:
            req.submit_clock = self.clock
        self.queue.append(req)

    @property
    def outstanding(self) -> bool:
        return bool(self.queue) or self.live_slots > 0

    def _budget(self, req: Request) -> int:
        # the cache holds prompt + generated tokens: never decode past it
        return min(req.max_new, self.max_total - len(req.prompt))

    def _retire(self, req: Request) -> None:
        """Mark done, stamp the clock, append the latency record."""
        req.done = True
        req.retire_clock = self.clock
        self.stats.requests_done += 1
        self.stats.records.append(RequestRecord(
            rid=req.rid, submit=req.submit_clock, admit=req.admit_clock,
            first_token=req.first_token_clock, retire=req.retire_clock,
            decode=len(req.out_tokens), budget=req.budget,
            prefill_chunks=req.prefill_chunks,
            prefix_pages_reused=req.prefix_pages_reused))

    # -- slot lifecycle hooks (overridden by the paged scheduler) -------
    def _slot_ready(self, i: int) -> bool:
        """Is slot ``i`` producing valid logits? (Paged slots are not
        ready while their chunked prefill is still streaming in.)"""
        return True

    def _occupy(self, i: int, req: Request, prefix_pages: int = 0,
                fresh_pages: int = 0) -> None:
        """Admit ``req`` into slot ``i``."""
        self.active[i] = req
        self.live_slots += 1
        self.obs.instant("sched.admitted", rid=req.rid, slot=i,
                         prefix_pages=prefix_pages, fresh_pages=fresh_pages)

    def _free_slot(self, i: int) -> None:
        """Release slot ``i``'s resources after retirement."""
        self.active[i] = None
        self.live_slots -= 1

    def _work_pending(self) -> bool:
        """Non-queue work in flight (e.g. unfinished chunked prefills)
        that must keep ``run`` stepping even when no tokens came out."""
        return False

    def _take_next(self) -> Optional[Request]:
        """Pop the next admissible request; zero-budget requests (prompt
        already fills the cache) complete immediately with no tokens."""
        while self.queue:
            req = self.queue.pop(0)
            req.budget = self._budget(req)
            if req.budget > 0:
                req.admit_clock = self.clock
                return req
            req.admit_clock = self.clock
            self._retire(req)
        return None

    def _sample(self, logits) -> jnp.ndarray:
        k = None
        if self.temperature > 0:
            self.key, k = jax.random.split(self.key)
        return self._sample_jit(logits, k)

    def _emit(self, tok_np) -> int:
        """Append sampled tokens to live requests; retire exhausted ones."""
        emitted = 0
        for i, r in enumerate(self.active):
            if r is None or r.done or not self._slot_ready(i):
                continue
            r.out_tokens.append(int(tok_np[i]))
            if r.first_token_clock < 0:
                r.first_token_clock = self.clock
            emitted += 1
            if len(r.out_tokens) >= r.budget:
                self._retire(r)
                self._free_slot(i)
        self.stats.tokens_generated += emitted
        return emitted

    def _decode_tick(self, params) -> int:
        """Sample from the held logits, emit/retire, then decode the
        batch one step (skipped when every lane just retired — the
        final tokens need no decode)."""
        obs = self.obs
        with obs.span("sched.sample"):
            tok = self._sample(self._last_logits)
        with obs.span("sched.readback"):
            tok_np = np.asarray(tok)[:, 0]
        with obs.span("sched.emit"):
            emitted = self._emit(tok_np)
        if not self.live_slots:
            return emitted
        with obs.span("sched.decode_step", step=self.clock):
            with self._mesh_ctx():
                cache = self._cache
                self._last_logits, self._cache = self._decode(
                    params, tok, cache, self._pos)
                self._count_donated(cache)
        self._pos = self._pos + 1
        self.stats.decode_steps += 1
        self.stats.slot_steps += self.slots
        self.stats.live_slot_steps += sum(
            r is not None and self._slot_ready(i)
            for i, r in enumerate(self.active))
        return emitted

    def _count_donated(self, cache) -> None:
        """Count a program call after which ``cache``, passed in, was
        consumed: how often donation lets the cache update in place."""
        self.cache_donated += jax.tree.leaves(cache)[0].is_deleted()

    def counters(self) -> dict:
        """The ``sched.step`` span's counters, read as the step ends."""
        return {"queue_depth": len(self.queue),
                "live_slots": self.live_slots,
                "decode_steps": self.stats.decode_steps,
                "prefills": self.stats.prefills,
                "operand_casts": self.operand_casts,
                "operand_bytes": self.operand_bytes,
                "cache_donated": self.cache_donated}

    def _operands(self, params):
        """The tree the programs compute with: ``params`` with its
        stacked matmul weights cast to bf16 where that is exact. The copy
        is built once for the params' arrays (and matmul precision) and
        held only while they live: a caller that frees its params frees
        the copy too, with no reference to them left here."""
        prec = jax.config.jax_default_matmul_precision
        held = self._held
        if held:
            leaves, tdef = jax.tree.flatten(params)
            if held["key"] == (prec, tdef) and all(
                    r() is x for r, x in zip(held["refs"], leaves)):
                return held["ops"]
            held.clear()                    # free the old copy first
        from repro.serving.engine import serve_operands
        ops = serve_operands(self.model.cfg, params)
        self.operand_bytes = 0
        if ops is params:
            return params
        leaves, tdef = jax.tree.flatten(params)
        copies = [o for o, x in zip(jax.tree.leaves(ops), leaves)
                  if o is not x]
        if not copies:
            return params
        self.operand_bytes = sum(o.nbytes for o in copies)
        self.operand_casts += 1
        held = self._held = {"key": (prec, tdef), "ops": ops}
        drop = lambda _: held.clear()       # noqa: E731
        held["refs"] = [weakref.ref(x, drop) for x in leaves]
        return ops

    def step(self, params) -> int:
        """One scheduler tick (admission, prefill, one decode step for
        the live lanes, as the subclass schedules them); returns #tokens
        emitted."""
        with self.obs.span("sched.step", counters=self.counters):
            self.clock += 1
            return self._step(self._operands(params))

    def _step(self, params) -> int:
        raise NotImplementedError

    def run(self, params, max_steps: int = 1000) -> SchedulerStats:
        steps = 0
        with self.obs.span("sched.run", scheduler=type(self).__name__,
                           slots=self.slots):
            while self.outstanding and steps < max_steps:
                if self.step(params) == 0 and not self.queue \
                        and not self._work_pending():
                    break
                steps += 1
        if self.outstanding:
            import warnings
            warnings.warn(
                f"{type(self).__name__}.run hit max_steps={max_steps} "
                "with requests still outstanding — results are "
                "truncated; raise max_steps", RuntimeWarning,
                stacklevel=2)
        return self.stats


class BatchScheduler(_SchedulerBase):
    """Slot-based wave batching (static shapes, per-slot pos)."""

    def __init__(self, model: ModelApi, **kw):
        super().__init__(model, **kw)
        max_total = self.max_total
        cache_dtype = self.cache_dtype
        sh = self.shardings
        jit_kw_pf = {} if sh is None else {
            "out_shardings": (sh.logits, sh.cache, sh.pos)}
        jit_kw_dec = {} if sh is None else {
            "out_shardings": (sh.logits, sh.cache)}
        def serve_prefill(p, b, l):
            return model.prefill(p, b, dtype=jnp.float32,
                                 cache_dtype=cache_dtype,
                                 cache_len=max_total, lengths=l)

        self._prefill = jax.jit(serve_prefill, **jit_kw_pf)
        self._decode = jax.jit(_serve_decode(model), donate_argnums=(2,),
                               **jit_kw_dec)
        self._cache = None
        self._pos = None            # (slots,) per-slot absolute position
        self._last_logits = None

    # ------------------------------------------------------------------
    def _admit(self, params) -> bool:
        """Fill free slots from the queue and prefill the wave jointly.

        Prompts are RIGHT-padded to ``max_prompt`` (one prefill jit
        signature for the process lifetime) with per-request ``lengths``
        so padded tails never enter attention or the cache."""
        free = [i for i, r in enumerate(self.active) if r is None]
        if not free or not self.queue:
            return False
        for i in free:
            req = self._take_next()
            if req is None:
                break
            self._occupy(i, req)
        if not self.live_slots:
            return False
        toks = np.zeros((self.slots, self.max_prompt), np.int32)
        lens = np.zeros((self.slots,), np.int32)
        for i, r in enumerate(self.active):
            if r is not None:
                toks[i, : len(r.prompt)] = r.prompt
                lens[i] = len(r.prompt)
        with self.obs.span("sched.prefill", wave=self.stats.prefills,
                           requests=int((lens > 0).sum())):
            with self._mesh_ctx():
                logits, cache, pos = self._prefill(
                    params, {"tokens": jnp.asarray(toks)},
                    jnp.asarray(lens))
        self._cache = cache
        self._pos = pos             # (slots,) = per-request prompt length
        self._last_logits = logits
        self.stats.prefills += 1
        return True

    def _step(self, params) -> int:
        """One decode step for all live slots."""
        if self._cache is None:
            with self.obs.span("sched.admission", step=self.clock):
                admitted = self._admit(params)
            if not admitted:
                return 0
        emitted = self._decode_tick(params)
        if not self.live_slots:
            self._cache = None  # drained -> allow the next admission wave
        return emitted


class ContinuousScheduler(_SchedulerBase):
    """Per-slot admission/retirement without draining the batch.

    The cache for all ``slots`` lanes is allocated once; a freed slot is
    refilled by a batch-1 prefill spliced in along the batch axis
    (``jax.lax.dynamic_update_slice`` with a *traced* slot index), so
    admission, like decode, has a single jit signature for the process
    lifetime."""

    def __init__(self, model: ModelApi, **kw):
        super().__init__(model, **kw)
        cfg = model.cfg
        slots, max_total = self.slots, self.max_total
        cache_dtype = self.cache_dtype
        sh = self.shardings
        crules = None if sh is None else sh.cache_rules
        self._cache = model.init_cache(slots, max_total, cache_dtype,
                                       mesh=self.mesh, cache_rules=crules)
        self._pos = jnp.zeros((slots,), jnp.int32)
        self._last_logits = jnp.zeros((slots, 1, cfg.padded_vocab),
                                      jnp.float32)
        if sh is not None:
            self._pos = jax.device_put(self._pos, sh.pos)
            self._last_logits = jax.device_put(self._last_logits,
                                               sh.logits)

        def serve_admit(params, cache, pos, logits, tokens, length, slot):
            lg1, c1, p1 = model.prefill(
                params, {"tokens": tokens}, dtype=jnp.float32,
                cache_dtype=cache_dtype, cache_len=max_total,
                lengths=length)
            cache, pos = model.write_cache_slot(cache, c1, slot, pos=pos,
                                                one_pos=p1[0],
                                                cache_rules=crules)
            logits = jax.lax.dynamic_update_slice(logits, lg1, (slot, 0, 0))
            return cache, pos, logits

        jit_kw_adm = {} if sh is None else {
            "out_shardings": (sh.cache, sh.pos, sh.logits)}
        jit_kw_dec = {} if sh is None else {
            "out_shardings": (sh.logits, sh.cache)}
        self._admit_one = jax.jit(serve_admit, donate_argnums=(1, 3),
                                  **jit_kw_adm)
        self._decode = jax.jit(_serve_decode(model), donate_argnums=(2,),
                               **jit_kw_dec)

    # ------------------------------------------------------------------
    def _admit(self, params) -> int:
        """Prefill queued requests into every free slot; others keep
        their cache/pos untouched."""
        admitted = 0
        for i, r in enumerate(self.active):
            if r is not None or not self.queue:
                continue
            req = self._take_next()
            if req is None:
                break
            self._occupy(i, req)
            toks = np.zeros((1, self.max_prompt), np.int32)
            toks[0, : len(req.prompt)] = req.prompt
            with self.obs.span("sched.prefill", slot=i, rid=req.rid):
                with self._mesh_ctx():
                    cache = self._cache
                    self._cache, self._pos, self._last_logits = \
                        self._admit_one(
                            params, cache, self._pos,
                            self._last_logits, jnp.asarray(toks),
                            jnp.asarray([len(req.prompt)], jnp.int32),
                            jnp.asarray(i, jnp.int32))
                    self._count_donated(cache)
            self.stats.prefills += 1
            admitted += 1
        return admitted

    def _step(self, params) -> int:
        """Admit into free slots, then one decode step for the batch."""
        with self.obs.span("sched.admission", step=self.clock):
            self._admit(params)
        if not self.live_slots:
            return 0
        return self._decode_tick(params)


class PagedContinuousScheduler(_SchedulerBase):
    """Continuous batching over the PAGED cache (DESIGN.md §15).

    Attention K/V live in a shared refcounted page pool instead of one
    ``(slots, max_total)`` ring per lane:

    * **Admission** allocates ``ceil((plen + budget) / page_size)``
      pages up front (minus any shared prefix) — when the free list is
      short the head request DEFERS in the queue instead of failing, so
      memory pressure degrades to queueing latency, never to an OOM.
    * **Prefix sharing**: prompts are hashed against the resident-prefix
      trie; matched full-page chunks are retained (refcount++) and the
      prefill starts after them. Pages are published to the trie at
      prefill *completion* and forgotten when their refcount hits zero.
      Only attention-cache families share (dense/moe) — recurrent state
      is per-request and cannot be borrowed.
    * **Chunked prefill**: prompts stream in ``prefill_chunk``-sized
      pieces (a page_size multiple), at most ``chunks_per_tick`` chunk
      launches per scheduler tick, interleaved with decode steps for the
      live lanes. A slot flips live only after its last chunk, so decode
      never observes a half-written prefix: until then its page-map row
      is all-dummy and its recurrent state is masked via ``live``.

    The device only ever sees static shapes — the page map is a fixed
    ``(slots, pages_per_slot)`` i32 array — so both entry points keep
    the single process-lifetime jit signature (PR 5 invariant).
    """

    def __init__(self, model: ModelApi, *, page_size: int = 16,
                 cache_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 chunks_per_tick: int = 1,
                 paged_kernel: Optional[bool] = None, **kw):
        from repro.serving.pages import (DUMMY_PAGE, PageTable, PrefixTrie,
                                         pages_per_slot)
        self.page_size = page_size
        self.pages_slot = pages_per_slot(
            kw.get("max_total", 128), page_size)
        if cache_pages is None:
            # default: every slot can hold a full-length request (+1 for
            # the dummy page) — byte-parity with the ring layout; pass
            # fewer pages to trade capacity for queueing (the
            # --memory-ceiling benchmark regime)
            cache_pages = kw.get("slots", 4) * self.pages_slot + 1
        self.cache_pages = cache_pages
        super().__init__(
            model, **kw,
            **({"page_size": page_size, "cache_pages": cache_pages}
               if kw.get("mesh") is not None else {}))
        cfg = model.cfg
        slots = self.slots
        if prefill_chunk is None:
            prefill_chunk = -(-self.max_prompt // page_size) * page_size
        assert prefill_chunk % page_size == 0 and prefill_chunk > 0, \
            "prefill_chunk must be a positive page_size multiple"
        self.prefill_chunk_len = prefill_chunk
        self.chunks_per_tick = chunks_per_tick
        if paged_kernel is None:
            from repro.kernels import runtime
            paged_kernel = not runtime.default_interpret()
        self.paged_kernel = paged_kernel
        # page pools only exist for attention-bearing families; pure-SSM
        # archs carry O(1) per-slot state and need zero pages
        from repro.models import layout
        self._has_pages = layout.has_page_pools(cfg)
        self._shareable = layout.shares_prefix(cfg)
        self._dummy = DUMMY_PAGE
        self.table = PageTable(cache_pages, page_size)
        self.trie = PrefixTrie(page_size)
        # memory-pressure / prefix-sharing / prefill counters
        # (benchmarks read these; the step span carries them)
        self.page_deferrals = 0
        self.prefix_pages_hit = 0
        self.prefix_pages_possible = 0
        self.prefill_chunks = 0
        # the paged-decode kernel's walk, one layer's worth, counted from
        # the host's copy of the positions: blocks fetched, and what a
        # walk of every block of every slot would fetch
        self.kv_blocks_walked = 0
        self.kv_blocks_full = 0
        self._walk = self._kernel_walk(cfg) if self._has_pages else None

        self._page_map = np.full((slots, self.pages_slot), DUMMY_PAGE,
                                 np.int32)
        self._live = np.zeros((slots,), bool)
        self._pos_host = np.zeros((slots,), np.int64)
        self._slot_pages: list[Optional[list]] = [None] * slots
        self._jobs: dict[int, dict] = {}

        sh = self.shardings
        crules = None if sh is None else sh.cache_rules
        self._cache = model.init_paged_cache(
            slots, cache_pages, page_size, self.cache_dtype,
            mesh=self.mesh, cache_rules=crules)
        self._pos = jnp.zeros((slots,), jnp.int32)
        self._last_logits = jnp.zeros((slots, 1, cfg.padded_vocab),
                                      jnp.float32)
        if sh is not None:
            self._pos = jax.device_put(self._pos, sh.pos)
            self._last_logits = jax.device_put(self._last_logits,
                                               sh.logits)

        def serve_prefill_chunk(params, cache, logits, tokens, start, valid,
                                row, slot):
            c1, lg = model.prefill_chunk(
                params, cache, tokens, start, valid, row, slot,
                dtype=jnp.float32)
            logits = jax.lax.dynamic_update_slice(logits, lg,
                                                  (slot, 0, 0))
            return c1, logits

        use_kernel = self.paged_kernel
        jit_kw_ch = {} if sh is None else {
            "out_shardings": (sh.paged_cache, sh.logits)}
        jit_kw_dec = {} if sh is None else {
            "out_shardings": (sh.logits, sh.paged_cache)}
        def serve_decode(p, t, c, s, pm, lv):
            return model.decode_step_paged(p, t, c, s, pm, lv,
                                           dtype=jnp.float32,
                                           use_kernel=use_kernel)

        # the cache (and the chunk's logits) are donated: the layer loop
        # writes each layer's new rows into them in place
        self._chunk_jit = jax.jit(serve_prefill_chunk, donate_argnums=(1, 2),
                                  **jit_kw_ch)
        self._decode_jit = jax.jit(serve_decode, donate_argnums=(2,),
                                   **jit_kw_dec)

    def _kernel_walk(self, cfg):
        """``(window, block_tokens, num_blocks, banded)`` of the kernel's
        walk (``kernels/paged_attn``), or None without the kernel."""
        if not self.paged_kernel:
            return None
        from repro.kernels import paged_attn, runtime
        from repro.serving.engine import effective_window
        P = self.pages_slot
        if paged_attn.page_grid(cfg.head_dim, runtime.default_interpret()):
            return 0, self.page_size, P, False
        w = effective_window(cfg)
        ppb = paged_attn.block_pages(
            self.page_size, P, cfg.num_kv_heads, cfg.head_dim,
            jnp.dtype(self.cache_dtype).itemsize, w)
        return w, ppb * self.page_size, -(-P // ppb), True

    # -- page planning --------------------------------------------------
    def _plan_pages(self, req: Request, budget: int):
        """(shared, fresh) page lists for a request, or None to defer.

        Commit is atomic: the trie match is only retained once the fresh
        allocation is known to fit, so a deferral leaves no refcounts
        behind."""
        if not self._has_pages:
            return [], []
        plen = len(req.prompt)
        total = -(-(plen + budget) // self.page_size)
        assert total <= self.pages_slot
        shared: list = []
        if self._shareable:
            # cap: at least one prompt token always prefills, so the
            # admission logits come from a real forward pass
            cap = min((plen - 1) // self.page_size, total)
            shared = self.trie.match(np.asarray(req.prompt), cap)
            self.prefix_pages_possible += cap
        need = total - len(shared)
        if self.table.num_free < need:
            return None
        if shared:
            self.table.retain(shared)
            self.prefix_pages_hit += len(shared)
        fresh = self.table.alloc(need)
        assert fresh is not None
        return shared, fresh

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_pages_hit / max(self.prefix_pages_possible, 1)

    # -- slot lifecycle -------------------------------------------------
    def _slot_ready(self, i: int) -> bool:
        return bool(self._live[i])

    def _free_slot(self, i: int) -> None:
        pages = self._slot_pages[i]
        if pages:
            for pg in self.table.release(pages):
                self.trie.forget(pg)
        self._slot_pages[i] = None
        self._page_map[i] = self._dummy
        self._live[i] = False
        self._jobs.pop(i, None)
        super()._free_slot(i)

    def _work_pending(self) -> bool:
        return bool(self._jobs)

    # -- admission / prefill --------------------------------------------
    def _admit(self) -> int:
        """Plan pages + enqueue a chunked-prefill job per free slot.
        Head-of-line deferral: if the head request's pages don't fit,
        admission stops until retirements refill the free list."""
        admitted = 0
        for i in range(self.slots):
            if self.active[i] is not None or not self.queue:
                continue
            req = self.queue[0]
            budget = self._budget(req)
            if budget <= 0:
                self.queue.pop(0)
                req.budget = budget
                req.admit_clock = self.clock
                self._retire(req)
                continue
            plan = self._plan_pages(req, budget)
            if plan is None:
                self.page_deferrals += 1
                break
            self.queue.pop(0)
            shared, fresh = plan
            req.budget = budget
            req.admit_clock = self.clock
            req.prefix_pages_reused = len(shared)
            self._occupy(i, req, len(shared), len(fresh))
            pages = shared + fresh
            self._slot_pages[i] = pages
            self._jobs[i] = {
                "req": req, "pages": pages,
                "start": len(shared) * self.page_size,
                "plen": len(req.prompt)}
            admitted += 1
        return admitted

    def _advance_prefills(self, params) -> None:
        """Run up to ``chunks_per_tick`` prefill chunks per pending job;
        completed slots splice their page row in and flip live."""
        C = self.prefill_chunk_len
        P = self.pages_slot
        for slot in list(self._jobs):
            job = self._jobs[slot]
            req = job["req"]
            row = np.full((P,), self._dummy, np.int32)
            row[: len(job["pages"])] = job["pages"]
            for _ in range(self.chunks_per_tick):
                start, plen = job["start"], job["plen"]
                valid = min(C, plen - start)
                toks = np.zeros((1, C), np.int32)
                toks[0, :valid] = req.prompt[start:start + valid]
                with self.obs.span("sched.prefill_chunk", slot=slot,
                                   rid=req.rid, start=start, valid=valid):
                    with self._mesh_ctx():
                        cache = self._cache
                        self._cache, self._last_logits = self._chunk_jit(
                            params, cache, self._last_logits,
                            jnp.asarray(toks),
                            jnp.asarray(start, jnp.int32),
                            jnp.asarray(valid, jnp.int32),
                            jnp.asarray(row),
                            jnp.asarray(slot, jnp.int32))
                        self._count_donated(cache)
                req.prefill_chunks += 1
                self.prefill_chunks += 1
                job["start"] = start + valid
                if job["start"] >= plen:
                    self._page_map[slot] = row
                    self._live[slot] = True
                    self._pos = self._pos.at[slot].set(plen)
                    self._pos_host[slot] = plen
                    if self._shareable:
                        self.trie.register(
                            np.asarray(req.prompt),
                            job["pages"][: plen // self.page_size])
                    self.stats.prefills += 1
                    del self._jobs[slot]
                    break

    # -- decode ---------------------------------------------------------
    def _decode(self, params, tok, cache, pos):
        # copies: the host rewrites both arrays in place while the step
        # may still be queued, and a device array made from a numpy
        # array can alias it (zero-copy on the CPU)
        out = self._decode_jit(params, tok, cache, pos,
                               jnp.asarray(self._page_map.copy()),
                               jnp.asarray(self._live.copy()))
        if self._walk is not None:
            from repro.kernels.paged_attn import block_band
            window, bt, nblk, banded = self._walk
            walked = self.slots * nblk
            if banded:
                walked = int(block_band(
                    self._pos_host, self._live, window=window,
                    block_tokens=bt, num_blocks=nblk)[1].sum())
            self.kv_blocks_walked += walked
            self.kv_blocks_full += self.slots * nblk
        self._pos_host += 1
        return out

    def counters(self) -> dict:
        return {"queue_depth": len(self.queue),
                "live_slots": self.live_slots,
                "free_pages": self.table.num_free,
                "prefix_pages_hit": self.prefix_pages_hit,
                "prefix_pages_possible": self.prefix_pages_possible,
                "page_deferrals": self.page_deferrals,
                "decode_steps": self.stats.decode_steps,
                "prefill_chunks": self.prefill_chunks,
                "kv_blocks_walked": self.kv_blocks_walked,
                "kv_blocks_full": self.kv_blocks_full,
                "operand_casts": self.operand_casts,
                "operand_bytes": self.operand_bytes,
                "cache_donated": self.cache_donated}

    def _step(self, params) -> int:
        """Admit + advance chunked prefills, then one decode step for
        the live lanes."""
        with self.obs.span("sched.admission", step=self.clock):
            self._admit()
        self._advance_prefills(params)
        if not self._live.any():
            return 0
        return self._decode_tick(params)


SCHEDULERS = {"wave": BatchScheduler, "continuous": ContinuousScheduler,
              "paged": PagedContinuousScheduler}


def make_scheduler(kind: str, model: ModelApi, **kw):
    try:
        cls = SCHEDULERS[kind]
    except KeyError:
        raise ValueError(
            f"unknown scheduler {kind!r}; choose from {sorted(SCHEDULERS)}")
    return cls(model, **kw)


def run_trace(sched, params, arrivals, max_steps: int = 10_000):
    """Drive a scheduler through an arrival trace.

    arrivals: iterable of ``(arrive_step, Request)`` — each request is
    submitted once the driver's step counter reaches ``arrive_step``
    (steps advance even while the scheduler idles waiting for work, so
    a bursty Poisson trace exercises admission under load). Returns the
    scheduler's stats.
    """
    pending = sorted(arrivals, key=lambda a: a[0])
    i = 0
    steps = 0
    with sched.obs.span("sched.run", scheduler=type(sched).__name__,
                        driver="trace", requests=len(pending)):
        while (i < len(pending) or sched.outstanding) and \
                steps < max_steps:
            while i < len(pending) and pending[i][0] <= steps:
                sched.submit(pending[i][1])
                i += 1
            sched.step(params)
            steps += 1
    if i < len(pending) or sched.outstanding:
        import warnings
        warnings.warn(
            f"run_trace hit max_steps={max_steps} with requests still "
            "outstanding — results are truncated; raise max_steps",
            RuntimeWarning, stacklevel=2)
    return sched.stats
