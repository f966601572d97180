"""Paged serving correctness (DESIGN.md §15): PageTable/PrefixTrie
invariants, paged-vs-ring decode parity for every family, chunked
prefill == one-shot, Pallas kernel parity, cache-dtype plumbing, and
scheduler-level equivalence with prefix reuse and zero page leaks."""
import dataclasses
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import build_model, layout
from repro.serving import (
    ContinuousScheduler, DUMMY_PAGE, PagedContinuousScheduler, PageTable,
    PrefixTrie, Request, engine, pages_per_slot, run_trace,
)

# ---------------------------------------------------------------- pages


def test_page_table_alloc_release_roundtrip():
    t = PageTable(num_pages=8, page_size=4)      # 7 usable + dummy
    assert t.num_free == 7
    a = t.alloc(3)
    b = t.alloc(4)
    assert a is not None and b is not None
    assert t.num_free == 0
    assert DUMMY_PAGE not in a + b
    assert len(set(a + b)) == 7                  # no double-handout
    # pool exhausted -> deferral cue, no partial allocation
    assert t.alloc(1) is None
    assert t.num_free == 0
    freed = t.release(a)
    assert sorted(freed) == sorted(a)
    assert t.num_free == 3
    t.release(b)
    assert t.num_free == 7


def test_page_table_refcounts():
    t = PageTable(num_pages=4, page_size=4)
    (p,) = t.alloc(1)
    t.retain([p])                                # shared by two owners
    assert t.release([p]) == []                  # still referenced
    assert t.num_free == 2
    assert t.release([p]) == [p]                 # last owner frees
    assert t.num_free == 3


def test_page_table_occupancy():
    t = PageTable(num_pages=5, page_size=4)
    assert t.occupancy == 0.0
    t.alloc(2)
    assert t.occupancy == pytest.approx(0.5)


def test_prefix_trie_match_register_forget():
    ps = 4
    cap = lambda p: (len(p) - 1) // ps
    trie = PrefixTrie(ps)
    prompt = np.arange(1, 12, dtype=np.int32)    # 11 tokens, 2 full pages
    assert trie.match(prompt, cap(prompt)) == []
    assert trie.register(prompt, [3, 5]) == 2
    # full-page chunks shared; callers cap at (plen-1)//ps so the page
    # holding the final prompt token is never shared mid-write
    assert trie.match(prompt, cap(prompt)) == [3, 5]
    assert trie.match(prompt[:ps + 1], 1) == [3]
    assert trie.match(prompt[:ps], cap(prompt[:ps])) == []   # cap == 0
    divergent = prompt.copy()
    divergent[1] = 99
    assert trie.match(divergent, cap(divergent)) == []
    # forgetting the parent page orphans the chain from the root
    trie.forget(3)
    assert trie.match(prompt, cap(prompt)) == []
    trie.register(prompt, [3, 5])
    trie.forget(5)
    assert trie.match(prompt, cap(prompt)) == [3]
    # first writer keeps a trie slot; duplicates stay unshared
    assert trie.register(prompt, [3, 7]) == 1    # only chunk 2 republished
    assert trie.match(prompt, cap(prompt)) == [3, 7]


def test_pages_per_slot():
    assert pages_per_slot(16, 4) == 4
    assert pages_per_slot(17, 4) == 5


# ------------------------------------------------- paged decode parity

FAMILIES = {
    "dense": ("qwen1.5-0.5b", 0, {}),
    "dense-window": ("qwen1.5-0.5b", 8, {}),
    "sliding": ("starcoder2-3b", 0, {"sliding_window": 8}),
    "moe": ("llama4-scout-17b-a16e", 0, {"moe_capacity_factor": 8.0}),
    "ssm": ("mamba2-370m", 0, {}),
    "hybrid": ("recurrentgemma-9b", 0, {"attention_window": 8}),
    # 1 (rec, rec, attn) group + a 2-layer recurrent tail stack
    "hybrid_tail": ("recurrentgemma-9b", 0,
                    {"attention_window": 8, "num_layers": 5}),
}


def _tiny(arch, **over):
    cfg = get_arch(arch).reduced(num_layers=2, d_model=64, d_ff=128,
                                 vocab_size=128)
    if over:
        cfg = dataclasses.replace(cfg, **over)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _ring_reference(params, cfg, prompt, max_new, serve_window):
    max_total = len(prompt) + max_new
    toks = jnp.asarray(prompt)[None]
    logits, cache, pos = engine.prefill(
        params, cfg, {"tokens": toks}, dtype=jnp.float32,
        cache_dtype=jnp.float32, cache_len=max_total,
        serve_window=serve_window)
    out_logits = [np.asarray(logits[0, 0])]
    tok = jnp.argmax(logits, -1).astype(jnp.int32).reshape(1, 1)
    out_toks = [int(tok[0, 0])]
    for _ in range(max_new - 1):
        logits, cache = engine.decode_step(
            params, cfg, tok, cache, pos, dtype=jnp.float32,
            serve_window=serve_window)
        out_logits.append(np.asarray(logits[0, 0]))
        tok = jnp.argmax(logits, -1).astype(jnp.int32).reshape(1, 1)
        out_toks.append(int(tok[0, 0]))
        pos = pos + 1
    return out_logits, out_toks


def _paged_run(params, cfg, prompt, max_new, serve_window, *, ps=4,
               chunk=8):
    plen = len(prompt)
    P = pages_per_slot(plen + max_new, ps)
    table = PageTable(P + 1, ps)
    cache = engine.init_paged_cache_tree(cfg, 1, P + 1, ps, jnp.float32)
    row = jnp.asarray(table.alloc(P), jnp.int32)
    padded = np.zeros(((plen + chunk - 1) // chunk) * chunk, np.int32)
    padded[:plen] = prompt
    start = 0
    while start < plen:
        valid = min(chunk, plen - start)
        cache, logits = engine.prefill_chunk(
            params, cfg, cache, jnp.asarray(
                padded[start:start + chunk])[None],
            start, valid, row, 0, dtype=jnp.float32,
            serve_window=serve_window)
        start += valid
    out_logits = [np.asarray(logits[0, 0])]
    tok = jnp.argmax(logits, -1).astype(jnp.int32).reshape(1, 1)
    out_toks = [int(tok[0, 0])]
    page_map, live = row[None], jnp.asarray([True])
    pos = jnp.asarray([plen], jnp.int32)
    for _ in range(max_new - 1):
        logits, cache = engine.decode_step_paged(
            params, cfg, tok, cache, pos, page_map, live,
            dtype=jnp.float32, serve_window=serve_window)
        out_logits.append(np.asarray(logits[0, 0]))
        tok = jnp.argmax(logits, -1).astype(jnp.int32).reshape(1, 1)
        out_toks.append(int(tok[0, 0]))
        pos = pos + 1
    return out_logits, out_toks


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_paged_decode_matches_ring(family):
    arch, serve_window, over = FAMILIES[family]
    cfg, _, params = _tiny(arch, **over)
    prompt = np.random.default_rng(0).integers(
        1, cfg.vocab_size, size=11).astype(np.int32)
    ref_l, ref_t = _ring_reference(params, cfg, prompt, 5, serve_window)
    pg_l, pg_t = _paged_run(params, cfg, prompt, 5, serve_window)
    assert pg_t == ref_t
    err = max(np.abs(a - b).max() for a, b in zip(ref_l, pg_l))
    assert err <= 1e-5, f"{family}: max |logits diff| {err}"


def test_chunked_prefill_matches_one_shot():
    cfg, _, params = _tiny("qwen1.5-0.5b")
    prompt = np.random.default_rng(1).integers(
        1, cfg.vocab_size, size=13).astype(np.int32)
    # one-shot: chunk covers the whole (padded) prompt
    l_one, t_one = _paged_run(params, cfg, prompt, 4, 0, ps=4, chunk=16)
    l_chk, t_chk = _paged_run(params, cfg, prompt, 4, 0, ps=4, chunk=4)
    assert t_chk == t_one
    err = max(np.abs(a - b).max() for a, b in zip(l_one, l_chk))
    assert err <= 1e-5


def _gather_attention(q, k_pages, v_pages, page_map, pos, window):
    """The jnp gather path's math: gather + masked softmax."""
    B, K, _, hd = q.shape
    P, ps = page_map.shape[1], k_pages.shape[1]
    kk = k_pages[page_map].reshape(B, P * ps, K, hd)
    vv = v_pages[page_map].reshape(B, P * ps, K, hd)
    k_pos = jnp.arange(P * ps)[None, :]
    ok = k_pos <= pos[:, None]
    if window:
        ok &= k_pos > pos[:, None] - window
    s = jnp.einsum("bkgh,btkh->bkgt", q, kk) / np.sqrt(hd)
    s = jnp.where(ok[:, None, None, :], s, -1e30)
    return jnp.einsum("bkgt,btkh->bkgh", jax.nn.softmax(s, -1), vv)


# page size 4; pages_per_block 2 (8-token blocks) unless a case says
# otherwise; page_map None: a shuffled map over pages 1.. of the pool
KERNEL_CASES = {
    "seed_no_window": dict(P=3, pos=[5, 9], window=0,
                           page_map=[[1, 2, 3], [3, 1, 2]]),
    "seed_window_4": dict(P=3, pos=[5, 9], window=4,
                          page_map=[[1, 2, 3], [3, 1, 2]]),
    "pos_0": dict(P=4, pos=[0, 0, 9]),
    "pos_on_page_edge": dict(P=4, pos=[3, 4, 11, 12]),
    "pos_on_block_edge": dict(P=6, pos=[7, 8, 15, 16, 23]),
    "ragged_last_block": dict(P=7, ppb=3, pos=[27, 12, 24, 11]),
    "window_under_block": dict(P=6, ppb=4, pos=[2, 17, 23, 16], window=3),
    "window_not_page_multiple": dict(P=6, pos=[5, 13, 22, 23], window=6),
    "shuffled_page_map": dict(P=8, pos=[31, 0, 17, 25], window=10),
    "dead_lanes_beside_live": dict(P=5, pos=[13, 19, 7, 4],
                                   live=[True, False, True, False]),
    "page_grid": dict(P=3, pos=[5, 9], window=4, grid=True),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_paged_kernel_matches_jnp_gather(case):
    """The kernel (interpret mode) against the gather path, walking
    several blocks per slot."""
    from repro.kernels.paged_attn import _page_grid_decode, paged_decode
    c = KERNEL_CASES[case]
    P, pos = c["P"], c["pos"]
    B, ps, K, G, hd = len(pos), 4, 2, 2, 8
    rng = np.random.default_rng(2)
    num_pages = B * P + 1
    q = jnp.asarray(rng.normal(size=(B, K, G, hd)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(num_pages, ps, K, hd)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(num_pages, ps, K, hd)),
                          jnp.float32)
    page_map = c.get("page_map")
    if page_map is None:
        page_map = rng.permutation(num_pages - 1).reshape(B, P) + 1
    live = np.asarray(c.get("live", [True] * B))
    page_map = np.where(live[:, None], page_map, DUMMY_PAGE)
    page_map = jnp.asarray(page_map, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    window = c.get("window", 0)
    if c.get("grid"):
        out = _page_grid_decode(q, k_pages, v_pages, page_map, pos, window,
                                interpret=True)
    else:
        out = paged_decode(q, k_pages, v_pages, page_map, pos,
                           window=window, live=jnp.asarray(live),
                           pages_per_block=c.get("ppb", 2), interpret=True)
    ref = _gather_attention(q, k_pages, v_pages, page_map, pos, window)
    assert float(jnp.abs(out - ref)[live].max()) <= 1e-5
    assert bool(jnp.isfinite(out).all())
    if not live.all():
        # live lanes come out bit for bit as in a walk where all are live
        every = paged_decode(q, k_pages, v_pages, page_map, pos,
                             window=window, pages_per_block=c.get("ppb", 2),
                             interpret=True)
        assert np.array_equal(np.asarray(out)[live],
                              np.asarray(every)[live])


@pytest.mark.parametrize("dead", [False, True])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("walk", ["block", "page_grid"])
def test_paged_kernel_reads_one_layer_of_a_stack(walk, layer, dead):
    """On stacked pools ``(layers, num_pages, ...)`` with a traced
    ``layer`` the kernel (interpret mode) returns exactly what it returns
    on that layer's own pool: the block walk and the page grid (head dim
    64), with dead lanes beside live ones."""
    from repro.kernels.paged_attn import _page_grid_decode, paged_decode
    L, P, ps, K, G = 3, 5, 4, 2, 2
    hd = 64 if walk == "page_grid" else 8
    pos = jnp.asarray([13, 19, 7, 4], jnp.int32)
    B = pos.shape[0]
    live = np.asarray([True, not dead, True, not dead])
    rng = np.random.default_rng(4)
    num_pages = B * P + 1
    q = jnp.asarray(rng.normal(size=(B, K, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(L, num_pages, ps, K, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, num_pages, ps, K, hd)), jnp.float32)
    page_map = rng.permutation(num_pages - 1).reshape(B, P) + 1
    page_map = jnp.asarray(np.where(live[:, None], page_map, DUMMY_PAGE),
                           jnp.int32)

    def attend(kp, vp, ly):
        if walk == "page_grid":
            return _page_grid_decode(q, kp, vp, page_map, pos, 6,
                                     interpret=True, layer=ly)
        return paged_decode(q, kp, vp, page_map, pos, window=6,
                            live=jnp.asarray(live), layer=ly,
                            pages_per_block=2, interpret=True)

    stacked = jax.jit(attend)(k, v, jnp.asarray(layer, jnp.int32))
    own = attend(k[layer], v[layer], None)
    assert np.array_equal(np.asarray(stacked), np.asarray(own))
    ref = _gather_attention(q, k[layer], v[layer], page_map, pos, 6)
    assert float(jnp.abs(stacked - ref)[live].max()) <= 1e-5


def test_block_band_bounds_the_walk():
    from repro.kernels.paged_attn import block_band, block_pages
    pos = np.asarray([0, 15, 16, 100, 4095, 4095])
    live = np.asarray([True, True, True, True, True, False])
    first, count = block_band(pos, live, window=0, block_tokens=16,
                              num_blocks=256)
    assert first.tolist() == [0] * 6
    assert count.tolist() == [1, 1, 2, 7, 256, 0]
    first, count = block_band(pos, live, window=20, block_tokens=16,
                              num_blocks=256)
    assert first.tolist() == [0, 0, 0, 5, 254, 254]
    assert count.tolist() == [1, 1, 2, 2, 2, 0]
    # the code cell's widths: 32 pages of 16 tokens (512) per block
    assert block_pages(16, 256, 2, 128, 4, 4096) == 32
    assert block_pages(16, 256, 2, 128, 4, 100) == 7     # the band
    assert block_pages(16, 3, 2, 128, 4) == 3            # the slot
    assert block_pages(16, 256, 8, 128, 4) == 16         # VMEM


# --------------------------------------------------- cache-dtype plumb


@pytest.mark.parametrize("sched_cls",
                         [ContinuousScheduler, PagedContinuousScheduler])
def test_cache_dtype_reaches_cache_leaves(sched_cls):
    cfg, model, params = _tiny("qwen1.5-0.5b")
    sched = sched_cls(model, slots=2, max_prompt=8, max_total=16,
                      cache_dtype=jnp.bfloat16)
    sched.submit(Request(rid=0, prompt=np.arange(1, 7, dtype=np.int32),
                         max_new=2))
    for _ in range(16):
        sched.step(params)
        if not sched.outstanding:
            break
    floating = [leaf.dtype for leaf in jax.tree.leaves(sched._cache)
                if jnp.issubdtype(leaf.dtype, jnp.floating)]
    assert floating and all(d == jnp.bfloat16 for d in floating)
    assert sched.stats.requests_done == 1


# ------------------------------------------------- scheduler-level e2e


def _trace(cfg, rng, n_req, template=0):
    tmpl = rng.integers(1, cfg.vocab_size, size=template).astype(np.int32)
    arrivals, step = [], 0
    for rid in range(n_req):
        tail = rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(3, 10))).astype(np.int32)
        prompt = np.concatenate([tmpl, tail])[:14].astype(np.int32)
        arrivals.append((step, Request(rid=rid, prompt=prompt,
                                       max_new=int(rng.integers(2, 6)))))
        step += int(rng.poisson(2.0))
    return arrivals


def test_paged_scheduler_matches_continuous():
    cfg, model, params = _tiny("qwen1.5-0.5b")
    mk = lambda: np.random.default_rng(7)
    ring = _trace(cfg, mk(), 6)
    paged = _trace(cfg, mk(), 6)
    kw = dict(slots=2, max_prompt=14, max_total=20, temperature=0.0)
    s_ring = run_trace(ContinuousScheduler(model, **kw), params, ring)
    sched = PagedContinuousScheduler(model, page_size=4, prefill_chunk=8,
                                     **kw)
    s_paged = run_trace(sched, params, paged)
    assert s_paged.requests_done == s_ring.requests_done == 6
    for (_, a), (_, b) in zip(ring, paged):
        assert b.out_tokens == a.out_tokens, f"rid {a.rid} diverged"
    # every page returned to the pool, trie fully forgotten
    assert sched.table.num_free == sched.cache_pages - 1
    p0 = ring[0][1].prompt
    assert sched.trie.match(p0, (len(p0) - 1) // 4) == []
    assert len(sched.trie) == 0
    # chunk=8 over up-to-14-token prompts -> some prompts take 2 chunks
    assert any(r.prefill_chunks >= 2 for r in s_paged.records)


# every kind the paged scheduler serves, by its layer layout
SERVED_KINDS = {"dense": "dense", "moe": "moe", "ssm": "ssm",
                "hybrid": "hybrid_tail", "mamba_hybrid": None}
FORWARD_TOL = 2e-5


def _served_model(kind):
    if SERVED_KINDS[kind] is None:
        cfg, params = _granite_tiny()
        return cfg, build_model(cfg), params
    arch, _, over = FAMILIES[SERVED_KINDS[kind]]
    return _tiny(arch, **over)


@pytest.mark.parametrize("kind", sorted(SERVED_KINDS))
def test_paged_scheduler_serves_the_forward_walk(kind):
    """Each served kind, with requests streaming in chunk by chunk (so
    lanes sit mid-prefill, not live, while others decode) and the cache
    donated to every call: each served token and the logits it was
    sampled from are those of the plain forward walk over the same
    weights (no cache), and every call consumed the cache it was
    handed."""
    cfg, model, params = _served_model(kind)
    max_total = 24
    sched = PagedContinuousScheduler(
        model, slots=3, max_prompt=14, max_total=max_total, page_size=4,
        prefill_chunk=4, temperature=0.0)
    served = {}                        # rid -> logits rows sampled from
    sample = sched._sample

    def recording(logits):
        rows = np.asarray(logits)[:, 0]
        for i, r in enumerate(sched.active):
            if r is not None and sched._slot_ready(i):
                served.setdefault(r.rid, []).append(rows[i])
        return sample(logits)

    sched._sample = recording
    arrivals = _trace(cfg, np.random.default_rng(8), 6)
    pending, step, both = list(arrivals), 0, 0
    while pending or sched.outstanding:
        while pending and pending[0][0] <= step:
            sched.submit(pending.pop(0)[1])
        cache = sched._cache
        before = (sched.stats.decode_steps, sched.prefill_chunks)
        sched.step(params)
        if (sched.stats.decode_steps > before[0]
                and sched.prefill_chunks > before[1]):
            # a chunk and a decode ran: the cache handed in is consumed
            assert all(a.is_deleted() for a in jax.tree.leaves(cache))
            both += 1
        step += 1
    assert both and sched.stats.requests_done == 6
    c = sched.counters()
    assert c["cache_donated"] == c["decode_steps"] + c["prefill_chunks"]

    fwd = jax.jit(lambda t: model.forward(params, {"tokens": t},
                                          dtype=jnp.float32, remat=False)[0])
    for _, r in arrivals:
        seq = np.zeros((1, max_total), np.int32)
        full = np.concatenate([r.prompt, r.out_tokens[:-1]])
        seq[0, :len(full)] = full
        ref = np.asarray(fwd(jnp.asarray(seq)))[0, len(r.prompt) - 1:
                                                  len(full)]
        assert r.out_tokens == ref.argmax(-1).tolist(), f"rid {r.rid}"
        err = float(np.abs(np.stack(served[r.rid]) - ref).max())
        assert err <= FORWARD_TOL, f"rid {r.rid}: {err}"


@pytest.mark.parametrize("family", ["dense", "sliding"])
def test_paged_scheduler_kernel_matches_gather(family):
    """The scheduler with the paged-decode kernel (interpret mode, lanes
    mid-prefill beside decoding ones) emits the gather path's tokens."""
    arch, _, over = FAMILIES[family]
    cfg, model, params = _tiny(arch, **over)
    kw = dict(slots=3, max_prompt=14, max_total=20, temperature=0.0,
              page_size=4, prefill_chunk=4)
    out = {}
    for kernel in (False, True):
        arrivals = _trace(cfg, np.random.default_rng(5), 6)
        sched = PagedContinuousScheduler(model, paged_kernel=kernel, **kw)
        assert run_trace(sched, params, arrivals).requests_done == 6
        out[kernel] = [r.out_tokens for _, r in arrivals]
        if kernel:
            assert 0 < sched.kv_blocks_walked <= sched.kv_blocks_full
    assert out[True] == out[False]


def test_paged_scheduler_prefix_reuse_and_deferral():
    cfg, model, params = _tiny("qwen1.5-0.5b")
    rng = np.random.default_rng(11)
    arrivals = _trace(cfg, rng, 8, template=8)
    # pool sized below slots * pages_per_slot: deferrals must engage
    sched = PagedContinuousScheduler(
        model, page_size=4, cache_pages=9, slots=2, max_prompt=14,
        max_total=20, temperature=0.0)
    stats = run_trace(sched, params, arrivals)
    assert stats.requests_done == 8
    reused = sum(r.prefix_pages_reused for r in stats.records)
    assert reused > 0                    # shared template actually hit
    assert sched.prefix_hit_rate > 0
    assert sched.table.num_free == sched.cache_pages - 1   # no leaks


# ------------------------------------------------- bf16 matmul operands


def _leaves_with_path(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


def _is_operand(cfg, path) -> bool:
    keys = [getattr(k, "key", None) for k in path]
    stacked = layout.stacked_keys(layout.layer_layout(cfg))
    return (keys[0] in stacked and all(
        isinstance(getattr(k, "key", None), str) for k in path)
        and keys[-1] in engine.OPERAND_WEIGHTS)


def _bf16_rounded(cfg, params):
    """``params`` whose stacked matmul weights went to bf16 and back."""
    return engine.map_operands(
        cfg, lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), params)


def _granite_tiny():
    cfg = get_arch("granite-4.0-h-small").reduced(
        num_layers=4, d_model=128, d_ff=64, vocab_size=256, num_experts=8)
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


def test_operand_tree_is_params_on_cpu_and_at_highest(monkeypatch):
    cfg, _, params = _tiny("qwen1.5-0.5b")
    assert engine.serve_operands(cfg, params) is params      # CPU
    monkeypatch.setattr(engine, "_on_tpu", lambda p: True)
    assert engine.serve_operands(cfg, params) is not params  # TPU, DEFAULT
    for prec, exact in (("bfloat16", True), ("highest", False),
                        ("float32", False), ("tensorfloat32", False)):
        with jax.default_matmul_precision(prec):
            assert engine.bf16_operands_exact(params) is exact, prec
            assert (engine.serve_operands(cfg, params)
                    is params) is not exact


@pytest.mark.parametrize("family", sorted(FAMILIES) + ["mamba_hybrid"])
def test_forced_operands_cast_exactly_the_stacked_matmul_weights(
        family, monkeypatch):
    monkeypatch.setattr(engine, "_on_tpu", lambda p: True)
    if family == "mamba_hybrid":
        cfg, params = _granite_tiny()
    else:
        arch, _, over = FAMILIES[family]
        cfg, _, params = _tiny(arch, **over)
    ops = engine.serve_operands(cfg, params)
    assert (jax.tree_util.tree_structure(ops)
            == jax.tree_util.tree_structure(params))
    cast = 0
    for (path, o), (_, p) in zip(_leaves_with_path(ops),
                                 _leaves_with_path(params)):
        if _is_operand(cfg, path):
            cast += 1
            assert o.dtype == jnp.bfloat16 and o.shape == p.shape
            assert o.shape[0] == jax.tree.leaves(params[path[0].key])[0] \
                .shape[0]                          # the scanned layer axis
            assert np.array_equal(np.asarray(o),
                                  np.asarray(p.astype(jnp.bfloat16)))
        else:
            assert o is p, jax.tree_util.keystr(path)
    # granite's layers are a list of unrolled trees: nothing is stacked
    assert (cast == 0) == (family == "mamba_hybrid")


@pytest.mark.parametrize("tpu", [False, True])
def test_scheduler_builds_operands_once_per_params(tpu, monkeypatch):
    # on a CPU the copy is made only where the platform check is patched
    monkeypatch.setattr(engine, "_on_tpu", lambda p: tpu)
    cfg, model, params = _tiny("qwen1.5-0.5b")
    sched = PagedContinuousScheduler(
        model, slots=2, max_prompt=14, max_total=20, page_size=4,
        prefill_chunk=8, temperature=0.0)
    arrivals = _trace(cfg, np.random.default_rng(3), 4)
    assert run_trace(sched, params, arrivals).requests_done == 4
    assert sched.stats.decode_steps > 4
    with monkeypatch.context() as m:
        m.setattr(engine, "_on_tpu", lambda p: True)
        held = engine.serve_operands(cfg, params)
    nbytes = sum(o.nbytes for o, p in zip(jax.tree.leaves(held),
                                          jax.tree.leaves(params))
                 if o is not p)
    assert nbytes > 0
    c = sched.counters()
    assert (c["operand_casts"], c["operand_bytes"]) == \
        ((1, nbytes) if tpu else (0, 0))
    new = jax.tree.map(jnp.copy, params)          # new params arrays
    sched.submit(Request(rid=9, prompt=np.arange(1, 6, dtype=np.int32),
                         max_new=3))
    run_trace(sched, new, [])
    assert sched.counters()["operand_casts"] == (2 if tpu else 0)
    assert sched.stats.requests_done == 5
    # nothing here keeps the caller's params alive: freeing them frees
    # the copy made from them
    probe = weakref.ref(new["layers"]["attn"]["wq"])
    del new
    assert probe() is None and not sched._held


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_forced_operands_serve_like_bf16_rounded_weights(family,
                                                          monkeypatch):
    """On a CPU an f32 dot is f32, so the operand tree must serve what
    f32 weights rounded to bf16 beforehand serve, bit for bit: on a TPU
    the DEFAULT dot rounds the f32 weights the same way. (Two or more
    slots: XLA's CPU matrix-vector emitter sums a one-row dot in another
    order when the convert is fused into it.)"""
    arch, _, over = FAMILIES[family]
    cfg, model, params = _tiny(arch, **over)
    runs = {}
    for force, p in ((True, params), (False, _bf16_rounded(cfg, params))):
        monkeypatch.setattr(engine, "_on_tpu", lambda _, on=force: on)
        sched = PagedContinuousScheduler(
            model, slots=3, max_prompt=14, max_total=20, page_size=4,
            prefill_chunk=8, temperature=0.0)
        reqs = [r for _, r in _trace(cfg, np.random.default_rng(6), 5)]
        for r in reqs:
            sched.submit(r)
        logits = []
        while sched.outstanding:
            sched.step(p)
            logits.append(np.asarray(sched._last_logits))
        assert sched.stats.requests_done == 5
        assert sched.operand_casts == int(force)
        runs[force] = ([r.out_tokens for r in reqs], logits)
    assert runs[True][0] == runs[False][0]
    assert len(runs[True][1]) == len(runs[False][1]) > 5
    for a, b in zip(runs[True][1], runs[False][1]):
        assert np.array_equal(a, b)
