"""granite-4.0-h-small's kind (``mamba_hybrid``: Mamba-2 and NoPE GQA
mixers in a published order, held routed experts and a shared expert)
against the plain reference ``bench/configs/ref_granite_hybrid``, at a
small size on the CPU in float32, with the reference's seeded weights
handed to the program.

Tolerances. Every comparison is of logits, as a share of the largest
reference logit. Program and reference run the same float32 maths in
another order (the program's SSD chunk scan and paged attention
against the reference's chunked quadratic form and dense blocks), which
agree to about 1e-6 of the logits' scale here; ``TOL`` = 1e-4 leaves
two orders of magnitude for that and is still far below what leaving
out the gated norm, a residual multiplier or the attention scale does
(test (e): 1e-2 and more).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.configs import ref_granite_hybrid as ref
from repro.configs import get_arch
from repro.models import build_model
from repro.models import moe as moem
from repro.serving import (ContinuousScheduler, PagedContinuousScheduler,
                           Request)

TOL = 1e-4
HIGHEST = jax.lax.Precision.HIGHEST


def small_cfg(**over):
    """A 4-layer pattern (Mamba, Mamba, attention, Mamba), 4 of 8
    experts held, top-4, at small widths."""
    cfg = get_arch("granite-4.0-h-small").reduced(
        num_layers=4, d_model=128, d_ff=64, vocab_size=256, num_experts=8)
    return dataclasses.replace(cfg, **over)


def ref_params(cfg, seed=0):
    return ref.init(dataclasses.asdict(cfg), jax.random.PRNGKey(seed))


def ref_logits(cfg, params, tokens):
    """(T,) tokens -> (T, V) reference logits."""
    m = dataclasses.asdict(cfg)
    return np.asarray(ref.logits(params, m, jnp.asarray(tokens)[None]))[0]


def rel_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got) - want))
                 / np.max(np.abs(want)))


def prompts(cfg, lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def serve(cfg, params, reqs, *, slots=3, chunk=16, paged_kernel=False,
          ring=False):
    """Run ``reqs`` [(rid, prompt, max_new)] through the paged scheduler
    (with ``ring``, the ring-cache ``ContinuousScheduler``); returns
    ({rid: out tokens}, {rid: [logits that chose each token]})."""
    model = build_model(cfg)
    if ring:
        sched = ContinuousScheduler(model, slots=slots, max_prompt=64,
                                    max_total=96)
    else:
        sched = PagedContinuousScheduler(
            model, slots=slots, max_prompt=64, max_total=96, page_size=8,
            prefill_chunk=chunk, chunks_per_tick=1,
            paged_kernel=paged_kernel)
    seen: dict = {}
    real_emit = sched._emit

    def emit(tok_np):
        lg = np.array(sched._last_logits)[:, 0]
        for i, r in enumerate(sched.active):
            if r is not None and not r.done and sched._slot_ready(i):
                seen.setdefault(r.rid, []).append(lg[i])
        return real_emit(tok_np)

    sched._emit = emit
    out = {}
    for rid, prompt, max_new in reqs:
        r = Request(rid=rid, prompt=prompt, max_new=max_new)
        out[rid] = r
        sched.submit(r)
    sched.run(params, max_steps=500)
    assert all(r.done for r in out.values())
    return ({k: list(r.out_tokens) for k, r in out.items()},
            {k: np.stack(v) for k, v in seen.items()})


# (a) ----------------------------------------------------------------------

@pytest.mark.parametrize("paged_kernel", [False, True])
def test_paged_serving_matches_reference(paged_kernel):
    """Chunked prefill (16-token chunks, prompts of 21-50 tokens, so SSM
    state and pages carry across chunks) then decode, three slots for
    five requests: slots prefill while others decode. Every served
    token's logits agree with the reference's full forward pass over
    the prompt and the tokens served before it."""
    cfg = small_cfg()
    params = ref_params(cfg)
    ps = prompts(cfg, [37, 21, 50, 29, 44])
    reqs = [(i, p, 5 + i) for i, p in enumerate(ps)]
    toks, seen = serve(cfg, params, reqs, paged_kernel=paged_kernel)
    for rid, prompt, _ in reqs:
        seq = np.concatenate([prompt, toks[rid][:-1]])
        want = ref_logits(cfg, params, seq)[len(prompt) - 1:]
        assert seen[rid].shape == want.shape
        assert rel_err(seen[rid], want) <= TOL, rid


# (b) ----------------------------------------------------------------------

def test_forward_matches_reference():
    cfg = small_cfg()
    params = ref_params(cfg)
    tokens = prompts(cfg, [70])[0]
    got, _ = build_model(cfg).forward(params, {"tokens": tokens[None]},
                                      dtype=jnp.float32)
    assert rel_err(got[0], ref_logits(cfg, params, tokens)) <= TOL


def test_full_config_builds_abstract_params():
    """The unreduced 40-layer configuration, all 72 experts: about 32.2B
    parameters, as published (32B)."""
    cfg = get_arch("granite-4.0-h-small")
    shapes, _ = build_model(cfg).abstract_params()
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 32.0e9 < n < 32.5e9, n
    assert abs(n - cfg.param_count()) < 1e-3 * n
    assert [i for i, t in enumerate(cfg.layer_types)
            if t == "attention"] == [5, 15, 25, 35]


# (c) ----------------------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight experts over two shares of four: what each share's held
    experts give, plus the shared expert counted once, is the uncut
    layer (all eight experts) of the reference."""
    cfg = small_cfg(moe_experts_held=8)
    full = ref_params(cfg)["layers"][0]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 40, cfg.d_model))
    want = np.asarray(ref.ffn(full, dataclasses.asdict(cfg), h[0], HIGHEST))
    got = 0.0
    for i, first in enumerate((0, 4)):
        share = {k: full[k][first:first + 4]
                 for k in ("w_gate", "w_up", "w_down")}
        share["router"] = full["router"]
        if i == 0:
            share["shared"] = full["shared"]
        got = got + moem.apply_held_moe(share, cfg, h, first=first)[0]
    assert rel_err(got, want) <= TOL


# (d) ----------------------------------------------------------------------

def test_held_experts_drop_no_token_under_skewed_routing():
    """Every row the same token: all route to the same experts, which a
    capacity layer would overflow. The held layer gives each row what
    the reference gives one token alone."""
    cfg = small_cfg()
    p = ref_params(cfg)["layers"][0]["moe"]
    row = jax.random.normal(jax.random.PRNGKey(6), (cfg.d_model,))
    h = jnp.broadcast_to(row, (2, 64, cfg.d_model))
    got = np.asarray(moem.apply_held_moe(p, cfg, h))
    one = np.asarray(ref.ffn(p, dataclasses.asdict(cfg), row[None],
                             HIGHEST))[0]
    assert rel_err(got.reshape(-1, cfg.d_model), one[None]) <= TOL


def test_slot_in_mixed_batch_equals_batch_one():
    """The same prompt alone (one slot busy) and beside four others:
    the same tokens, and logits equal to round-off."""
    cfg = small_cfg()
    params = ref_params(cfg)
    ps = prompts(cfg, [33, 45, 18, 27, 40], seed=3)
    alone_t, alone_l = serve(cfg, params, [(0, ps[0], 6)])
    mixed_t, mixed_l = serve(cfg, params,
                             [(i, p, 6) for i, p in enumerate(ps)])
    assert mixed_t[0] == alone_t[0]
    assert rel_err(mixed_l[0], alone_l[0]) <= 1e-6


# (e) ----------------------------------------------------------------------

def _without_gated_norm(cfg, params):
    params = jax.tree.map(lambda x: x, params)
    for lp in params["layers"]:
        lp.get("ssm", {}).pop("inner_norm", None)
    return dataclasses.replace(cfg, ssm_gated_norm=False), params


FAULTS = {
    "gated_norm": _without_gated_norm,
    "residual_multiplier": lambda c, p: (
        dataclasses.replace(c, residual_multiplier=1.0), p),
    "attention_scale": lambda c, p: (
        dataclasses.replace(c, attention_multiplier=0.0), p),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_program_missing_a_part_breaks_the_tolerance(fault):
    cfg = small_cfg()
    params = ref_params(cfg)
    tokens = prompts(cfg, [70])[0]
    want = ref_logits(cfg, params, tokens)
    bad_cfg, bad_params = FAULTS[fault](cfg, params)
    got, _ = build_model(bad_cfg).forward(
        bad_params, {"tokens": tokens[None]}, dtype=jnp.float32)
    assert rel_err(got[0], want) > 100 * TOL


# the ring layout -------------------------------------------------------------

def test_ring_layout_serves_like_the_paged_one():
    """The ring cache (one-shot mixed-length prefill, ContinuousScheduler)
    serves the tokens the paged cache serves, logits to round-off."""
    cfg = small_cfg()
    params = ref_params(cfg)
    reqs = [(i, p, 5) for i, p in enumerate(prompts(cfg, [37, 21, 50, 29],
                                                        seed=4))]
    paged_t, paged_l = serve(cfg, params, reqs)
    ring_t, ring_l = serve(cfg, params, reqs, ring=True)
    assert ring_t == paged_t
    for rid in paged_l:
        assert rel_err(ring_l[rid], paged_l[rid]) <= TOL
