"""The granite-4.0-h-small doc-chat cell on the host CPU at a small size,
and the readers of its two scope shares (``bench/hybrid_scopes.py``).

The small cell is the committed configuration, traffic and limits of
``granite-4.0-h-small.serve.doc_chat`` with every size shrunk (widths
included; the 10-layer pattern cut to Mamba, Mamba, attention, Mamba)
so that a run takes seconds; ``bench/tests/small.py``'s cells are left
as they are. The model width stays at 1,024: the tied embedding's
logits grow with it, and at 128 a fault in the attention layer reads
under the cell's limit (1.9e-3 to 2.4e-3 against 2.2e-3; at 1,024 5.6e-3
to 6.8e-3).
"""
from __future__ import annotations

import copy
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness, hybrid_scopes, trace
from bench.tests.test_checks import altered_token, drive

CELL = "granite-4.0-h-small.serve.doc_chat"


def doc_chat_cell() -> harness.Cell:
    cell = copy.deepcopy(harness.load_cell(harness.benchmark_spec(), CELL))
    cell.config["model"].update(
        num_layers=4, layer_types=["mamba", "mamba", "attention", "mamba"],
        d_model=1024, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=64,
        vocab_size=512, moe_num_experts=8, moe_experts_held=4, moe_top_k=4,
        moe_shared_d_ff=128, ssm_state_dim=16, ssm_head_dim=32,
        ssm_num_heads=64, ssm_chunk=32)
    cell.traffic["scheduler"].update(slots=4, max_prompt=96, max_total=128,
                                     prefill_chunk=32)
    cell.traffic["clients"].update(clients=4, first_prompt=[32, 72],
                                   reuse=[32, 72], tail=[8, 24],
                                   output=[8, 24])
    return cell


def test_sound_run_is_correct(monkeypatch, capsys):
    line = drive(monkeypatch, capsys, doc_chat_cell())
    assert line["correct"], line["checks"]
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_altered_token_is_caught(monkeypatch, capsys):
    altered_token(monkeypatch)
    line = drive(monkeypatch, capsys, doc_chat_cell())
    assert not line["correct"], line["checks"]


def attention_fault(monkeypatch, fault):
    """Plant a fault in the served path's attention layer: its output
    zeroed, or its K/V pages never written (each call hands back the
    pool it was given)."""
    from repro.models import attention
    from repro.serving import engine
    chunk, dec = engine._chunk_attn_mixer, attention.paged_decode_attention

    def wrap(real):
        def call(*a, **kw):
            y, new = real(*a, **kw)
            if fault == "output_zeroed":
                return y * 0, new
            return y, a[3]
        return call
    monkeypatch.setattr(engine, "_chunk_attn_mixer", wrap(chunk))
    monkeypatch.setattr(attention, "paged_decode_attention", wrap(dec))


@pytest.mark.parametrize("fault", ["output_zeroed", "kv_unwritten"])
def test_attention_fault_is_caught(monkeypatch, capsys, fault):
    """The check sees the one attention layer of the served path (the
    reference's attention init makes a query pick out a few keys)."""
    attention_fault(monkeypatch, fault)
    line = drive(monkeypatch, capsys, doc_chat_cell())
    assert not line["correct"], line["checks"]


# -- the scope shares ---------------------------------------------------------

def _op(name, s, d):
    return NS(name=f"%{name} = f32[4]{{0}} fusion(%a)", start_ns=s,
              duration_ns=d)


def _profile(ops, modules):
    return NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops)])])


# one decode program: two Mamba ops, an attention op, router, experts and
# shared expert ops, and one op outside every scope; a while loop holds
# the experts' op (its own time is what is left of it)
OP_NAMES = {"jit_serve_decode(123)": {
    "fusion.1": "jit(serve_decode)/ssm_mixer/dot_general",
    "fusion.2": "jit(serve_decode)/ssm_mixer/mul",
    "fusion.3": "jit(serve_decode)/attn_mixer/dot_general",
    "fusion.4": "jit(serve_decode)/moe_router/top_k",
    "while.5": "jit(serve_decode)/moe_experts/while",
    "fusion.6": "jit(serve_decode)/moe_experts/dot_general",
    "fusion.7": "jit(serve_decode)/shared_expert/dot_general",
    "fusion.8": "jit(serve_decode)/unembed/dot_general"}}
OPS = [_op("fusion.1", 0, 100), _op("fusion.2", 100, 50),
       _op("fusion.3", 150, 50), _op("fusion.4", 200, 20),
       NS(name="%while.5 = (f32[4]) while(%a)", start_ns=220,
          duration_ns=100),
       _op("fusion.6", 230, 60), _op("fusion.7", 320, 30),
       _op("fusion.8", 350, 50)]


def _reading(ops, names, lo=0.0, hi=400.0):
    pd = _profile(ops, [NS(name="jit_serve_decode(123)", start_ns=0,
                           duration_ns=400)])
    r = NS(trace=trace.from_profile(pd), lo=lo, hi=hi)
    r.hybrid_ops = hybrid_scopes.scoped_ops(pd, names)
    return r


def test_scope_shares_read_self_time_over_busy_time():
    r = _reading(OPS, OP_NAMES)
    # busy 400 ns; Mamba 150; router 20 + experts' loop 40 of its own +
    # the op inside it 60 + shared 30 = 150
    assert hybrid_scopes.ssm_device_share(r) == pytest.approx(37.5)
    assert hybrid_scopes.moe_device_share(r) == pytest.approx(37.5)
    # an op cut by the window counts by the share of it inside
    r = _reading(OPS, OP_NAMES, lo=50.0, hi=400.0)
    assert hybrid_scopes.ssm_device_share(r) == pytest.approx(
        100.0 * 100 / 350)


def test_scope_shares_read_nothing_from_an_unscoped_program():
    """The parent's programs carry none of the scopes: no reading."""
    names = {"jit_serve_decode(123)": {
        k: "jit(serve_decode)/dot_general" for k in OP_NAMES[
            "jit_serve_decode(123)"]}}
    r = _reading(OPS, names)
    assert hybrid_scopes.ssm_device_share(r) is None
    assert hybrid_scopes.moe_device_share(r) is None
    r = _reading(OPS, {})
    assert hybrid_scopes.moe_device_share(r) is None


def test_flops_count_the_work_asked_of_this_chip():
    """Routed experts count at top-k x held/E passes a token; decode
    attention bytes count the attention layers only."""
    from bench.flops import granite_hybrid as fl
    m = harness.load_cell(harness.benchmark_spec(), CELL).config["model"]
    d, f = m["d_model"], m["d_ff"]
    assert fl.ffn_flops(m) == pytest.approx(
        2 * d * 72 + 6 * d * f * 10 * 9 / 72 + 6 * d * 1536)
    assert fl.decode_attention_bytes(m, 99) == pytest.approx(
        2 * 100 * 8 * 128 * 4 + 2 * 32 * 128 * 4)
    # one token at position 0: a decode step of context 1 and a prefill
    # of one token do the same work
    assert fl.decode_flops_per_token(m, 1) == pytest.approx(
        fl.prefill_chunk_flops(m, 0, 1) - 9 * (
            fl._ssd(m, 1) - 5.0 * 128 * 128 * 64))
    assert np.isfinite(fl.prefill_chunk_flops(m, 0, 6656))
