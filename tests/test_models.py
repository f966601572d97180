"""Per-arch smoke tests: REDUCED variant of each assigned architecture,
one forward + one train (SGD) step on CPU; shapes + no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.models import build_model

ALL_ARCHS = sorted(ARCHS)
# recurrentgemma-9b at 5 layers: one (rec, rec, attn) group, then a
# 2-layer recurrent tail stack (the published 38 end the same way)
HYBRID_TAIL = "recurrentgemma-9b@5"


def reduced(name):
    """``name``'s reduced config; ``arch@n`` asks for n layers."""
    arch, _, layers = name.partition("@")
    return get_arch(arch).reduced(**({"num_layers": int(layers)}
                                     if layers else {}))


def make_batch(cfg, B=2, T=32, key=None):
    key = key or jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.kind == "vlm":
        batch["patches"] = jax.random.normal(
            key, (B, cfg.enc_seq_len, cfg.d_model)) * 0.1
    if cfg.kind in ("encdec", "audio"):
        batch["frames"] = jax.random.normal(
            key, (B, cfg.enc_seq_len, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("name", ALL_ARCHS + [HYBRID_TAIL])
def test_arch_smoke_forward_and_train_step(name):
    cfg = reduced(name)
    assert cfg.d_model <= 512
    assert cfg.num_layers <= (5 if name == HYBRID_TAIL else 3)
    if cfg.moe_num_experts:
        assert cfg.moe_num_experts <= 4
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, T = 2, 32
    batch = make_batch(cfg, B, T)

    logits, aux = model.forward(params, batch, dtype=jnp.float32)
    t_text = T
    assert logits.shape == (B, t_text, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())

    # one SGD step decreases nothing catastrophic & produces finite params
    loss, grads = jax.value_and_grad(
        lambda p: model.loss(p, batch, dtype=jnp.float32))(params)
    assert np.isfinite(float(loss))
    new_params = jax.tree.map(lambda w, g: w - 1e-3 * g, params, grads)
    for leaf in jax.tree.leaves(new_params):
        assert bool(jnp.isfinite(leaf).all()), "non-finite params after step"
    loss2 = model.loss(new_params, batch, dtype=jnp.float32)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_arch_abstract_params_match_real(name):
    cfg = get_arch(name).reduced()
    model = build_model(cfg)
    shapes, axes = model.abstract_params()
    params = model.init(jax.random.PRNGKey(0))
    flat_s = jax.tree.leaves(shapes)
    flat_p = jax.tree.leaves(params)
    assert len(flat_s) == len(flat_p)
    for s, p in zip(flat_s, flat_p):
        assert s.shape == p.shape and s.dtype == p.dtype
    # axes tree matches params structure and ranks
    flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    for a, p in zip(flat_a, flat_p):
        assert len(a) == p.ndim, (a, p.shape)


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned hyperparameters."""
    c = get_arch("gemma-2b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (18, 2048, 8, 1, 16384, 256000)
    c = get_arch("llama4-maverick-400b-a17b")
    assert c.moe_num_experts == 128 and c.moe_top_k == 1
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads) == \
        (48, 5120, 40, 8)
    c = get_arch("mamba2-370m")
    assert c.ssm_state_dim == 128 and c.num_heads == 0
    c = get_arch("recurrentgemma-9b")
    assert (c.num_layers, c.d_model) == (38, 4096)
    c = get_arch("whisper-small")
    assert c.enc_seq_len == 1500 and c.cross_attention
    c = get_arch("qwen1.5-0.5b")
    assert c.qkv_bias
    c = get_arch("starcoder2-3b")
    assert c.num_kv_heads == 2 and c.rope
    c = get_arch("granite-3-8b")
    assert (c.num_layers, c.num_heads, c.num_kv_heads) == (40, 32, 8)
    c = get_arch("paligemma-3b")
    assert c.vocab_size == 257_216 and c.enc_seq_len == 256
    c = get_arch("llama4-scout-17b-a16e")
    assert c.moe_num_experts == 16


def test_param_counts_plausible():
    assert abs(get_arch("gemma-2b").param_count() / 1e9 - 2.5) < 0.5
    assert abs(get_arch("granite-3-8b").param_count() / 1e9 - 8.2) < 1.0
    mav = get_arch("llama4-maverick-400b-a17b")
    assert 350e9 < mav.param_count() < 450e9
    assert mav.active_param_count() < 20e9
