"""Operations and bytes of a Granite 4.0-H stack (``mamba_hybrid``:
Mamba-2 and NoPE grouped-query attention mixers in ``layer_types``
order, each followed by routed SwiGLU experts and a shared SwiGLU
expert), computed from its configuration's shapes. One multiply-add
counts two operations.

Counted: every matmul (the Mamba in and out projections and
convolutions; q, k, v and output projections; the router; the shared
expert; the unembedding of the positions whose logits the step
produces), the SSD mixer as the chunked algorithm needs it (as in
``bench/flops/mamba2``), and attention over the live context (``QK``
and ``PV`` for every earlier position: full causal attention, no
window). The routed experts count at the work a token asks of this
chip: ``moe_top_k`` selected experts, of which a share of
``moe_experts_held / moe_num_experts`` is held here, so top-10 x 9/72 =
1.25 expert passes a token, not the 9 held experts the program runs on
every token. Norms, gates, softmax and elementwise work are left out.
"""
from __future__ import annotations


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def _types(cfg: dict) -> list:
    return list(cfg["layer_types"])


def mamba_matmul_flops(cfg: dict) -> float:
    d = cfg["d_model"]
    d_in = cfg["ssm_expand"] * d
    H, S, K = cfg["ssm_num_heads"], cfg["ssm_state_dim"], \
        cfg["ssm_conv_width"]
    return 2.0 * d * (2 * d_in + 2 * S + H) + 2.0 * d_in * d \
        + 2.0 * K * (d_in + 2 * S)


def attention_matmul_flops(cfg: dict) -> float:
    d, H, K = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // H
    return 2.0 * d * (H * hd + 2 * K * hd) + 2.0 * H * hd * d


def ffn_flops(cfg: dict) -> float:
    """Router, the routed experts' share asked of this chip, shared."""
    d, f, E = cfg["d_model"], cfg["d_ff"], cfg["moe_num_experts"]
    held = cfg.get("moe_experts_held") or E
    routed = cfg["moe_top_k"] * held / E
    return 2.0 * d * E + 6.0 * d * f * routed \
        + 6.0 * d * cfg["moe_shared_d_ff"]


def _ssd(cfg: dict, Q: int) -> float:
    """SSD operations per token with chunks of Q tokens."""
    H, P, S = cfg["ssm_num_heads"], cfg["ssm_head_dim"], \
        cfg["ssm_state_dim"]
    return (Q + 1) * (S + H * P) + 4.0 * S * H * P


def _attn_per_key(cfg: dict) -> float:
    H = cfg["num_heads"]
    return 4.0 * H * (cfg.get("head_dim") or cfg["d_model"] // H)


def decode_flops_per_token(cfg: dict, context: int) -> float:
    """One decode step of one sequence whose new token sits at position
    ``context - 1``: the recurrent state update and read (decay, outer
    product, add, ``C.h``) in each Mamba layer, attention over
    ``context`` keys in each attention layer."""
    H, P, S = cfg["ssm_num_heads"], cfg["ssm_head_dim"], \
        cfg["ssm_state_dim"]
    n = 0.0
    for t in _types(cfg):
        if t == "mamba":
            n += mamba_matmul_flops(cfg) + 5.0 * S * H * P
        else:
            n += attention_matmul_flops(cfg) + _attn_per_key(cfg) * context
        n += ffn_flops(cfg)
    return n + 2.0 * cfg["d_model"] * padded_vocab(cfg)


def prefill_chunk_flops(cfg: dict, start: int, valid: int) -> float:
    """A prefill of ``valid`` tokens at positions start.. (logits of its
    last token only)."""
    Q = min(cfg["ssm_chunk"], max(valid, 1))
    keys = valid * start + valid * (valid + 1) / 2
    n = 0.0
    for t in _types(cfg):
        if t == "mamba":
            n += valid * (mamba_matmul_flops(cfg) + _ssd(cfg, Q))
        else:
            n += valid * attention_matmul_flops(cfg) \
                + _attn_per_key(cfg) * keys
        n += valid * ffn_flops(cfg)
    return n + 2.0 * cfg["d_model"] * padded_vocab(cfg)


def decode_attention_bytes(cfg: dict, pos: int, cache_bytes: int = 4) -> float:
    """Bytes one decode step of one sequence must move through paged
    attention, over the attention layers only: the live K and V
    (positions 0..pos), the query and the output."""
    H, K = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or cfg["d_model"] // H
    n_attn = sum(t == "attention" for t in _types(cfg))
    kv = 2.0 * (pos + 1) * K * hd * cache_bytes
    return n_attn * (kv + 2.0 * H * hd * 4)
