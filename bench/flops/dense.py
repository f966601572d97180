"""Operations and bytes of a dense decoder (grouped-query attention and
a two-matrix MLP), computed from its configuration's shapes. One
multiply-add counts two operations.

Counted: the q, k, v and output projections, the MLP, the unembedding
of the positions whose logits the step produces, and attention over the
live context only (``QK`` and ``PV`` for every position a query may see:
causal, inside the window). Norms, rotary and softmax are left out.
"""
from __future__ import annotations


def _dims(cfg: dict):
    d, H, K = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // H
    return d, H, K, hd, cfg["d_ff"], cfg["num_layers"]


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def layer_matmul_flops(cfg: dict) -> float:
    d, H, K, hd, f, _ = _dims(cfg)
    return 2.0 * d * (H * hd + 2 * K * hd) + 2.0 * H * hd * d + 4.0 * d * f


def visible(cfg: dict, pos: int) -> int:
    """Keys a query at position ``pos`` attends to."""
    w = cfg.get("sliding_window") or 0
    return min(pos + 1, w) if w else pos + 1


def decode_flops_per_token(cfg: dict, context: int) -> float:
    """One decode step of one sequence whose new token sits at position
    ``context - 1``."""
    d, H, K, hd, f, L = _dims(cfg)
    attn = 4.0 * H * hd * visible(cfg, context - 1)
    return L * (layer_matmul_flops(cfg) + attn) + 2.0 * d * padded_vocab(cfg)


def prefill_chunk_flops(cfg: dict, start: int, valid: int) -> float:
    """A prefill chunk of ``valid`` tokens at positions start.. (logits
    of its last token only)."""
    d, H, K, hd, f, L = _dims(cfg)
    keys = sum(visible(cfg, p) for p in range(start, start + valid))
    return L * (valid * layer_matmul_flops(cfg) + 4.0 * H * hd * keys) \
        + 2.0 * d * padded_vocab(cfg)


def decode_attention_bytes(cfg: dict, pos: int, cache_bytes: int = 4) -> float:
    """Bytes one decode step of one sequence must move through paged
    attention, over all layers: the live K and V (positions a query at
    ``pos`` sees), the query and the output."""
    d, H, K, hd, f, L = _dims(cfg)
    kv = 2.0 * visible(cfg, pos) * K * hd * cache_bytes
    return L * (kv + 2.0 * H * hd * 4)
