"""Operations and bytes of a Mamba-2 (SSD) model, computed from its
configuration's shapes. One multiply-add counts two operations.

Counted: every matmul (in and out projections, the tied unembedding),
the depthwise convolutions, and the SSD mixer as the chunked algorithm
needs it — per token, the causal half of the intra-chunk products
(``C.B`` over the state, then the decayed mix of x over the heads), the
read of the carried state and its update. Elementwise work (norms,
gates, softplus, exp) is left out. Recomputation (rematerialised
activations) never counts.
"""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["d_model"]
    d_in = cfg["ssm_expand"] * d
    return (d, d_in, cfg["ssm_num_heads"], cfg["ssm_head_dim"],
            cfg["ssm_state_dim"], cfg["ssm_conv_width"])


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def layer_matmul_flops(cfg: dict) -> float:
    d, d_in, H, P, S, K = _dims(cfg)
    return 2.0 * d * (2 * d_in + 2 * S + H) + 2.0 * d_in * d \
        + 2.0 * K * (d_in + 2 * S)


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations per token of a sequence of ``seq_len``."""
    d, d_in, H, P, S, K = _dims(cfg)
    Q = min(cfg["ssm_chunk"], seq_len)
    ssd = (Q + 1) * (S + H * P) + 4.0 * S * H * P
    per_layer = layer_matmul_flops(cfg) + ssd
    return cfg["num_layers"] * per_layer + 2.0 * d * padded_vocab(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (twice the forward) per token."""
    return 3.0 * forward_flops_per_token(cfg, seq_len)


def decode_flops_per_token(cfg: dict, context: int = 0) -> float:
    """One recurrent decode step of one sequence: the projections, the
    state update (decay, outer product, add) and the read ``C.h``; the
    context length does not enter."""
    d, d_in, H, P, S, K = _dims(cfg)
    per_layer = layer_matmul_flops(cfg) + 5.0 * S * H * P
    return cfg["num_layers"] * per_layer + 2.0 * d * padded_vocab(cfg)


def prefill_chunk_flops(cfg: dict, start: int, valid: int) -> float:
    """A prefill chunk of ``valid`` tokens, logits of its last token only
    (the carried state enters as in the chunked scan; ``start`` does not
    change the work)."""
    unembed = 2.0 * cfg["d_model"] * padded_vocab(cfg)
    return valid * (forward_flops_per_token(cfg, valid) - unembed) + unembed
