"""granite-4.0-h-small [mamba_hybrid]: IBM Granite 4.0-H Small (32B-A9B).
[hf:ibm-granite/granite-4.0-h-small]

40 layers in the published ``layer_types`` order: 36 Mamba-2 mixers
(128 heads of 64, d_state 128, one B/C group, conv 4 with bias, chunk
256, gated RMSNorm before out_proj) and 4 NoPE GQA attention mixers (32
query / 8 KV heads of 128, softmax scale 1/128) at layers 5, 15, 25 and
35. After every mixer an FFN of 72 routed SwiGLU experts of width 768,
top-10 with a softmax over the selected logits, plus one shared SwiGLU
expert of width 1536. Embeddings x12, residual branches x0.22, logits
/16; RMSNorm eps 1e-5; vocabulary 100,352, tied.
"""
from repro.configs.base import ModelConfig

LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    kind="mamba_hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,                 # one routed expert's width
    vocab_size=100_352,
    mlp_variant="swiglu",
    rope=False,               # position_embedding_type: nope
    norm="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=True,
    layer_types=LAYER_TYPES,
    moe_num_experts=72,
    moe_top_k=10,
    moe_shared_d_ff=1536,
    ssm_state_dim=128,
    ssm_num_heads=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_chunk=256,
    ssm_conv_width=4,
    ssm_conv_bias=True,
    ssm_gated_norm=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    max_seq_len=131_072,
    source="hf:ibm-granite/granite-4.0-h-small",
)
