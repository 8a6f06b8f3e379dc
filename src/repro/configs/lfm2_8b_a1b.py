"""lfm2-8b-a1b [hybrid moe] — 24L d2048: 18 gated short-convolution layers
(kernel 3) and 6 GQA layers (32H, kv=8, head 64, QK-norm) at layers 2, 6,
10, 14, 18, 21; two leading dense SwiGLU FFNs (7168), then 32 routed
SwiGLU experts (1792) top-4 with sigmoid scores, a selection bias and
normalised weights; V=65536, tied head.
[hf:LiquidAI/LFM2-8B-A1B config.json, model_type lfm2_moe]

Short convolution:  [B, C, h] = split3(x W_in);  y = C * conv3(B * h);
out = y W_out, with conv3 causal and depthwise, no bias.
"""
from repro.core.model_config import ModelSpec, MoESpec

_ATTN = (2, 6, 10, 14, 18, 21)

SPEC = ModelSpec(
    name="lfm2-8b-a1b", family="hybrid",
    num_layers=24, d_model=2048, num_heads=32, num_kv_heads=8,
    d_ff=7168, vocab_size=65536, rope_theta=1e6, norm_eps=1e-5,
    qk_norm=True, tie_embeddings=True, max_seq_len=128000,
    layer_types=tuple("attn" if i in _ATTN else "conv" for i in range(24)),
    dense_layers=2, conv_width=3,
    moe=MoESpec(num_experts=32, top_k=4, expert_ff=1792,
                score="sigmoid", expert_bias=True, norm_topk=True,
                scale=1.0),
)
