"""DeepSeek-V2-Lite 16B [arXiv:2405.04434] — one of the paper's own
evaluation models (huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
config.json).  27L (first layer dense FFN d_ff=10944), d_model=2048,
16 heads, MLA (kv_lora=512, no q-LoRA, rope_head=64, nope/v head 128),
vocab=102400.  MoE: 64 routed experts top-6 + 2 shared, expert
d_ff=1408; softmax then greedy top-6 with ``norm_topk_prob: false`` (the
gates are the softmax probabilities, not renormalised) and
``routed_scaling_factor`` 1.  ``rope_scaling``: YaRN, factor 40 over
``original_max_position_embeddings`` 4096, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 0.707.

Note: the assignment line mentions "160 routed" which is full DeepSeek-V2;
V2-Lite (and the primary "MoE 64e top-6" spec) is 64 routed — we follow the
primary spec and the source paper."""
from repro.models.config import (AttentionConfig, MLAConfig, ModelConfig,
                                 MoEConfig, YarnConfig)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    source="arXiv:2405.04434",
    n_layers=27,
    d_model=2048,
    d_ff=10944,
    vocab=102400,
    attn=AttentionConfig(n_heads=16, n_kv_heads=16,
                         rope_theta=10_000.0,
                         mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0,
                                       qk_nope_head_dim=128,
                                       qk_rope_head_dim=64,
                                       v_head_dim=128),
                         yarn=YarnConfig(factor=40.0,
                                         original_max_position_embeddings=4096,
                                         beta_fast=32.0, beta_slow=1.0,
                                         mscale=0.707, mscale_all_dim=0.707)),
    moe=MoEConfig(n_routed=64, top_k=6, d_expert=1408,
                  n_shared=2, d_shared=2816,
                  router_type="softmax_topk", renormalize=False,
                  first_dense=1),
    norm="rmsnorm",
    act="silu",
    glu=True,
    dtype="bfloat16",
)
