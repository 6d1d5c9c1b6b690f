"""moonlight-16b-a3b [moe] — DeepSeek-V3 block: latent attention, 64 routed
experts top-6 + 2 shared [hf:moonshotai/Moonlight-16B-A3B].

27L d_model=2048 16H, MLA with no query low-rank path (kv_lora_rank=512,
qk 128 nope + 64 rope, v 128), one leading dense SwiGLU layer of 11264, then
expert layers of 64 SwiGLU experts of 1408 (top-6, sigmoid scores with a
selection-only correction bias, normalised top-k weights × 2.446) and 2
shared experts; untied vocab 163840, rope_theta 50000, RMSNorm eps 1e-5, no
embedding multiplier. The sequence-wise balance factor 1e-4 is the
DeepSeek-V3 report's (arXiv:2412.19437 §4.2): the model's config gives none.
"""
from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    source="hf:moonshotai/Moonlight-16B-A3B (DeepSeek-V3 block, arXiv:2412.19437)",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    attention="mla",
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    activation="swiglu",
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    num_shared_experts=2,
    router_score="sigmoid",
    routed_scaling=2.446,
    router_aux_weight=1e-4,
    first_dense_layers=1,
    norm_eps=1e-5,
    tie_embeddings=False,
    embed_scale=1.0,
)
