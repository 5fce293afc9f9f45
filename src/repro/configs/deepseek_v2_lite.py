"""DeepSeek-V2-Lite (15.7B, 2.4B active) [hf:deepseek-ai/DeepSeek-V2-Lite]
— multi-head latent attention with YaRN rope, 64 routed experts top-6 and
2 shared, first layer dense.  Every expert is held; a deployment's chip
holds its share through ``experts_held``."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe", num_layers=27, d_model=2048,
    num_heads=16, num_kv_heads=16, d_ff=10944, vocab_size=102400,
    pattern=("moe",), num_experts=64, top_k=6, num_shared_experts=2,
    expert_d_ff=1408, first_dense_layers=1, first_dense_ff=10944,
    norm_topk_prob=False, router_aux="seq", router_aux_coef=0.001,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=10000.0,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    norm_eps=1e-6, act="silu", tie_embeddings=False,
    # at 8k a step fits only if MLA's attention core is not live through
    # the experts' backward pass
    remat="nested",
)
