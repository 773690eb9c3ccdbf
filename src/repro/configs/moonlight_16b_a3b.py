"""moonlight-16b-a3b — latent attention and sigmoid-routed fine-grained MoE.

[hf:moonshotai/Moonlight-16B-A3B, config.json, model_type deepseek_v3]
27 layers, d_model=2048, 16 heads of multi-head latent attention without
query compression (kv_lora_rank 512, qk_nope_head_dim 128,
qk_rope_head_dim 64, v_head_dim 128, rope_theta 50,000, no rope scaling),
rms_norm_eps 1e-5.  Layer 0 has a dense SwiGLU MLP of width 11,264
(first_k_dense_replace 1); layers 1-26 route each token to 6 of 64
experts of width 1,408 by sigmoid scores with a selection-only bias
(noaux_tc, one group), renormalise the 6 gates and scale them by 2.446,
and add 2 shared experts (one SwiGLU of width 2 x 1,408).  Vocabulary
163,840, untied head, 8,192 positions.  LoRA r=8 on q (W_q) and v (the
value half of kv_b_proj).
"""

from repro.configs.base import LoRAConfig, MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    first_dense_layers=1,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11_264,
    vocab_size=163_840,
    mla=MLAConfig(kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff=1408, scoring="sigmoid",
                  routed_scale=2.446, shared_d_ff=2 * 1408),
    rope_theta=50_000.0,
    norm_eps=1e-5,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
    remat=True,
    microbatches=4,
    max_seq_len=8192,
    lora=LoRAConfig(rank=8, alpha=32.0, dropout=0.1),
    cite="hf:moonshotai/Moonlight-16B-A3B",
)

# the same block at CPU-test size: one dense layer and two MoE layers,
# 8 routed experts (top 3) and one shared expert, a 64-wide latent
SMOKE_CONFIG = CONFIG.with_overrides(
    name="moonlight-smoke", num_layers=3, d_model=128, num_heads=4, num_kv_heads=4,
    d_ff=256, vocab_size=512, max_seq_len=256,
    mla=MLAConfig(kv_rank=64, nope_dim=32, rope_dim=16, v_dim=32),
    moe=MoEConfig(num_experts=8, top_k=3, d_ff=64, scoring="sigmoid",
                  routed_scale=2.446, shared_d_ff=64),
    param_dtype="float32", compute_dtype="float32", remat=False, microbatches=1,
    lora=LoRAConfig(rank=4, alpha=8.0, dropout=0.1),
)
