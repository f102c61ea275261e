"""moonlight-16b-a3b [moe]: DeepSeek-V3 layers at 16B total / 3B active.
Multi-head latent attention (16 heads; no query LoRA; a 512-wide latent
KV row plus one 64-wide RoPE key shared by all heads), layer 0 a dense
SwiGLU MLP of 11264, layers 1-26 MoE: 64 routed experts of 1408, top 6 by
sigmoid score plus a selection-only bias, weights renormalised and scaled
by 2.446, and 2 shared experts (one GLU of 2 x 1408).
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2412.19437, 2405.04434]"""

from repro.configs.base import AttnConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    d_ff=11264,                    # the dense layer 0
    vocab_size=163840,
    attn=AttnConfig(num_heads=16, num_kv_heads=16, head_dim=128,
                    kv_lora_rank=512, qk_rope_head_dim=64, v_head_dim=128,
                    rope_theta=50_000.0),
    moe=MoEConfig(num_experts=64, top_k=6, expert_ff=1408,
                  shared_expert_ff=2 * 1408, interleave_step=1,
                  capacity_factor=1.25, parallelism="ep", scoring="sigmoid",
                  routed_scaling=2.446),
    first_k_dense=1,
    norm_eps=1e-5,
    sharding="fsdp",
)
