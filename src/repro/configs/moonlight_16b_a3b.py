"""moonlight-16b-a3b [moe] — DeepSeek-V3 block (model_type deepseek_v3):
multi-head latent attention, 64 sigmoid-routed experts (top-6, noaux_tc
selection bias, routed scaling 2.446) with 2 shared experts, layer 0 dense
[huggingface.co/moonshotai/Moonlight-16B-A3B config.json].  Trains only:
serving waits for MLA's latent KV cache."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe", num_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, head_dim=128, d_ff=11264, vocab_size=163840,
    rope_theta=50000.0, act="swiglu", norm_eps=1e-5,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    n_experts=64, top_k=6, router="sigmoid", routed_scale=2.446,
    d_expert=1408, n_shared_experts=2, first_dense=1, loss_chunk=2048)

SMOKE = ModelConfig(
    name="moonlight-16b-a3b-smoke", family="moe", num_layers=3, d_model=64,
    n_heads=4, n_kv_heads=4, head_dim=16, d_ff=96, vocab_size=256,
    rope_theta=50000.0, act="swiglu", kv_lora_rank=32, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=16, n_experts=16, top_k=4, router="sigmoid",
    routed_scale=2.446, d_expert=32, n_shared_experts=2, experts_held=8,
    first_dense=1, loss_chunk=16, param_dtype="float32", dtype="float32")
