"""moonlight-16b-a3b [moe]: DeepSeek-V3 layers: latent attention (MLA,
kv rank 512, q/k 128 + 64 rope, v 128), one leading dense layer, then 64
routed experts (top-6, sigmoid scores with a selection-only bias, gates
x 2.446, dropless) and 2 shared experts per layer.
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2412.19437 §2.1]

The sequence-wise balance coefficient (alpha) and the bias update rate
(gamma) are not in the published config: both are assumed 0.001
(DeepSeek-V3 §4.2 trains V3 with gamma 0.001 and alpha 0.0001).
"""

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab=163840, max_seq=8192,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    head_dim=192, rope_theta=50000.0, norm_eps=1e-5,
    first_k_dense=1, n_experts=64, top_k=6, moe_d_ff=1408,
    n_shared_experts=2, capacity_factor=0.0,
    routed_scale=2.446, seq_aux_coef=0.001, bias_rate=0.001,
    source="hf:moonshotai/Moonlight-16B-A3B",
)

SMOKE = CONFIG.with_overrides(
    name="moonlight-smoke", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=128, vocab=512, max_seq=128, kv_lora_rank=32,
    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, head_dim=24,
    n_experts=8, experts_here=4, top_k=2, moe_d_ff=32, n_shared_experts=1)
