"""DeepSeek-V2-Lite: MLA (kv_lora=512, no q-LoRA, YaRN rotary part) + 64
routed experts top-6 by unnormalised softmax gates and 2 shared experts,
first layer dense [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite
config.json]."""
from ..models.config import ModelConfig, YaRN

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab=102400, rope_theta=1e4, norm_eps=1e-6,
    rope_yarn=YaRN(factor=40.0, original_max_position=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    use_mla=True, kv_lora_rank=512, q_lora_rank=0,
    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    n_experts=64, top_k=6, n_shared_experts=2, moe_d_ff=1408,
    norm_topk_prob=False, first_dense_layers=1, dense_d_ff=10944,
)
