"""deepseek-v2-lite [moe] - multi-head latent attention (no query LoRA),
2 shared + 64 routed top-6 experts, first layer dense
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite].

Lowered by the trace backends only (``repro.workloads``
``deepseek-v2-lite``): there is no JAX model with latent attention, so
it is not in ``ARCH_IDS``.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, kv_heads=16,
    d_ff=10944, vocab=102400,
    moe_experts=64, moe_topk=6, moe_shared_experts=2, moe_d_ff=1408,
    moe_first_dense=1,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
)

SMOKE = ArchConfig(
    name="deepseek-v2-lite-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, kv_heads=4,
    d_ff=192, vocab=256,
    moe_experts=8, moe_topk=2, moe_shared_experts=1, moe_d_ff=32,
    moe_first_dense=1,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, loss_chunk=64,
)
