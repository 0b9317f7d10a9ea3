"""OLMoE-1B-7B — 64-expert top-8 MoE [arXiv:2409.02060; hf].

Registered with the repo's defaults for three of the published model's
flags: no QK-norm (``qk_norm`` False, published True), top-8 gates rescaled
to sum to 1 (``moe_norm_topk_prob`` True, published False), and the
capacity-buffer dispatch (``moe_dispatch`` "banked", published dropless);
``norm_eps`` is the repo's 1e-6 (published 1e-5).  The benchmark's
``chipbench/configs/olmoe-1b-7b-0924.json`` sets all four by override
(``moe_dispatch`` "grouped"), together with its depth.
"""
from repro.models.config import ModelConfig, register

CONFIG = register(ModelConfig(
    name="olmoe-1b-7b", family="moe",
    num_layers=16, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=1024, vocab_size=50304, head_dim=128,
    num_experts=64, experts_per_token=8,
    rope_theta=1e4, act="silu",
))
