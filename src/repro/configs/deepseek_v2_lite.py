"""DeepSeek-V2-Lite: MLA without q LoRA, one leading dense SwiGLU layer,
then 26 MoE layers of 64 routed experts (top-6, softmax) plus 2 shared.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]
27L d_model=2048 16H (kv=16) d_ff=10944 (dense) d_ff_expert=1408
vocab=102400, untied.  The zoo's model plane renormalises the top-k gate
values, which the source does not (``norm_topk_prob: false``); parameter
counts and the extracted workload do not depend on it."""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=10944, vocab=102400, d_head=192,
    norm_eps=1e-6,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                  first_k_dense=1),
)

def reduced() -> ArchConfig:
    return ArchConfig(
        name="deepseek-v2-lite-reduced", family="moe", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=128, vocab=256, d_head=24,
        mla=MLAConfig(kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, n_shared=2,
                      first_k_dense=1),
    )
