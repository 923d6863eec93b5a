"""deepseek-v3.2 [moe]: multi-head latent attention, the DSA lightning
indexer, 3 leading dense layers, then 256 routed experts (top-8, grouped
sigmoid routing with a bias) and 1 shared expert.

61L d_model=7168 128H, q latent 1536, kv latent 512 + 64 RoPE key, head
128 nope + 64 rope, v 128; indexer 64 x 128, K=2048; d_ff 18432 (dense),
2048 (expert); vocab 129280; YaRN x40 over 4096
[hf:deepseek-ai/DeepSeek-V3.2 config.json]. The multi-token-prediction
layer is a drafter and is not part of the served model here.
"""
from repro.models.config import DSAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v3.2", family="moe", n_layers=61, d_model=7168,
    n_heads=128, n_kv_heads=128, d_ff=18432, vocab=129280,
    rope_kind="yarn", rope_base=10000.0,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, first_k_dense=3,
    yarn=YaRNConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale_all_dim=1.0),
    moe=MoEConfig(num_experts=256, total_experts=256, first_expert=0,
                  top_k=8, expert_d_ff=2048, shared_d_ff=2048, n_group=8,
                  topk_group=4, routed_scaling=2.5),
    # V3.2 runs the indexer at every length: below K every position is
    # selected, so the gate sits at K
    dsa=DSAConfig(enabled=True, k=2048, indexer_heads=64, indexer_dim=128,
                  min_n=2048),
)

SMOKE = ModelConfig(
    name="deepseek-v3.2-smoke", family="moe", n_layers=3, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=96, vocab=256,
    rope_kind="yarn", rope_base=10000.0,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, first_k_dense=1, yarn=YaRNConfig(),
    moe=MoEConfig(num_experts=16, total_experts=16, first_expert=0, top_k=4,
                  expert_d_ff=24, shared_d_ff=24, n_group=4, topk_group=2,
                  routed_scaling=2.5),
    dsa=DSAConfig(enabled=True, k=16, indexer_heads=4, indexer_dim=16,
                  min_n=16),
    dtype="float32",
)
