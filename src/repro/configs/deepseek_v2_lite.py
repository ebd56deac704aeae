"""deepseek-v2-lite [moe, latent attention] — 27L d_model=2048 16H, MLA
(kv_lora_rank 512, no q compression; per head q/k 128 nope + 64 rope, v
128) under YaRN (factor 40 over 4096); layer 0 dense (d_ff 10944), then
64 routed experts of 1408 top-6 (softmax, greedy, no renormalisation) + 2
shared; vocab 102400, untied head, eps 1e-6.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]

`held_share(cfg, first, count)` is the config one chip of an expert-
parallel group serves: `count` routed experts from `first`, of the
router's published width."""
import dataclasses

from repro.models.config import ModelConfig, MoEConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab=102400,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
    layout="moe",
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  first_dense=1, first_dense_ff=10944,
                  norm_topk_prob=False),
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=128, vocab=512,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16,
    rope_theta=10_000.0,
    rope_scaling=YarnScaling(factor=40.0, original_max_position=16,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
    layout="moe", remat=False,
    moe=MoEConfig(num_experts=16, top_k=4, d_expert=32, n_shared=2,
                  first_dense=1, first_dense_ff=128,
                  norm_topk_prob=False),
)


def held_share(cfg: ModelConfig, first: int, count: int) -> ModelConfig:
    """`cfg` as one chip of an expert-parallel group holds it: routed
    experts [first, first + count) of the router's published width."""
    return cfg.with_(moe=dataclasses.replace(
        cfg.moe, num_experts=count, first_held=first,
        router_experts=cfg.moe.n_router))
