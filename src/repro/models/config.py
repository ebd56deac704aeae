"""Model configuration for the assigned architecture fleet.

One `ModelConfig` describes any of the ten assigned LM-family archs:
dense GQA transformers, fine-grained MoE, Mamba2 SSM, Zamba2-style hybrid,
plus stub-frontend audio/VLM backbones.  The config fully determines the
parameter plan, the forward pass, and the sharding layout.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int                 # per-expert FFN width
    n_shared: int = 0             # always-on shared experts (deepseek-moe)
    first_dense: int = 0          # leading dense layers
    first_dense_ff: int = 0       # their FFN width
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True   # renormalise the top-k gates to sum 1
    # expert parallelism: this chip holds routed experts
    # [first_held, first_held + num_experts) of a router over
    # `router_experts` (0: the router's width is num_experts, all held)
    router_experts: int = 0
    first_held: int = 0

    @property
    def n_router(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def is_share(self) -> bool:
        """True when this layer holds only part of the routed experts."""
        return self.n_router != self.num_experts


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (arXiv 2309.00071), as DeepSeek-V2 applies it."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state: int = 128              # S — state dimension per head
    head_dim: int = 64            # P — channels per head
    expand: int = 2               # d_inner = expand * d_model
    n_groups: int = 1             # B/C projection groups
    conv_width: int = 4           # short causal conv
    chunk: int = 256              # SSD chunk length
    attn_every: int = 0           # hybrid: shared attn block every N blocks

    def n_heads(self, d_model: int) -> int:
        return (self.expand * d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int                  # 0 for attention-free archs
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    # multi-head latent attention (DeepSeek-V2): kv_lora_rank > 0 selects it
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[YarnScaling] = None
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    layout: str = "dense"         # dense | moe | ssm | hybrid
    input_mode: str = "tokens"    # tokens | embeddings (stub frontend)
    sub_quadratic: bool = False   # eligible for long_500k
    # plastic adapter (the paper's technique as an LM serving feature)
    plastic_adapter: bool = False
    adapter_neurons: int = 512
    adapter_impl: str = "xla"     # PlasticEngine backend for the adapter
                                  # ("xla" | "pallas" | "pallas-interpret")
    adapter_quant: bool = False   # fixed-point adapter pool: int8 W_fast
                                  # with per-slot scales, int32 membranes/
                                  # traces (EngineParams.quant datapath)
    # int8 KV cache (beyond-paper: halves decode cache reads — the memory
    # roofline term of every decode cell; per-(position, kv-head) scales)
    kv_quant: bool = False
    # numerics
    dtype: str = "bfloat16"       # activations/params storage
    remat: bool = True
    # residual-stream activation sharding between blocks:
    #   "dp" — batch over data only (baseline)
    #   "sp" — batch over data + sequence over model (Megatron-SP analogue;
    #          required for the biggest train cells to fit 16 GiB/chip)
    act_shard: str = "dp"

    @property
    def act_spec(self):
        return (("data", "model", None) if self.act_shard == "sp"
                else ("data", None, None))

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // max(self.n_heads, 1)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes assigned to the LM fleet (one set shared by all ten archs).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """long_500k only for sub-quadratic archs (see DESIGN.md
    §Arch-applicability for the layout x shape/adapter composition table)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("skipped: pure full-attention arch — 524k dense-"
                       "attention KV decode is the quadratic regime the "
                       "shape spec excludes")
    return True, ""
