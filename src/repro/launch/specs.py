"""Abstract input construction for the dry-run: ShapeDtypeStruct stand-ins
with shardings for every (arch x shape) cell, plus the per-arch launch
setup table (microbatches / activation sharding / moment dtype) that makes
the big train cells fit 16 GiB/chip.

Nothing here allocates device memory — everything is eval_shape-grade.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp  # noqa: F401

from repro.distributed import sharding as shd
from repro.models import transformer as T
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.layers import abstract_from_plan, shardings_from_plan
from repro.optim import OptState

# ---------------------------------------------------------------------------
# Per-arch train launch setup.  Derived by napkin math against 16 GiB/chip
# (see EXPERIMENTS.md §Dry-run): saved-residual bytes = L * B_loc/mb * S * D
# * 2 / (model-axis if sp), plus params + grads + Adam moments under 2D
# (fsdp x tensor) sharding.
# ---------------------------------------------------------------------------

#   * "sp" activation sharding (sequence over the model axis between
#     blocks) is used wherever the saved-residual footprint would not fit —
#     it also halves the TP activation collectives (RS+AG vs AR).
#   * microbatches are kept MINIMAL: with fsdp-sharded parameters every
#     extra microbatch pays one more weight-grad reduction per layer
#     (comm ∝ μb), so μb is a memory knob of last resort.
#   * SSM/hybrid archs stay "dp": the SSD chunk scan wants contiguous
#     sequence per device; sharding seq over model would gather per chunk.
TRAIN_SETUP: dict[str, dict] = {
    "qwen2-72b":        dict(microbatches=2, act_shard="sp"),
    "qwen1.5-32b":      dict(microbatches=2, act_shard="sp"),
    "internlm2-20b":    dict(microbatches=2, act_shard="sp"),
    "grok-1-314b":      dict(microbatches=1, act_shard="sp",
                             moment_dtype="bfloat16",
                             accum_dtype="bfloat16"),
    "pixtral-12b":      dict(microbatches=2, act_shard="sp"),
    "qwen3-4b":         dict(microbatches=2),
    "deepseek-moe-16b": dict(microbatches=2),
    "deepseek-v2-lite": dict(microbatches=2),
    "musicgen-medium":  dict(microbatches=2),
    "zamba2-7b":        dict(microbatches=4),
    "mamba2-1.3b":      dict(microbatches=2),
}


def train_setup(arch: str) -> dict:
    return dict(TRAIN_SETUP.get(arch, {}))


def apply_setup(cfg: ModelConfig, setup: dict) -> ModelConfig:
    """Fold launch-level overrides that live on the ModelConfig."""
    if "act_shard" in setup:
        cfg = cfg.with_(act_shard=setup["act_shard"])
    return cfg


# ---------------------------------------------------------------------------
# Abstract values
# ---------------------------------------------------------------------------


def _sds(shape, dtype, spec, mesh):
    sh = shd.named_sharding(mesh, spec, shape) if mesh is not None else None
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sh)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    """Train batch stand-ins: tokens or (stub-frontend) embeddings."""
    b, s = shape.global_batch, shape.seq_len
    if cfg.input_mode == "embeddings":
        inputs = _sds((b, s, cfg.d_model), cfg.dtype,
                      ("data", None, None), mesh)
    else:
        inputs = _sds((b, s), "int32", ("data", None), mesh)
    return {"inputs": inputs,
            "labels": _sds((b, s), "int32", ("data", None), mesh)}


def prefill_specs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    return {"inputs": batch_specs(cfg, shape, mesh)["inputs"]}


def decode_specs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    b = shape.global_batch
    spec = ("data", None) if b > 1 else (None, None)
    return {
        "tokens": _sds((b, 1), "int32", spec, mesh),
        "cache": abstract_from_plan(
            T.cache_plan(cfg, b, shape.seq_len), mesh),
    }


def params_abstract(cfg: ModelConfig, mesh, fsdp: bool = True):
    return abstract_from_plan(T.plan(cfg, fsdp), mesh)


def opt_state_abstract(params_abs, moment_dtype: str = "float32"):
    """OptState stand-in mirroring the parameter tree (ZeRO-sharded)."""
    mdt = jnp.dtype(moment_dtype)

    def like(p):
        return jax.ShapeDtypeStruct(p.shape, mdt, sharding=p.sharding)

    return OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=jax.tree.map(like, params_abs),
        nu=jax.tree.map(like, params_abs),
        master=None)


def input_specs(arch: str, shape_name: str, mesh,
                plastic: bool = False, fsdp: bool = True,
                cfg_overrides: Optional[dict] = None) -> dict:
    """Everything dryrun.py needs to lower one (arch x shape) cell.

    Returns {"kind", "cfg", "setup", "args": tuple of abstract values
    ordered as the step function expects}.  `cfg_overrides` are applied
    BEFORE abstract args are built (e.g. kv_quant changes the cache plan).
    """
    from repro.configs import get_config
    from repro.models.config import SHAPES, shape_applicable

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"kind": "skip", "cfg": cfg, "why": why}
    if plastic:
        cfg = cfg.with_(plastic_adapter=True)
    if cfg_overrides:
        cfg = cfg.with_(**cfg_overrides)

    setup = train_setup(arch) if shape.kind == "train" else {}
    if shape.kind == "train":
        cfg = apply_setup(cfg, setup)
    else:
        # Serving: parameters replicate over the data axis (pure tensor
        # parallel).  ZeRO-sharded serving params would re-gather every
        # layer every token — §Perf decode hillclimb measured 31x lower
        # collective wire by switching this off.  Baselines with fsdp=True
        # are snapshotted in roofline_*_baseline.json.
        fsdp = False

    p_abs = params_abstract(cfg, mesh, fsdp)
    if shape.kind == "train":
        o_abs = opt_state_abstract(
            p_abs, setup.get("moment_dtype", "float32"))
        args = (p_abs, o_abs, batch_specs(cfg, shape, mesh))
    elif shape.kind == "prefill":
        args = (p_abs, prefill_specs(cfg, shape, mesh)["inputs"])
    else:  # decode
        d = decode_specs(cfg, shape, mesh)
        args = (p_abs, d["cache"], d["tokens"])
    return {"kind": shape.kind, "cfg": cfg, "setup": setup,
            "shape": shape, "args": args}
