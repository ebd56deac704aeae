"""Config-driven model factory: one uniform surface over every layout.

`build(arch_or_cfg)` turns any `ModelConfig` in `repro.configs` — dense GQA
(qwen*, internlm2, musicgen, pixtral), fine-grained MoE (deepseek-moe,
grok-1), Mamba2 SSM (mamba2), Zamba2 hybrid — into a `Model` bundle whose
entry points (`init` / `forward` / `loss_fn` / `prefill` / `decode_step` /
`decode_rollout` / cache builders) are what `launch/steps.py`,
`launch/serve.py`, and `serving.lm.LMScheduler` consume.  Callers never
import `models.transformer` directly: a config that the factory cannot
lower fails `tests/test_factory.py` at tier-1 instead of failing at serve
time.

The factory also owns the SERVING-POOL plumbing the `SessionPool`
machinery needs (`serving/scheduler.py`): which axis of each decode-cache
leaf carries the slot rows (`cache_axes` — inferred structurally, so a new
segment layout cannot silently desynchronize the scheduler's gather/
scatter), a pooled cache with per-slot sequence indices (`pool_cache`),
and the B=1-prefill -> session-row conversion (`session_from_prefill`)
that makes "admit a freshly prefilled stream" one traced-slot scatter.

Layout x adapter applicability is documented in DESIGN.md
§Arch-applicability.
"""
from __future__ import annotations

from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config, get_smoke
from repro.core.engine import IMPLS
from repro.models import transformer as T
from repro.models.config import ModelConfig

_LAYOUTS = ("dense", "moe", "ssm", "hybrid")


def _validate(cfg: ModelConfig) -> None:
    if not isinstance(cfg, ModelConfig):
        raise TypeError(
            f"factory.build needs a ModelConfig (an LM backbone); got "
            f"{type(cfg).__name__}.  The 'firefly-snn' arch is the paper's "
            "SNN controller (core.snn.SNNConfig) — it is served through "
            "serving.FleetScheduler, not the LM decode path.")
    if cfg.layout not in _LAYOUTS:
        raise ValueError(f"unknown layout {cfg.layout!r}; expected one of "
                         f"{_LAYOUTS}")
    if cfg.layout == "moe" and cfg.moe is None:
        raise ValueError(f"{cfg.name}: layout 'moe' needs cfg.moe")
    if cfg.layout in ("ssm", "hybrid") and cfg.ssm is None:
        raise ValueError(f"{cfg.name}: layout {cfg.layout!r} needs cfg.ssm")
    if cfg.plastic_adapter:
        if cfg.adapter_impl not in IMPLS:
            raise ValueError(
                f"{cfg.name}: adapter_impl must be one of {IMPLS}, got "
                f"{cfg.adapter_impl!r}")
        if cfg.adapter_neurons < 1:
            raise ValueError(f"{cfg.name}: plastic_adapter needs "
                             f"adapter_neurons >= 1")


def _infer_axes(cfg: ModelConfig, max_len: int):
    """Per-leaf slot axis of the pooled decode cache, found structurally:
    the one axis whose extent tracks the batch argument.  Survives any
    segment layout (zsuper's stacked inner SSM caches put the slot axis at
    position 2) without hand-maintained tables."""
    import numpy as np
    a = T.cache_plan(cfg, 2, max_len, per_slot_index=True)
    b = T.cache_plan(cfg, 3, max_len, per_slot_index=True)

    def one(da, db):
        diff = [i for i, (x, y) in enumerate(zip(da.shape, db.shape))
                if x != y]
        if len(diff) != 1:
            raise ValueError(
                f"cannot infer the slot axis of cache leaf {da.shape} vs "
                f"{db.shape}: expected exactly one batch-tracking axis, "
                f"found {diff}")
        return diff[0]

    is_desc = lambda x: hasattr(x, "shape") and hasattr(x, "spec")
    return jax.tree.map(one, a, b, is_leaf=is_desc)


class Model:
    """A `ModelConfig` bound to every entry point the stack consumes.

    Thin by design: each method forwards to `models.transformer` (which
    already dispatches per segment kind), so the factory adds validation
    and the serving-pool plumbing, not a parallel implementation.
    """

    def __init__(self, cfg: ModelConfig):
        _validate(cfg)
        self.cfg = cfg

    # ---- parameters ------------------------------------------------------

    def plan(self, fsdp: bool = True):
        return T.plan(self.cfg, fsdp)

    def init(self, key: jax.Array, fsdp: bool = True):
        return T.init(self.cfg, key, fsdp)

    def abstract(self, mesh=None, fsdp: bool = True):
        return T.abstract(self.cfg, mesh, fsdp)

    def shardings(self, mesh, fsdp: bool = True):
        return T.shardings(self.cfg, mesh, fsdp)

    def n_params(self) -> int:
        return T.n_params(self.cfg)

    # ---- train / eval ----------------------------------------------------

    def forward(self, params, inputs, **kw):
        return T.forward(params, inputs, self.cfg, **kw)

    def loss_fn(self, params, batch, **kw):
        return T.loss_fn(params, batch, self.cfg, **kw)

    # ---- serving ---------------------------------------------------------

    def prefill(self, params, inputs, max_len: int, **kw):
        return T.prefill(params, inputs, self.cfg, max_len, **kw)

    def decode_step(self, params, cache, tokens, active=None,
                    slot_mesh=None):
        return T.decode_step(params, cache, tokens, self.cfg, active=active,
                             slot_mesh=slot_mesh)

    def decode_rollout(self, params, cache, tokens, active=None,
                       slot_mesh=None):
        return T.decode_rollout(params, cache, tokens, self.cfg,
                                active=active, slot_mesh=slot_mesh)

    def cache_plan(self, batch: int, max_len: int,
                   per_slot_index: bool = False):
        return T.cache_plan(self.cfg, batch, max_len, per_slot_index)

    def init_cache(self, batch: int, max_len: int,
                   per_slot_index: bool = False):
        return T.init_cache(self.cfg, batch, max_len, per_slot_index)

    # ---- serving-pool plumbing (SessionPool contract) --------------------

    def pool_cache(self, slots: int, max_len: int):
        """Zeroed pooled decode cache: per-slot ``(B,)`` sequence indices,
        one session row per slot in every leaf."""
        return T.init_cache(self.cfg, slots, max_len, per_slot_index=True)

    def cache_axes(self, max_len: int):
        """Slot-axes pytree for `pool_cache` (see `serving.scheduler`)."""
        return _infer_axes(self.cfg, max_len)

    def session_from_prefill(self, cache1):
        """Squeeze a B=1 prefill cache into one session row (the pytree a
        `SessionPool` scatters into a slot and a `SessionStore` persists).
        The prefill's scalar index passes through as the session's
        position."""
        axes = self.cache_axes(self._max_len_of(cache1))

        def one(leaf, ax):
            leaf = jnp.asarray(leaf)
            if leaf.ndim > ax and leaf.shape[ax] == 1:
                return jnp.squeeze(leaf, ax)
            if leaf.ndim == 0:      # the scalar prefill index
                return leaf
            raise ValueError(
                f"session_from_prefill needs a batch=1 cache; got a leaf "
                f"of shape {leaf.shape} with slot axis {ax}")

        return jax.tree.map(one, cache1, axes)

    def session_template(self, max_len: int):
        """Abstract one-session pytree (ShapeDtypeStructs): the
        `SessionStore` validation template for this pool layout."""
        pool = jax.eval_shape(
            lambda: self.pool_cache(2, max_len))

        def one(leaf, ax):
            shape = leaf.shape[:ax] + leaf.shape[ax + 1:]
            return jax.ShapeDtypeStruct(shape, leaf.dtype)

        return jax.tree.map(one, pool, self.cache_axes(max_len))

    @staticmethod
    def _max_len_of(cache) -> int:
        # any attention/ssm layout keeps max_len discoverable from the
        # index-free leaves only through construction args; callers that
        # built the cache know it — this helper just needs A consistent
        # value for axis inference, which does not depend on max_len.
        return 8


def build(arch_or_cfg: Union[str, ModelConfig], smoke: bool = False,
          **overrides) -> Model:
    """Resolve an arch id (or pass a ModelConfig through), apply overrides,
    validate, and return the bound `Model` bundle.

    ``smoke=True`` resolves the reduced same-family config (CPU tests).
    ``overrides`` are `ModelConfig.with_` fields (e.g.
    ``plastic_adapter=True, adapter_impl="pallas-interpret"``).
    """
    if isinstance(arch_or_cfg, str):
        if arch_or_cfg not in ARCHS:
            raise KeyError(f"unknown arch {arch_or_cfg!r}; choose from "
                           f"{ARCHS}")
        cfg = (get_smoke(arch_or_cfg) if smoke else get_config(arch_or_cfg))
    else:
        cfg = arch_or_cfg
    if not isinstance(cfg, ModelConfig):
        _validate(cfg)  # raises the informative TypeError (firefly-snn)
    if overrides:
        cfg = cfg.with_(**overrides)
    return Model(cfg)
