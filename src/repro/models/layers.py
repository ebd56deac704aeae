"""Common layers + the parameter-plan machinery.

A model is described by a PLAN: a pytree whose leaves are `ParamDesc`
(shape, dtype, init, logical sharding spec).  From one plan we derive:

  * init_from_plan(plan, key)        — real parameters (CPU smoke tests)
  * abstract_from_plan(plan)         — ShapeDtypeStructs (dry-run lowering)
  * shardings_from_plan(plan, mesh)  — NamedShardings (pjit in_shardings)

keeping init / abstract / sharding structurally identical by construction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import sharding as shd


@dataclasses.dataclass(frozen=True)
class ParamDesc:
    shape: Tuple[int, ...]
    spec: Tuple[Any, ...]              # logical axes, len == ndim
    init: str = "normal"               # normal | zeros | ones | full
                                       # ("full" fills with `scale` — e.g.
                                       # the int8 adapter's default w_scale)
    scale: float = 1.0                 # stddev multiplier (normal)
    fan_in: Optional[int] = None       # normal: std = scale / sqrt(fan_in)
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.spec), (self.shape, self.spec)


def _is_desc(x):
    return isinstance(x, ParamDesc)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _scaled_normal(key, shape, std, dtype):
    # one program per (shape, std, dtype): the float32 draw is fused into
    # the cast, so a multi-GiB stacked-layer leaf never exists in float32
    return (std * jax.random.normal(key, shape)).astype(dtype)


def init_from_plan(plan, key: jax.Array):
    leaves, treedef = jax.tree.flatten(plan, is_leaf=_is_desc)
    keys = jax.random.split(key, len(leaves))

    def mk(desc: ParamDesc, k):
        dt = jnp.dtype(desc.dtype)
        if desc.init == "zeros":
            return jnp.zeros(desc.shape, dt)
        if desc.init == "ones":
            return jnp.ones(desc.shape, dt)
        if desc.init == "full":
            return jnp.full(desc.shape, desc.scale, dt)
        fan = desc.fan_in if desc.fan_in else (desc.shape[-2] if len(desc.shape) >= 2 else desc.shape[-1])
        std = desc.scale / (fan ** 0.5)
        return _scaled_normal(k, tuple(desc.shape), std, dt)

    return jax.tree.unflatten(treedef, [mk(d, k) for d, k in zip(leaves, keys)])


def abstract_from_plan(plan, mesh=None):
    def mk(desc: ParamDesc):
        sh = (shd.named_sharding(mesh, desc.spec, desc.shape)
              if mesh is not None else None)
        return jax.ShapeDtypeStruct(desc.shape, jnp.dtype(desc.dtype), sharding=sh)
    return jax.tree.map(mk, plan, is_leaf=_is_desc)


def shardings_from_plan(plan, mesh):
    return jax.tree.map(
        lambda d: shd.named_sharding(mesh, d.spec, d.shape), plan,
        is_leaf=_is_desc)


def specs_from_plan(plan, mesh):
    return jax.tree.map(
        lambda d: shd.logical_to_physical(mesh, d.spec, d.shape), plan,
        is_leaf=_is_desc)


def param_count(plan) -> int:
    leaves = jax.tree.leaves(plan, is_leaf=_is_desc)
    n = 0
    for d in leaves:
        c = 1
        for s in d.shape:
            c *= s
        n += c
    return n


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return out.astype(x.dtype)


def rope(q, k, positions, theta: float):
    """Rotary embeddings.  q/k (..., S, H, D); positions (..., S)."""
    d = q.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
        return jnp.concatenate(
            [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1).astype(x.dtype)

    return rot(q), rot(k)


def cross_entropy(logits, labels, mask=None):
    """Mean token NLL in fp32.  logits (..., V); labels (...) int32.

    Written as reductions over the vocab axis (logsumexp + one-hot
    contraction) rather than a gather, so a model-sharded vocab dim stays
    sharded under SPMD — the picked-logit term lowers to a partial einsum +
    all-reduce instead of an all-gather of the full logit tensor.
    """
    lf = logits.astype(jnp.float32)
    v = lf.shape[-1]
    lmax = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - lmax), axis=-1)) + lmax[..., 0]
    onehot = jax.nn.one_hot(labels, v, dtype=lf.dtype)
    picked = jnp.einsum("...v,...v->...", lf, onehot)
    nll = lse - picked
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: down( silu(x@gate) * (x@up) )."""
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, w_gate))
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", g * u, w_down)
