"""Composable decoder-only LM covering the assigned architecture fleet.

A model is a sequence of SEGMENTS, each a stack of identical blocks scanned
with `lax.scan` (stacked params => small HLO, fast compile, layer-count-
independent program size):

  dense     — GQA attention + SwiGLU MLP           (qwen*, internlm2, musicgen,
                                                    pixtral backbones)
  dense_ff  — dense with an override FFN width      (deepseek-moe layer 0)
  moe       — GQA attention + routed-expert FFN     (deepseek-moe, grok-1)
  ssm       — Mamba2 SSD block                      (mamba2)
  zsuper    — one SHARED transformer block + (attn_every-1) Mamba2 blocks
              (zamba2; the shared block's params live once at top level)

Attention is GQA (`models.attention`) or, where ``cfg.kv_lora_rank`` is
set, multi-head latent attention (`models.mla`, deepseek-v2), whose cache
holds a latent and a rotated key part per position instead of k and v.
Serving routes MoE layers dropless (`moe.apply_held`), and so does every
pass of a config that holds only a share of the routed experts; training
a whole expert set keeps the capacity dispatch (`moe.apply`).

Entry points:
  plan / init / abstract          — parameter plan machinery
  forward                         — full-sequence logits (train/prefill)
  loss_fn                         — next-token cross entropy
  init_cache / prefill / decode_step — serving path with KV/SSM caches
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_constraint
from repro.models import attention, mla, moe as moe_mod, plastic, \
    ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (ParamDesc, abstract_from_plan,
                                 cross_entropy, init_from_plan, param_count,
                                 rms_norm, shardings_from_plan,
                                 specs_from_plan, swiglu)

# ---------------------------------------------------------------------------
# Segments
# ---------------------------------------------------------------------------


def segments(cfg: ModelConfig) -> list[tuple[str, int]]:
    if cfg.layout == "dense":
        return [("dense", cfg.n_layers)]
    if cfg.layout == "moe":
        fd = cfg.moe.first_dense
        segs = []
        if fd:
            segs.append(("dense_ff", fd))
        segs.append(("moe", cfg.n_layers - fd))
        return segs
    if cfg.layout == "ssm":
        return [("ssm", cfg.n_layers)]
    if cfg.layout == "hybrid":
        per = cfg.ssm.attn_every
        n_super = cfg.n_layers // per
        rem = cfg.n_layers - n_super * per
        segs: list[tuple[str, int]] = [("zsuper", n_super)]
        if rem:
            segs.append(("ssm", rem))
        return segs
    raise ValueError(cfg.layout)


def _stack_plan(p, n: int):
    """Prepend a stacking dim to every ParamDesc in a plan."""
    return jax.tree.map(
        lambda d: ParamDesc((n, *d.shape), (None, *d.spec), d.init, d.scale,
                            d.fan_in, d.dtype),
        p, is_leaf=lambda x: isinstance(x, ParamDesc))


def _mlp_plan(cfg: ModelConfig, d_ff: int, stack: int = 0) -> dict:
    d = cfg.d_model

    def desc(shape, spec, **kw):
        if stack:
            shape, spec = (stack, *shape), (None, *spec)
        return ParamDesc(shape, spec, dtype=cfg.dtype, **kw)

    return {
        "norm": desc((d,), (None,), init="ones"),
        "w_gate": desc((d, d_ff), ("data", "model"), fan_in=d),
        "w_up": desc((d, d_ff), ("data", "model"), fan_in=d),
        "w_down": desc((d_ff, d), ("model", "data"), fan_in=d_ff),
    }


def _segment_plan(cfg: ModelConfig, kind: str, count: int):
    attn = mla if cfg.mla else attention
    if kind == "dense":
        return {"attn": attn.plan(cfg, stack=count),
                "mlp": _mlp_plan(cfg, cfg.d_ff, stack=count)}
    if kind == "dense_ff":
        return {"attn": attn.plan(cfg, stack=count),
                "mlp": _mlp_plan(cfg, cfg.moe.first_dense_ff, stack=count)}
    if kind == "moe":
        return {"attn": attn.plan(cfg, stack=count),
                "moe": moe_mod.plan(cfg, stack=count)}
    if kind == "ssm":
        return ssm_mod.plan(cfg, stack=count)
    if kind == "zsuper":
        inner = cfg.ssm.attn_every - 1
        return {"ssm": _stack_plan(ssm_mod.plan(cfg, stack=inner), count)}
    raise ValueError(kind)


def plan(cfg: ModelConfig, fsdp: bool = True) -> dict:
    d, v = cfg.d_model, cfg.vocab
    p: dict[str, Any] = {
        "embed": ParamDesc((v, d), ("model", "data"), scale=1.0, fan_in=d,
                           dtype=cfg.dtype),
        "segments": [_segment_plan(cfg, k, n) for k, n in segments(cfg)],
        "final_norm": ParamDesc((d,), (None,), init="ones", dtype=cfg.dtype),
    }
    if cfg.layout == "hybrid":
        p["shared_attn"] = attention.plan(cfg)
        p["shared_mlp"] = _mlp_plan(cfg, cfg.d_ff)
    if not cfg.tie_embeddings:
        p["lm_head"] = ParamDesc((d, v), ("data", "model"), fan_in=d,
                                 dtype=cfg.dtype)
    if cfg.plastic_adapter:
        p["adapter"] = plastic.plan(cfg)
    if not fsdp:
        p = jax.tree.map(
            lambda pd: ParamDesc(
                pd.shape,
                tuple(None if s == "data" else s for s in pd.spec),
                pd.init, pd.scale, pd.fan_in, pd.dtype),
            p, is_leaf=lambda x: isinstance(x, ParamDesc))
    return p


def init(cfg: ModelConfig, key: jax.Array, fsdp: bool = True):
    return init_from_plan(plan(cfg, fsdp), key)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _mlp_apply(p, x, cfg: ModelConfig):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    return x + shard_constraint(
        swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), cfg.act_spec)


def _block_fn(cfg: ModelConfig, kind: str, *, collect_cache: bool,
              attn_impl: str, ssd_impl: str, shared=None):
    """Returns body(x, p) -> (x, cache_leaf) for one block of `kind`.
    With `collect_cache` (prefill, a serving path) MoE layers route
    dropless."""

    def attn_apply(p, x):
        if cfg.mla:
            return mla.apply(p, x, cfg)
        return attention.apply(p, x, cfg, impl=attn_impl)

    def dense(x, p):
        x, (k, v) = attn_apply(p["attn"], x)
        x = _mlp_apply(p["mlp"], x, cfg)
        return x, ((k, v) if collect_cache else None)

    def moe_block(x, p):
        x, (k, v) = attn_apply(p["attn"], x)
        if collect_cache or cfg.moe.is_share:
            x, _ = moe_mod.apply_held(p["moe"], x, cfg)
        else:
            x = moe_mod.apply(p["moe"], x, cfg)
        return x, ((k, v) if collect_cache else None)

    def ssm_block(x, p):
        x, state, conv = ssm_mod.apply(p, x, cfg, impl=ssd_impl)
        return x, ((state, conv) if collect_cache else None)

    def zsuper(x, p):
        x, (k, v) = attention.apply(shared[0], x, cfg, impl=attn_impl)
        x = _mlp_apply(shared[1], x, cfg)

        def inner(h, pl):
            h, state, conv = ssm_mod.apply(pl, h, cfg, impl=ssd_impl)
            return h, ((state, conv) if collect_cache else None)

        x, inner_cache = jax.lax.scan(inner, x, p["ssm"])
        return x, (((k, v), inner_cache) if collect_cache else None)

    return {"dense": dense, "dense_ff": dense, "moe": moe_block,
            "ssm": ssm_block, "zsuper": zsuper}[kind]


_REMAT_POLICIES = {
    "none": None,   # no remat
    "nothing": "nothing_saveable",
    "dots": "dots_with_no_batch_dims_saveable",
}


def _maybe_remat(fn, cfg: ModelConfig, remat_policy: str):
    if not cfg.remat or remat_policy == "none":
        return fn
    pol = getattr(jax.checkpoint_policies, _REMAT_POLICIES[remat_policy])
    return jax.checkpoint(fn, policy=pol)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(params, inputs, cfg: ModelConfig, *, collect_cache: bool = False,
            attn_impl: str = "xla_flash", ssd_impl: str = "xla",
            remat_policy: str = "nothing", head: bool = True):
    """inputs: tokens (B,S) int32 or embeddings (B,S,D) per cfg.input_mode.

    Returns (logits (B,S,V), per-segment caches or Nones); with head=False
    the first element is the final hidden state (B,S,D) instead (prefill
    uses this to avoid materializing all-position logits).
    """
    if cfg.input_mode == "embeddings" and inputs.ndim == 3:
        h = inputs.astype(cfg.adtype)
    else:
        h = jnp.take(params["embed"], inputs, axis=0)
    h = shard_constraint(h, cfg.act_spec)

    shared = ((params["shared_attn"], params["shared_mlp"])
              if cfg.layout == "hybrid" else None)
    caches = []
    for seg_idx, (kind, count) in enumerate(segments(cfg)):
        blk = _block_fn(cfg, kind, collect_cache=collect_cache,
                        attn_impl=attn_impl, ssd_impl=ssd_impl, shared=shared)

        def body(x, p, _blk=blk):
            return _blk(x, p)

        body = _maybe_remat(body, cfg, remat_policy)
        h, cache = jax.lax.scan(body, h, params["segments"][seg_idx])
        caches.append(cache)

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if not head:
        return h, caches
    head_w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", h, head_w)
    return shard_constraint(logits, ("data", None, "model")), caches


def loss_fn(params, batch, cfg: ModelConfig, **fw):
    """batch: {"inputs": tokens|embeddings, "labels": (B,S) int32 (-1 = pad)}."""
    logits, _ = forward(params, batch["inputs"], cfg, **fw)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    return cross_entropy(logits, jnp.maximum(labels, 0), mask)


# ---------------------------------------------------------------------------
# Serving: cache plan, prefill, decode
# ---------------------------------------------------------------------------


def cache_plan(cfg: ModelConfig, batch: int, max_len: int,
               per_slot_index: bool = False) -> dict:
    """Descriptor pytree for the decode cache (shardable, eval_shape-able).

    ``per_slot_index=True`` gives the cache a ``(batch,)`` sequence index —
    one length per stream — instead of the scalar lockstep index: the
    continuous-batching pool layout, where streams admitted at different
    times decode at different positions (`serving.lm.LMScheduler`).
    """
    import dataclasses as _dc
    seq_shard = batch == 1  # long-context: shard sequence, not batch
    segs = segments(cfg)

    def attn_cache(count):
        if cfg.mla:
            return mla.plan_cache(cfg, batch, max_len, count)
        kv = attention.plan_kv_cache(cfg, batch, max_len, count, seq_shard)
        if not cfg.kv_quant:
            return {"k": kv, "v": kv}
        kv8 = _dc.replace(kv, dtype="int8")
        sc = attention.plan_kv_scale(cfg, batch, max_len, count)
        return {"k": kv8, "v": kv8, "k_scale": sc, "v_scale": sc}

    seg_caches: list[Any] = []
    for kind, count in segs:
        if kind in ("dense", "dense_ff", "moe"):
            seg_caches.append(attn_cache(count))
        elif kind == "ssm":
            seg_caches.append(ssm_mod.plan_cache(cfg, batch, count))
        elif kind == "zsuper":
            inner = cfg.ssm.attn_every - 1
            c = attn_cache(count)
            c["ssm"] = _stack_plan(ssm_mod.plan_cache(cfg, batch, inner),
                                   count)
            seg_caches.append(c)
    idx_shape, idx_spec = (((batch,), ("data",)) if per_slot_index
                           else ((), ()))
    out = {"segments": seg_caches,
           "index": ParamDesc(idx_shape, idx_spec, init="zeros",
                              dtype="int32")}
    if cfg.layout == "moe":
        out["moe_load"] = _plan_moe_load(cfg, batch)
    if cfg.plastic_adapter:
        out["adapter"] = plastic.plan_cache(cfg, batch)
    return out


def _plan_moe_load(cfg: ModelConfig, batch: int) -> ParamDesc:
    """Per stream, the routed assignments of its last decoded token (of
    a window's tokens, after `decode_rollout`) to each held expert, summed
    over the MoE layers; 0 after prefill.  `LMScheduler` counts them as
    the held experts' load."""
    return ParamDesc((batch, cfg.moe.num_experts), ("data", None),
                     init="zeros", dtype="int32")


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               per_slot_index: bool = False):
    return init_from_plan(cache_plan(cfg, batch, max_len, per_slot_index),
                          jax.random.PRNGKey(0))


def prefill(params, inputs, cfg: ModelConfig, max_len: int, *,
            attn_impl: str = "xla_flash", ssd_impl: str = "xla"):
    """Run the prompt through the model, building the decode cache.

    Returns (last-position logits (B,V), cache).
    """
    bsz = inputs.shape[0]
    s = inputs.shape[1]
    hidden, caches = forward(params, inputs, cfg, collect_cache=True,
                             attn_impl=attn_impl, ssd_impl=ssd_impl,
                             remat_policy="none", head=False)
    head_w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bd,dv->bv", hidden[:, -1], head_w)
    logits = shard_constraint(logits, ("data", "model"))
    segs = segments(cfg)

    def pack_kv(k, v):
        out = {}
        if cfg.mla:     # k: the normed latent c, v: the rotated k_pe
            out = {"ckv": _embed_kv(k, bsz, max_len, cfg),
                   "kpe": _embed_kv(v, bsz, max_len, cfg)}
        elif cfg.kv_quant:
            kq, ks = attention.quantize_kv(k)
            vq, vs = attention.quantize_kv(v)
            out = {"k": _embed_kv(kq, bsz, max_len, cfg),
                   "v": _embed_kv(vq, bsz, max_len, cfg),
                   "k_scale": _embed_kv(ks, bsz, max_len, cfg),
                   "v_scale": _embed_kv(vs, bsz, max_len, cfg)}
        else:
            out = {"k": _embed_kv(k, bsz, max_len, cfg),
                   "v": _embed_kv(v, bsz, max_len, cfg)}
        return out

    seg_caches = []
    for (kind, count), c in zip(segs, caches):
        if kind in ("dense", "dense_ff", "moe"):
            k, v = c
            seg_caches.append(pack_kv(k, v))
        elif kind == "ssm":
            state, conv = c
            seg_caches.append({"ssm": state, "conv": conv})
        else:  # zsuper
            (k, v), (state, conv) = c
            sc = pack_kv(k, v)
            sc["ssm"] = {"ssm": state, "conv": conv}
            seg_caches.append(sc)
    cache = {"segments": seg_caches, "index": jnp.asarray(s, jnp.int32)}
    if cfg.layout == "moe":
        cache["moe_load"] = init_from_plan(_plan_moe_load(cfg, bsz),
                                           jax.random.PRNGKey(0))
    if cfg.plastic_adapter:
        cache["adapter"] = init_from_plan(plastic.plan_cache(cfg, bsz),
                                          jax.random.PRNGKey(0))
    return logits, cache


def _embed_kv(k, bsz, max_len, cfg):
    """Place prefilled (L,B,S,...) into a (L,B,max_len,...) buffer."""
    if k.shape[2] == max_len:
        return k
    buf = jnp.zeros((*k.shape[:2], max_len, *k.shape[3:]), k.dtype)
    return jax.lax.dynamic_update_slice(buf, k, (0,) * k.ndim)


def _decode_backbone(params, cache, tokens, cfg: ModelConfig, active=None):
    """Embed + all segments for ONE new token per stream.  tokens (B,1).

    Returns (h (B,1,D) pre-final-norm, new segment caches, new index,
    load): `load` is the (B, E_held) routed assignments of each stream's
    token to the held experts, summed over the MoE layers (None for a
    layout without them).
    ``active (B,)`` is the pool's vacant-slot mask, enforcing the TRUE
    no-op contract on every piece of per-stream state: KV/scale cache rows
    are write-gated (`attention._write_at`), SSM/conv states are
    select-gated, per-slot sequence indices freeze, and MoE dispatch
    routes dropless (no row can displace another) and counts a vacant
    slot's token nowhere.  A vacant slot's entire
    session row is bit-identical after any number of pool steps.  Vacant
    rows' hidden-state COMPUTE is garbage, but nothing persistent reads it
    (the adapter and pending-token updates are gated downstream).
    """
    index = cache["index"]
    token_mask = (None if active is None
                  else jnp.broadcast_to(active.astype(bool)[:, None],
                                        tokens.shape))

    def gate_rows(new, old):
        # freeze vacant streams' state rows (leading axis = stream)
        if active is None:
            return new
        m = active.astype(bool).reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)
    h = jnp.take(params["embed"], tokens, axis=0)       # (B,1,D)
    h = shard_constraint(h, ("data", None, None))

    def ffn(p, x, kind):
        if kind == "moe":
            return moe_mod.apply_held(p["moe"], x, cfg, token_mask=token_mask)
        return _mlp_apply(p["mlp"], x, cfg), None

    new_segs, loads = [], []
    for seg_idx, (kind, count) in enumerate(segments(cfg)):
        seg_p = params["segments"][seg_idx]
        c = cache["segments"][seg_idx]
        if kind in ("dense", "dense_ff", "moe") and cfg.mla:
            def body(x, xs, _kind=kind):
                p, c_l, r_l = xs
                x, cn, rn = mla.decode_step(p["attn"], x, c_l, r_l, index,
                                            cfg, active=active)
                x, load = ffn(p, x, _kind)
                return x, ((cn, rn), load)

            h, ((cs, rs), load) = jax.lax.scan(
                body, h, (seg_p, c["ckv"], c["kpe"]))
            new_segs.append({"ckv": cs, "kpe": rs})
            if load is not None:
                loads.append(load.sum(0))
        elif kind in ("dense", "dense_ff", "moe"):
            def body(x, xs, _kind=kind):
                if cfg.kv_quant:
                    p, k_l, v_l, sk_l, sv_l = xs
                    x, kn, vn, skn, svn = attention.decode_step(
                        p["attn"], x, k_l, v_l, index, cfg,
                        scale_k=sk_l, scale_v=sv_l, active=active)
                else:
                    p, k_l, v_l = xs
                    x, kn, vn = attention.decode_step(p["attn"], x, k_l, v_l,
                                                      index, cfg,
                                                      active=active)
                    skn = svn = None
                x, load = ffn(p, x, _kind)
                out = (kn, vn, skn, svn) if cfg.kv_quant else (kn, vn)
                return x, (out if load is None else (*out, load))

            if cfg.kv_quant:
                h, ys = jax.lax.scan(
                    body, h, (seg_p, c["k"], c["v"],
                              c["k_scale"], c["v_scale"]))
            else:
                h, ys = jax.lax.scan(body, h, (seg_p, c["k"], c["v"]))
            if kind == "moe":
                *ys, load = ys
                loads.append(load.sum(0))
            if cfg.kv_quant:
                ks, vs, sks, svs = ys
                new_segs.append({"k": ks, "v": vs,
                                 "k_scale": sks, "v_scale": svs})
            else:
                ks, vs = ys
                new_segs.append({"k": ks, "v": vs})
        elif kind == "ssm":
            def body(x, xs):
                p, st, cv = xs
                x, st_n, cv_n = ssm_mod.decode_step(p, x, st, cv, cfg)
                return x, (gate_rows(st_n, st), gate_rows(cv_n, cv))

            h, (sts, cvs) = jax.lax.scan(body, h, (seg_p, c["ssm"], c["conv"]))
            new_segs.append({"ssm": sts, "conv": cvs})
        else:  # zsuper
            shared_p = (params["shared_attn"], params["shared_mlp"])

            def super_body(x, xs):
                if cfg.kv_quant:
                    p, k_l, v_l, sk_l, sv_l, st_l = xs
                    x, kn, vn, skn, svn = attention.decode_step(
                        shared_p[0], x, k_l, v_l, index, cfg,
                        scale_k=sk_l, scale_v=sv_l, active=active)
                else:
                    p, k_l, v_l, st_l = xs
                    x, kn, vn = attention.decode_step(shared_p[0], x, k_l,
                                                      v_l, index, cfg,
                                                      active=active)
                    skn = svn = None
                x = _mlp_apply(shared_p[1], x, cfg)

                def inner(xx, ys):
                    pl, st, cv = ys
                    xx, st_n, cv_n = ssm_mod.decode_step(pl, xx, st, cv, cfg)
                    return xx, (gate_rows(st_n, st), gate_rows(cv_n, cv))

                x, (sts, cvs) = jax.lax.scan(
                    inner, x, (p["ssm"], st_l["ssm"], st_l["conv"]))
                if cfg.kv_quant:
                    return x, (kn, vn, skn, svn, sts, cvs)
                return x, (kn, vn, sts, cvs)

            if cfg.kv_quant:
                h, (ks, vs, sks, svs, sts, cvs) = jax.lax.scan(
                    super_body, h,
                    (seg_p, c["k"], c["v"], c["k_scale"], c["v_scale"],
                     c["ssm"]))
                new_segs.append({"k": ks, "v": vs, "k_scale": sks,
                                 "v_scale": svs,
                                 "ssm": {"ssm": sts, "conv": cvs}})
            else:
                h, (ks, vs, sts, cvs) = jax.lax.scan(
                    super_body, h, (seg_p, c["k"], c["v"], c["ssm"]))
                new_segs.append({"k": ks, "v": vs,
                                 "ssm": {"ssm": sts, "conv": cvs}})

    if index.ndim == 0:
        new_index = index + 1
    else:  # per-slot: vacant slots' sequence positions stay frozen
        new_index = index + (active.astype(jnp.int32) if active is not None
                             else 1)
    return h, new_segs, new_index, (sum(loads) if loads else None)


def _head(params, h, cfg: ModelConfig):
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", h, head)
    return shard_constraint(logits, ("data", None, "model"))


def decode_step(params, cache, tokens, cfg: ModelConfig, active=None,
                slot_mesh=None):
    """One decode step.  tokens (B,1) int32.

    Returns (logits (B,V), new_cache).  cache["index"] is the number of
    tokens already resident (scalar for lockstep decode, per-slot ``(B,)``
    under the continuous-batching pool); the new token is written at that
    position.  ``active (B,)`` marks resident streams — vacant slots are
    no-ops: adapter state bit-frozen, per-slot index frozen, no expert
    capacity consumed, logits garbage nothing reads.  ``slot_mesh`` is the
    mesh whose ``"data"`` axis shards a pool's slot rows (see
    `plastic.decode_step`).
    """
    h, new_segs, new_index, load = _decode_backbone(params, cache, tokens,
                                                    cfg, active=active)
    new_cache = {"segments": new_segs, "index": new_index}
    if load is not None:
        new_cache["moe_load"] = load
    if cfg.plastic_adapter:
        h, new_cache["adapter"] = plastic.decode_step(
            params["adapter"], cache["adapter"], h, cfg, active=active,
            slot_mesh=slot_mesh)
    logits = _head(params, h, cfg)[:, 0]
    return shard_constraint(logits, ("data", "model")), new_cache


def decode_rollout(params, cache, tokens, cfg: ModelConfig, active=None,
                   slot_mesh=None):
    """K known tokens per stream in one jitted program.  tokens (B,K) int32.

    Teacher-forced multi-token decode — chunked prompt tails, speculative
    draft verification, the scheduler's windowed `decode_window`: the
    backbone advances token-by-token inside a `lax.scan` (each token's
    attention must see the one before it), but the plastic adapter's K
    update steps run as ONE time-fused `engine.rollout` launch via
    `plastic.decode_rollout` instead of K per-token `layer_step` launches.
    This is sound because the adapter sits AFTER all segments: it touches
    only the final hidden state (hence the logits), never the KV/SSM
    caches, so the backbone scan can run to completion first and hand the
    adapter the whole (B, K, D) window.  Bit-identical to K `decode_step`
    calls on the same tokens (pinned in tests/test_serving_lm.py).

    Returns (logits (B,K,V), new_cache).  Works for every layout, with or
    without the adapter; ``slot_mesh`` as in `decode_step`.
    """
    tk = jnp.swapaxes(tokens, 0, 1)[:, :, None]          # (K,B,1)

    def body(carry, tok):
        segs, index = carry
        h, segs, index, load = _decode_backbone(
            params, {"segments": segs, "index": index}, tok, cfg,
            active=active)
        return (segs, index), (h[:, 0], load)

    (new_segs, new_index), (hs, loads) = jax.lax.scan(
        body, (cache["segments"], cache["index"]), tk)
    h = jnp.swapaxes(hs, 0, 1)                           # (B,K,D)
    new_cache = {"segments": new_segs, "index": new_index}
    if loads is not None:       # the window's assignments, all K tokens
        new_cache["moe_load"] = loads.sum(0)
    if cfg.plastic_adapter:
        h, new_cache["adapter"] = plastic.decode_rollout(
            params["adapter"], cache["adapter"], h, cfg, active=active,
            slot_mesh=slot_mesh)
    return _head(params, h, cfg), new_cache


# ---------------------------------------------------------------------------
# Introspection
# ---------------------------------------------------------------------------


def n_params(cfg: ModelConfig) -> int:
    return param_count(plan(cfg))


def abstract(cfg: ModelConfig, mesh=None, fsdp: bool = True):
    return abstract_from_plan(plan(cfg, fsdp), mesh)


def shardings(cfg: ModelConfig, mesh, fsdp: bool = True):
    return shardings_from_plan(plan(cfg, fsdp), mesh)


def pspecs(cfg: ModelConfig, mesh, fsdp: bool = True):
    return specs_from_plan(plan(cfg, fsdp), mesh)
