"""Mixture-of-Experts FFN with capacity-bounded sort-based dispatch.

Fine-grained MoE per deepseek-moe (shared + routed experts, top-k) and
grok-1 (8 experts top-2).  Dispatch is the argsort/capacity scheme: tokens
are sorted by assigned expert, each expert processes a (E, C, D) buffer, and
outputs scatter back weighted by the router gate.  Under expert parallelism
the (E, C, D) buffer is sharded E->"model", so the token->expert resharding
lowers to the all-to-all pattern; compiled FLOPs track ACTIVE experts
(T * top_k * capacity_factor), not the dense all-experts product.

Serving routes dropless through `apply_held`: the layer is told which
routed experts it holds (``MoEConfig.first_held`` / ``num_experts`` of a
router over ``n_router``), routes every token over the whole router, and
computes the part of the result its own experts give, plus the shared
experts.  Under expert parallelism each chip holds one such share; on one
chip the layer runs without the exchange.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import get_mesh, shard_constraint
from repro.models.config import ModelConfig
from repro.models.layers import ParamDesc, rms_norm, swiglu


def plan(cfg: ModelConfig, stack: int = 0) -> dict:
    moe = cfg.moe
    d, f, e = cfg.d_model, moe.d_expert, moe.num_experts
    dt = cfg.dtype

    def desc(shape, spec, **kw):
        if stack:
            shape, spec = (stack, *shape), (None, *spec)
        kw.setdefault("dtype", dt)
        return ParamDesc(shape, spec, **kw)

    # expert-parallel ("model" on E) with automatic fallback to ffn-sharding
    # ("model" on F) when num_experts does not divide the model axis — see
    # sharding.logical_to_physical dedup (deepseek 64e vs grok 8e on 16-way).
    p = {
        "norm": desc((d,), (None,), init="ones"),
        "router": desc((d, moe.n_router), (None, None), fan_in=d,
                       dtype="float32"),
        "w_gate": desc((e, d, f), ("model", "data", "model"), fan_in=d),
        "w_up": desc((e, d, f), ("model", "data", "model"), fan_in=d),
        "w_down": desc((e, f, d), ("model", "model", "data"), fan_in=f),
    }
    if moe.n_shared:
        fs = moe.n_shared * moe.d_expert
        p["ws_gate"] = desc((d, fs), ("data", "model"), fan_in=d)
        p["ws_up"] = desc((d, fs), ("data", "model"), fan_in=d)
        p["ws_down"] = desc((fs, d), ("model", "data"), fan_in=fs)
    return p


def apply(params, x, cfg: ModelConfig, groups: int = 0):
    """x (B,S,D) -> (B,S,D) residual-added MoE FFN (the training dispatch;
    tokens past an expert's capacity are dropped).

    GROUPED LOCAL DISPATCH (EXPERIMENTS.md §Perf, deepseek/grok cells):
    tokens split into `groups` dispatch groups aligned with the data axis;
    the sort/scatter runs independently per group over a (G, E, C/G, D)
    buffer whose G dim shards over "data".  GSPMD keeps every
    scatter/gather SHARD-LOCAL and the only cross-device movement is the
    (G, E, ...) <-> expert-parallel reshard (the all-to-all pattern).  The
    original ungrouped global sort forced an all-gather of every token to
    every device, which made the MoE train cells ~100x collective-bound
    (baseline rows in EXPERIMENTS.md §Perf).  Capacity is enforced per
    group (standard local-dispatch semantics).
    """
    import math

    moe = cfg.moe
    b, s, d = x.shape
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    t = b * s
    e, k = moe.num_experts, moe.top_k
    if groups <= 0:
        # one dispatch group per data shard — MUST track the mesh: a fixed
        # group count that does not divide the (pod x data) axis silently
        # replicates the dispatch buffer (caught on the multi-pod sweep)
        mesh = get_mesh()
        groups = (mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
                  if mesh is not None else 1)
    g_n = max(1, math.gcd(b, groups))                # groups ride the batch dim
    tg = t // g_n                                    # tokens per group
    xt = h.reshape(g_n, tg, d)

    # ---- routing (fp32) ----------------------------------------------------
    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, moe.top_k)          # (G,Tg,K)
    if moe.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    cap = max(int(moe.capacity_factor * tg * k / e), 1)

    def dispatch_one(xt_g, idx_g, gate_g):
        """Per-group sort-based dispatch (shard-local under vmap)."""
        flat_e = idx_g.reshape(-1)                               # (Tg*K,)
        order = jnp.argsort(flat_e)                              # stable
        sorted_e = flat_e[order]
        pos = jnp.arange(tg * k) - jnp.searchsorted(sorted_e, sorted_e,
                                                    side="left")
        keep = pos < cap
        # dropped slots write to (and read from) a trash row so they never
        # clobber a kept token's buffer slot
        dest = jnp.where(keep, sorted_e * cap + pos, e * cap)
        tok = order // k
        buf = jnp.zeros((e * cap + 1, d), xt_g.dtype)
        buf = buf.at[dest].set(xt_g[tok])
        w = jnp.where(keep, gate_g.reshape(-1)[order], 0.0)
        return buf[:e * cap].reshape(e, cap, d), dest, tok, w

    buf, dest, tok, w = jax.vmap(dispatch_one)(xt, expert_idx, gates)
    # Pin the scatter output DATA-LOCAL first (G over data, E replicated):
    # without this anchor GSPMD partitions the scatter over the model axis
    # and must all-reduce (T*k, D)-sized partials (plus a u32 index-mask
    # reduction) — the 385s-collective baseline in EXPERIMENTS.md §Perf.
    buf = shard_constraint(buf, ("data", None, None, None))

    mesh = get_mesh()
    model_ax = mesh.shape.get("model", 1) if mesh is not None else 1
    expert_parallel = e % model_ax == 0
    if expert_parallel:
        # grouped all-to-all reshard onto the expert-parallel layout
        buf = shard_constraint(buf, ("data", "model", None, None))

    # ---- expert compute (batched over G, E) ---------------------------------
    gt = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, params["w_gate"]))
    u = jnp.einsum("gecd,edf->gecf", buf, params["w_up"])
    eo = jnp.einsum("gecf,efd->gecd", gt * u, params["w_down"])
    if expert_parallel:
        eo = shard_constraint(eo, ("data", "model", None, None))
        # reshard back so the combine gather is shard-local on the data axis
        eo = shard_constraint(eo, ("data", None, None, None))
    # else (dense-TP experts, e.g. grok 8e on a 16-way axis): w_down's
    # model-axis contraction leaves eo PARTIAL-summed; the combine gather
    # and scatter-add are linear, so the partial flows through them and one
    # all-reduce fires at token granularity (G,Tg,D) — 1/(k*capacity_factor)
    # of the buf-granularity volume an eo anchor would force.

    # ---- combine back (per group, shard-local) ------------------------------
    def combine_one(eo_g, dest_g, tok_g, w_g):
        eflat = jnp.concatenate([eo_g.reshape(e * cap, d),
                                 jnp.zeros((1, d), eo_g.dtype)], 0)
        vals = eflat[dest_g]                                     # (Tg*K, D)
        out = jnp.zeros((tg, d), jnp.float32)
        return out.at[tok_g].add(vals.astype(jnp.float32) * w_g[:, None])

    out = jax.vmap(combine_one)(eo, dest, tok, w)
    out = out.reshape(b, s, d).astype(x.dtype)

    if moe.n_shared:
        out = out + swiglu(h, params["ws_gate"], params["ws_up"],
                           params["ws_down"])
    return x + shard_constraint(out, cfg.act_spec)


def apply_held(params, x, cfg: ModelConfig, token_mask=None):
    """Dropless MoE FFN over this layer's held share of the routed experts.

    x (B,S,D) -> (x + the held experts' part + the shared experts,
    assignments (B,E_held) int32: each row's tokens routed to each held
    expert).
    Every token is routed over the whole router (softmax, greedy top-k,
    renormalised only with ``norm_topk_prob``); each held expert runs
    densely over all tokens and is weighted by its gate where chosen, 0
    elsewhere, so no token is ever dropped and rows never interact.
    ``token_mask`` (B,S) marks valid tokens: the others count nothing.
    """
    moe = cfg.moe
    e, lo = moe.num_experts, moe.first_held
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dr->bsr", h.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), moe.top_k)
    if moe.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    chosen = jax.nn.one_hot(idx - lo, e, dtype=jnp.float32)     # (B,S,K,E)
    if token_mask is not None:
        chosen = chosen * token_mask[..., None, None].astype(jnp.float32)
    w = jnp.einsum("bske,bsk->bse", chosen, gates)              # (B,S,E)
    counts = chosen.sum((1, 2)).astype(jnp.int32)
    g = jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, params["w_gate"]))
    u = jnp.einsum("bsd,edf->bsef", h, params["w_up"])
    eo = jnp.einsum("bsef,efd->bsed", g * u, params["w_down"])
    out = jnp.einsum("bsed,bse->bsd", eo.astype(jnp.float32),
                     w).astype(x.dtype)
    if moe.n_shared:
        out = out + swiglu(h, params["ws_gate"], params["ws_up"],
                           params["ws_down"])
    return x + shard_constraint(out, cfg.act_spec), counts


def aux_load_balance_loss(params, x, cfg: ModelConfig):
    """Switch-style load-balance auxiliary (mean over layers handled by caller)."""
    moe = cfg.moe
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,de->bse", h.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)
    top1 = jnp.argmax(probs, -1)
    frac = jnp.mean(jax.nn.one_hot(top1, moe.num_experts, dtype=jnp.float32),
                    axis=(0, 1))
    imp = probs.mean((0, 1))
    return moe.num_experts * jnp.sum(frac * imp)
