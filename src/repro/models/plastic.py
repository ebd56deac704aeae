"""Plastic fast-weight adapter — FireFly-P's rule as an LM serving feature.

A two-population spiking micro-network rides on the backbone's hidden state
during DECODE (adaptation is a serve-time behavior, matching the paper's
Phase 2).  Per decode step, per request:

    drive   = h @ P_in                  (fixed random projection, D -> N)
    s1      = LIF(v1, drive)            (presynaptic population)
    s2, W_fast <- PlasticEngine.layer_step(s1)   (fused forward + rule)
    h'      = h + scale * (s2 @ P_out)  (readout back into the residual)

The synaptic layer between the two populations is ONE fleet-mode
`core.engine.layer_step` over the whole batch: W_fast carries a leading
request rank (B, N, N) and every decode stream rewrites its own synapses
with a per-sample dw inside a single fused launch (grid (tiles, B/8) on
Pallas) — not B vmap-stamped kernel calls.  The serving hot path runs the
SAME fused dual-engine program as the SNN controller; ``cfg.adapter_impl``
selects the backend ("xla" | "pallas" | "pallas-interpret").

W_fast starts at ZERO and lives in the decode cache (B, N, N) — one plastic
memory per request stream, continuously rewritten online.  theta is the
offline-learned rule (ES / PEPG in core/), frozen at serve time.

Continuous-batching contracts (the `serving.lm.LMScheduler` pool):

  * ``active (B,)`` — vacant decode slots are TRUE no-ops: the engine's
    fleet mask freezes W_fast/v2/tr2 bit-exactly, and this module gates the
    presynaptic state (v1, tr1) and the per-session step counter ``t`` the
    same way, so a vacant slot's adapter state never drifts.
  * ``cfg.adapter_quant`` — the FPGA-faithful fixed-point pool: W_fast is
    int8 with a per-slot fp32 scale, membranes/traces are int32, and dw is
    rounded to grid steps by the deterministic stochastic round keyed on
    the per-SESSION counter ``t`` (never the slot), so evict -> persist ->
    re-admit is bit-identical mid-generation.  The presynaptic population
    stays float (it is driven by the float backbone h); the datapath
    boundary is ``to_fixed(s1)`` — exact, since spikes are 0/1.

Applicability notes per arch family are in DESIGN.md §Arch-applicability
(which backbone layouts the adapter composes with, and why).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core import plasticity as P
from repro.core.snn import LIFConfig, lif_step
from repro.kernels.plasticity import quant as Q
from repro.models.config import ModelConfig
from repro.models.layers import ParamDesc

LIF = LIFConfig(tau_m=2.0, v_threshold=1.0, v_reset=0.0)
# The adapter's fixed-point grid (cfg.adapter_quant).  Defaults pair with
# the paper's datapath: tau_m = 2**1 matches LIF.tau_m, trace decay 0.75,
# int8 weights on a 2**-5 grid spanning w_clip = 4.
QUANT = Q.QuantConfig()


def plan(cfg: ModelConfig) -> dict:
    d, n = cfg.d_model, cfg.adapter_neurons
    return {
        "p_in": ParamDesc((d, n), ("data", "model"), fan_in=d, dtype=cfg.dtype),
        "p_out": ParamDesc((n, d), ("model", "data"), fan_in=n, dtype=cfg.dtype),
        "theta": ParamDesc((P.NUM_TERMS, n, n), (None, None, "model"),
                           scale=0.3, fan_in=n, dtype="float32"),
        "scale": ParamDesc((), (), init="zeros", dtype="float32"),
    }


def plan_cache(cfg: ModelConfig, batch: int) -> dict:
    """Per-stream adapter state descriptors (one session = one row).

    ``t`` is the per-SESSION step counter: scattered in and out with the
    session, it seeds the quantized datapath's deterministic stochastic
    round (and is plain bookkeeping in float mode), so an update stream
    follows the session across evictions and slot changes.
    """
    n = cfg.adapter_neurons
    f32, i32 = "float32", "int32"

    def z(shape, spec, dtype=f32):
        return ParamDesc(shape, spec, init="zeros", dtype=dtype)

    sdt = i32 if cfg.adapter_quant else f32   # synaptic-layer state dtype
    out = {
        "w_fast": ParamDesc((batch, n, n), ("data", None, "model"),
                            init="zeros",
                            dtype="int8" if cfg.adapter_quant else f32),
        "v1": z((batch, n), ("data", "model")),          # presyn: always f32
        "v2": z((batch, n), ("data", "model"), sdt),
        "tr1": z((batch, n), ("data", "model"), sdt),
        "tr2": z((batch, n), ("data", "model"), sdt),
        "t": z((batch,), ("data",), i32),
    }
    if cfg.adapter_quant:
        # per-slot dequant scale: the int8 payload is meaningless without
        # it, so it travels with the session like every other state row
        out["w_scale"] = ParamDesc((batch,), ("data",), init="full",
                                   scale=QUANT.w_scale, dtype=f32)
    return out


def _engine_params(cfg: ModelConfig, trace_decay: float, w_clip: float
                   ) -> engine.EngineParams:
    if cfg.adapter_quant:
        return engine.EngineParams(
            tau_m=QUANT.tau_m, v_th=LIF.v_threshold, v_reset=LIF.v_reset,
            trace_decay=QUANT.decay, w_clip=w_clip, plastic=True,
            spiking=True, quant=QUANT)
    return engine.EngineParams(
        tau_m=LIF.tau_m, v_th=LIF.v_threshold, v_reset=LIF.v_reset,
        trace_decay=trace_decay, w_clip=w_clip, plastic=True, spiking=True)


def _gate(active, new, old):
    """Freeze per-slot rows whose active flag is false (bit-exact no-op)."""
    if active is None:
        return new
    mask = active.astype(bool).reshape((-1,) + (1,) * (new.ndim - 1))
    return jnp.where(mask, new, old)


def _per_shard(fn, slot_mesh, in_axes, out_axes):
    """Run `fn` per slot shard of `slot_mesh` (identity without a mesh).

    The synaptic layer's slot rows are independent, so a sharded pool runs
    the same engine program on each device's rows (`engine.fleet_spmd`);
    GSPMD cannot partition the Pallas kernel itself."""
    if slot_mesh is None:
        return fn
    return engine.fleet_spmd(fn, slot_mesh, in_axes, out_axes)


def decode_step(params, state: dict, h, cfg: ModelConfig,
                trace_decay: float = 0.8, w_clip: float = 4.0,
                active=None, slot_mesh=None):
    """h (B,1,D) -> (h', new_state).  One online plasticity step per token.

    ``active (B,)`` (optional) freezes vacant pool slots bit-exactly —
    presynaptic state, synaptic layer, and step counter alike.
    ``slot_mesh`` (optional): the mesh whose ``"data"`` axis shards the
    slot rows; the engine launch then runs per shard."""
    quant = cfg.adapter_quant
    drive = jnp.einsum("bd,dn->bn", h[:, 0].astype(jnp.float32),
                       params["p_in"].astype(jnp.float32))
    v1, s1 = lif_step(state["v1"], drive, LIF)
    v1 = _gate(active, v1, state["v1"])
    if quant:
        x = Q.to_fixed(s1, QUANT)                  # exact: spikes are 0/1
        tr1 = Q.trace_update_q(state["tr1"], x, QUANT)
    else:
        x = s1
        tr1 = P.update_trace(state["tr1"], s1, trace_decay)
    tr1 = _gate(active, tr1, state["tr1"])

    # Plastic synaptic layer: ONE fleet-mode fused dual-engine launch over
    # all request streams — w_fast (B, N, N) triggers per-sample dw, each
    # stream rewriting its own W_fast against the shared rule theta.
    ep = _engine_params(cfg, trace_decay, w_clip)

    def synapse(w, v, tpre, tpost, x, active, seed, w_scale, theta):
        layer = engine.LayerState(w=w, v=v, trace_pre=tpre,
                                  trace_post=tpost, theta=theta,
                                  w_scale=w_scale)
        layer, s2 = engine.layer_step(layer, x, params=ep,
                                      impl=cfg.adapter_impl, active=active,
                                      seed=seed)
        return layer.w, layer.v, layer.trace_post, s2

    synapse = _per_shard(synapse, slot_mesh, (0,) * 8 + (None,), (0,) * 4)
    w_fast, v2, tr2, s2 = synapse(
        state["w_fast"], state["v2"], tr1, state["tr2"], x, active,
        Q.fold_seed(state["t"], 0) if quant else None, state.get("w_scale"),
        params["theta"].astype(jnp.float32))

    s2f = Q.from_fixed(s2, QUANT) if quant else s2
    out = jnp.einsum("bn,nd->bd", s2f, params["p_out"].astype(jnp.float32))
    if active is not None:
        out = out * active.astype(jnp.float32)[:, None]
    h = h + (params["scale"] * out[:, None, :]).astype(h.dtype)
    new_state = {"w_fast": w_fast, "v1": v1, "v2": v2,
                 "tr1": tr1, "tr2": tr2,
                 "t": state["t"] + _gate(active, jnp.ones((), jnp.int32),
                                         jnp.zeros((), jnp.int32))}
    if quant:
        new_state["w_scale"] = state["w_scale"]
    return h, new_state


def decode_rollout(params, state: dict, h, cfg: ModelConfig,
                   trace_decay: float = 0.8, w_clip: float = 4.0,
                   active=None, slot_mesh=None):
    """h (B, K, D) -> (h', new_state).  K plasticity steps, ONE fused launch.

    The multi-token form of K sequential `decode_step` calls — speculative
    drafts, chunked prefill tails, the scheduler's windowed `decode_window`,
    any case where a decode stream advances several tokens at once.  The
    presynaptic population is feedforward (v1/s1 depend only on the tokens),
    so its LIF series is peeled into a cheap scan of per-token projections;
    the expensive part — K steps of the plastic synaptic layer, forward +
    four-term rule on every stream's own (N, N) W_fast — then runs as ONE
    time-fused `engine.rollout` launch (a single `pallas_call` on the
    Pallas backends) instead of K per-token `layer_step` launches.
    Bit-identical to the sequential path (`tests/test_fused.py` pins it):
    the per-token einsums stay per-token inside scans, and the rollout
    oracle is the same `layer_step` program.  In quant mode step k of the
    window draws its stochastic round from the per-session counter
    ``t + k`` — exactly the sequence K single `decode_step` calls would.
    ``slot_mesh`` as in `decode_step`.
    """
    quant = cfg.adapter_quant
    p_in = params["p_in"].astype(jnp.float32)
    p_out = params["p_out"].astype(jnp.float32)
    hk = jnp.swapaxes(h, 0, 1)                       # time-major (K, B, D)

    def pre(v1, h_t):
        drive = jnp.einsum("bd,dn->bn", h_t.astype(jnp.float32), p_in)
        v1_new, s1 = lif_step(v1, drive, LIF)
        return _gate(active, v1_new, v1), s1

    v1, s1_series = jax.lax.scan(pre, state["v1"], hk)   # (K, B, N)

    ep = _engine_params(cfg, trace_decay, w_clip)

    def synapses(w, v, tr1, tr2, drives, active, seed, w_scale, theta):
        net = engine.NetworkState(
            w=(w,), v=(v,), trace=(tr1, tr2), t=jnp.zeros((), jnp.int32),
            w_scale=(w_scale,) if quant else ())
        net, s2_series = engine.rollout(
            net, [theta], drives, params=ep, impl=cfg.adapter_impl,
            active=active, seed=seed)
        return net.w[0], net.v[0], net.trace[0], net.trace[1], s2_series

    synapses = _per_shard(synapses, slot_mesh,
                          (0, 0, 0, 0, 1, 0, 0, 0, None), (0, 0, 0, 0, 1))
    drives = Q.to_fixed(s1_series, QUANT) if quant else s1_series
    w_fast, v2, tr1, tr2, s2_series = synapses(
        state["w_fast"], state["v2"], state["tr1"], state["tr2"], drives,
        active, state["t"] if quant else None, state.get("w_scale"),
        params["theta"].astype(jnp.float32))

    def post(_, s2):
        s2f = Q.from_fixed(s2, QUANT) if quant else s2
        return None, jnp.einsum("bn,nd->bd", s2f, p_out)

    _, outs = jax.lax.scan(post, None, s2_series)        # (K, B, D)
    if active is not None:
        outs = outs * active.astype(jnp.float32)[None, :, None]
    h = h + (params["scale"] * jnp.swapaxes(outs, 0, 1)).astype(h.dtype)
    k_steps = h.shape[1]
    new_state = {"w_fast": w_fast, "v1": v1, "v2": v2,
                 "tr1": tr1, "tr2": tr2,
                 "t": state["t"] + _gate(active,
                                         jnp.full((), k_steps, jnp.int32),
                                         jnp.zeros((), jnp.int32))}
    if quant:
        new_state["w_scale"] = state["w_scale"]
    return h, new_state
