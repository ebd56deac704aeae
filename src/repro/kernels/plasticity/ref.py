"""Pure-jnp oracle for the fused dual-engine step (forward + plasticity).

Semantics of one SNN timestep for one synaptic layer — the single source of
truth the engine's ``impl="xla"`` backend runs and the Pallas kernel is
validated against:

    I        = x @ w (+ teach)             # psum stage (Forward Engine)
    v_new    = v + (I - v) * (1/tau_m)     # neuron dynamics, tau_m = 2
    spiking:   s = v_new >= v_th ; v_out = v_reset where s else v_new
    readout:   s = tanh(v_new)   ; v_out = v_new       (leaky integrator)
    tp_new   = lam * trace_post + s        # trace update
    hebb     = trace_pre^T @ tp_new / B    # Plasticity Engine (4 terms)
    dw       = a*hebb + b*mean(pre)[:,N] + g*mean(tp_new)[N,:] + d
    w_new    = clip(w + dw, -clip, clip)

`trace_pre` is the *already-updated* presynaptic trace for this timestep
(the Forward Engine's Trace Update Unit runs upstream of this layer).

Inputs may be unbatched ``(N,)`` or batched ``(B, N)``; shared weights
batch-average the update, matching ``core.plasticity.delta_w``.

`dual_engine_fleet_step` is the FLEET variant: weights carry a leading
request-stream rank ``(B, N, M)`` and every stream rewrites its own synapses
with a per-sample dw (no batch averaging) under one shared rule theta —
exactly ``vmap`` of the unbatched step over (x, w, v, traces).  On the xla
backend that vmap IS the best batched lowering (XLA turns it into batched
contractions), so the fleet oracle is defined as the vmap itself —
bit-identical to per-sample semantics by construction; the Pallas fleet
kernel re-expresses the same program as ONE launch over a (tiles, B) grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.plasticity import ALPHA, BETA, GAMMA, DELTA
from repro.kernels.plasticity import quant as Q
from repro.obs.telemetry import sat_threshold, sat_threshold_q


def _fleet_telemetry_raw(events, v_out, w_old, w_new, active, *,
                         v_th, scale=None, qcfg=None):
    """Raw per-slot telemetry sums ``(B, 3) float32`` (obs.telemetry schema).

    Computed from the already-gated fleet outputs: col 0 = sum |events|
    (event units — the fixed-point 0/``one`` grid is divided out), col 1 =
    sum |dw| in float weight units (int8 grid steps x per-slot scale on the
    quantized path), col 2 = count of membranes at >= SAT_FRACTION of
    threshold.  The whole row is multiplied by the active mask: events and
    dw are already zero for vacant slots (events gated, w frozen), but the
    frozen membrane of a vacant slot may well sit near threshold — without
    the gate col 2 would leak stale state.
    """
    if qcfg is not None:
        spike_sum = jnp.sum(jnp.abs(events), axis=1).astype(jnp.float32) \
            / qcfg.one
        dsteps = jnp.abs(w_new.astype(jnp.int32) - w_old.astype(jnp.int32))
        abs_dw = jnp.sum(dsteps, axis=(1, 2)).astype(jnp.float32) \
            * jnp.asarray(scale, jnp.float32).reshape(-1)
        sat = jnp.abs(v_out) >= sat_threshold_q(v_th, qcfg)
    else:
        spike_sum = jnp.sum(jnp.abs(events), axis=1).astype(jnp.float32)
        abs_dw = jnp.sum(
            jnp.abs(w_new.astype(jnp.float32) - w_old.astype(jnp.float32)),
            axis=(1, 2))
        sat = jnp.abs(v_out) >= sat_threshold(v_th)
    sat_cnt = jnp.sum(sat, axis=1).astype(jnp.float32)
    raw = jnp.stack([spike_sum, abs_dw, sat_cnt], axis=1)
    if active is not None:
        raw = raw * active.reshape(-1, 1).astype(jnp.float32)
    return raw


def dual_engine_step(x, w, theta, v, trace_pre, trace_post, *,
                     tau_m: float = 2.0, v_th: float = 1.0,
                     v_reset: float = 0.0, trace_decay: float = 0.8,
                     w_clip: float = 4.0, plastic: bool = True,
                     spiking: bool = True, teach=None):
    """Oracle.  Shapes: x (B,N)|(N,), w (N,M), theta (4,N,M)|None,
    v (B,M)|(M,), trace_pre (B,N)|(N,), trace_post (B,M)|(M,),
    teach (B,M)|(M,)|None.

    Returns (events, v_out, trace_post_new, w_new) with batch rank preserved.
    Contractions run at ``Precision.HIGHEST``: float32 semantics on every
    backend (TPU's default for a float32 dot is one bfloat16 pass).
    """
    compute = jnp.float32
    current = jnp.dot(x.astype(compute), w.astype(compute),
                      precision=jax.lax.Precision.HIGHEST)
    if teach is not None:
        current = current + teach.astype(compute)
    v32 = v.astype(compute)
    v_new = v32 + (current - v32) * (1.0 / tau_m)
    if spiking:
        spikes = (v_new >= v_th).astype(compute)
        v_out = jnp.where(spikes > 0, v_reset, v_new)
    else:
        spikes = jnp.tanh(v_new)
        v_out = v_new
    tp_new = trace_decay * trace_post.astype(compute) + spikes

    if plastic:
        tpre = trace_pre.astype(compute)
        tpo = tp_new
        if tpre.ndim == 1:
            tpre, tpo = tpre[None], tpo[None]
        b = tpre.shape[0]
        th = theta.astype(compute)
        hebb = jnp.einsum("bi,bj->ij", tpre, tpo,
                          precision=jax.lax.Precision.HIGHEST) / b
        pre_m = tpre.mean(0)
        post_m = tpo.mean(0)
        dw = (th[ALPHA] * hebb + th[BETA] * pre_m[:, None]
              + th[GAMMA] * post_m[None, :] + th[DELTA])
        w_new = jnp.clip(w.astype(compute) + dw, -w_clip, w_clip)
    else:
        w_new = w.astype(compute)

    return (spikes.astype(x.dtype), v_out.astype(v.dtype),
            tp_new.astype(trace_post.dtype), w_new.astype(w.dtype))


def dual_engine_fleet_step(x, w, theta, v, trace_pre, trace_post, *,
                           tau_m: float = 2.0, v_th: float = 1.0,
                           v_reset: float = 0.0, trace_decay: float = 0.8,
                           w_clip: float = 4.0, plastic: bool = True,
                           spiking: bool = True, teach=None, active=None,
                           telemetry: bool = False):
    """Fleet oracle: per-request weights, per-sample dw, shared rule.

    Shapes: x (B,N), w (B,N,M), theta (4,N,M)|None, v (B,M),
    trace_pre (B,N), trace_post (B,M), teach (B,M)|None, active (B,)|None.

    Returns (events, v_out, trace_post_new, w_new) with w_new (B,N,M).
    Defined as ``vmap(dual_engine_step)`` over the leading rank with theta
    closed over (shared, unmapped) — per-sample semantics bit-identical to
    B independent unbatched steps, and the fastest XLA lowering measured
    on CPU (hand-written batched einsums were up to 2x slower).

    ``active`` is the slot mask of the session-serving subsystem: a stream
    whose slot is inactive is a TRUE no-op — its weights, membrane, and
    postsynaptic trace come back bit-identical (the dw is gated, not merely
    small) and its output events are zero.  This is what makes continuous
    batching into a fixed-shape fleet tensor semantically correct: padded /
    vacated slots cannot drift between swap-out and the next swap-in.
    """
    assert w.ndim == 3 and x.ndim == 2, (x.shape, w.shape)
    if teach is not None and teach.ndim == 1:
        # Unbatched (M,) teaching current: same signal to every stream.
        # Without this, vmap would consume the class axis as the stream
        # axis — silently wrong whenever M == B.
        teach = jnp.broadcast_to(teach, (x.shape[0], teach.shape[0]))
    step = functools.partial(
        dual_engine_step, tau_m=tau_m, v_th=v_th, v_reset=v_reset,
        trace_decay=trace_decay, w_clip=w_clip, plastic=plastic,
        spiking=spiking)
    if teach is None:
        out = jax.vmap(
            lambda xb, wb, vb, tpb, tqb:
                step(xb, wb, theta, vb, tpb, tqb)
        )(x, w, v, trace_pre, trace_post)
    else:
        out = jax.vmap(
            lambda xb, wb, vb, tpb, tqb, tb:
                step(xb, wb, theta, vb, tpb, tqb, teach=tb)
        )(x, w, v, trace_pre, trace_post, teach)
    if active is not None:
        # Slot gating: select the OLD value wholesale for inactive streams
        # (the same computed-then-selected structure the Pallas kernel
        # uses), so the frozen state is bit-identical, not
        # recomputed-and-close.
        events, v_out, tp_new, w_new = out
        a = active.reshape(-1).astype(bool)
        assert a.shape[0] == x.shape[0], (active.shape, x.shape)
        events = jnp.where(a[:, None], events, jnp.zeros_like(events))
        v_out = jnp.where(a[:, None], v_out, v.astype(v_out.dtype))
        tp_new = jnp.where(a[:, None], tp_new,
                           trace_post.astype(tp_new.dtype))
        w_new = jnp.where(a[:, None, None], w_new, w.astype(w_new.dtype))
        out = (events, v_out, tp_new, w_new)
    if not telemetry:
        return out
    tel = _fleet_telemetry_raw(out[0], out[1], w, out[3], active, v_th=v_th)
    return out + (tel,)


# ---- fixed-point (quantized) oracle ----------------------------------------

def dual_engine_step_q(x, w, scale, theta, v, trace_pre, trace_post, *,
                       qcfg: Q.QuantConfig, v_th: float = 1.0,
                       v_reset: float = 0.0, w_clip: float = 4.0,
                       plastic: bool = True, spiking: bool = True,
                       teach=None, seed=None):
    """Fixed-point oracle (FPGA-faithful datapath; see quant.py for scheme).

    Shapes as the float oracle, but dtypes carry the mode: x (B,N)|(N,)
    int32 fixed point, w (N,M) int8, scale () f32 per-tile weight scale,
    v/traces int32 fixed point, theta (4,N,M) f32, teach int32 fixed point,
    seed () int32 (the session step counter driving the deterministic
    stochastic round).  Returns (events, v_out, trace_post_new, w_new) with
    events/v/trace int32 and w_new int8.

    Every reduction is integer (exact), every float op elementwise — this is
    what the Pallas quant kernel must (and does) match BIT-for-bit.
    """
    scale = jnp.asarray(scale, jnp.float32)
    seed = jnp.asarray(0 if seed is None else seed, jnp.int32)
    acc = jnp.dot(x.astype(jnp.int32), w.astype(jnp.int32))   # exact psum
    i_fx = Q.current_fx(acc, scale, qcfg)
    if teach is not None:
        i_fx = i_fx + teach.astype(jnp.int32)
    events, v_out = Q.neuron_update_q(v.astype(jnp.int32), i_fx, qcfg,
                                      v_th, v_reset, spiking)
    tp_new = Q.trace_update_q(trace_post.astype(jnp.int32), events, qcfg)

    if plastic:
        tpre, tpo = trace_pre.astype(jnp.int32), tp_new
        if tpre.ndim == 1:
            tpre, tpo = tpre[None], tpo[None]
        b = tpre.shape[0]
        hebb_i = jnp.dot(tpre.T, tpo)                         # exact int32
        dw = Q.dw_from_int_reductions(hebb_i, tpre.sum(0), tpo.sum(0),
                                      theta, b, qcfg)
        n, m = w.shape
        idx = (jax.lax.broadcasted_iota(jnp.int32, (n, m), 0) * m
               + jax.lax.broadcasted_iota(jnp.int32, (n, m), 1))
        steps = Q.round_steps(dw / scale, seed, idx, qcfg)
        qmax = Q.qclip(w_clip, scale)
        w_new = jnp.clip(w.astype(jnp.int32) + steps,
                         -qmax, qmax).astype(jnp.int8)
    else:
        w_new = w

    return events, v_out, tp_new, w_new


def dual_engine_fleet_step_q(x, w, scale, theta, v, trace_pre, trace_post, *,
                             qcfg: Q.QuantConfig, v_th: float = 1.0,
                             v_reset: float = 0.0, w_clip: float = 4.0,
                             plastic: bool = True, spiking: bool = True,
                             teach=None, seed=None, active=None,
                             telemetry: bool = False):
    """Fixed-point fleet oracle: int8 per-request weights, per-slot scale.

    Shapes: x (B,N) int32, w (B,N,M) int8, scale (B,) f32, theta (4,N,M)
    f32 shared, v/traces (B,.) int32, teach (B,M)|(M,) int32 | None,
    seed (B,) int32 per-SESSION step counters (slot-independent — the
    stochastic-round stream belongs to the session, which is what makes
    evict -> re-admit-into-any-slot bit-identical), active (B,) | None.

    Defined as vmap of the unbatched quantized step (per-sample dw, shared
    theta), exactly like the float fleet oracle; inactive slots select OLD
    integer state wholesale (bit-frozen trivially — these are ints).
    """
    assert w.ndim == 3 and x.ndim == 2, (x.shape, w.shape)
    b = x.shape[0]
    scale = jnp.asarray(scale, jnp.float32)
    if scale.ndim == 0:
        scale = jnp.broadcast_to(scale, (b,))      # one scale per slot
    seed = (jnp.zeros((b,), jnp.int32) if seed is None
            else jnp.asarray(seed, jnp.int32))
    if seed.ndim == 0:
        seed = jnp.broadcast_to(seed, (b,))        # one seed per session
    if teach is not None and teach.ndim == 1:
        teach = jnp.broadcast_to(teach, (b, teach.shape[0]))
    step = functools.partial(
        dual_engine_step_q, qcfg=qcfg, v_th=v_th, v_reset=v_reset,
        w_clip=w_clip, plastic=plastic, spiking=spiking)
    if teach is None:
        out = jax.vmap(
            lambda xb, wb, sb, vb, tpb, tqb, sd:
                step(xb, wb, sb, theta, vb, tpb, tqb, seed=sd)
        )(x, w, scale, v, trace_pre, trace_post, seed)
    else:
        out = jax.vmap(
            lambda xb, wb, sb, vb, tpb, tqb, sd, tb:
                step(xb, wb, sb, theta, vb, tpb, tqb, seed=sd, teach=tb)
        )(x, w, scale, v, trace_pre, trace_post, seed, teach)
    if active is not None:
        events, v_out, tp_new, w_new = out
        a = active.reshape(-1).astype(bool)
        assert a.shape[0] == b, (active.shape, x.shape)
        events = jnp.where(a[:, None], events, jnp.zeros_like(events))
        v_out = jnp.where(a[:, None], v_out, v.astype(v_out.dtype))
        tp_new = jnp.where(a[:, None], tp_new,
                           trace_post.astype(tp_new.dtype))
        w_new = jnp.where(a[:, None, None], w_new, w)
        out = (events, v_out, tp_new, w_new)
    if not telemetry:
        return out
    tel = _fleet_telemetry_raw(out[0], out[1], w, out[3], active,
                               v_th=v_th, scale=scale, qcfg=qcfg)
    return out + (tel,)
