"""Fused dual-engine Pallas TPU kernel (FireFly-P Secs. III-B/C on TPU).

One kernel invocation = one SNN timestep for one synaptic layer: the Forward
Engine (psum matmul -> neuron dynamics -> trace) AND the Plasticity Engine
(four-term dw) execute on the SAME VMEM-resident weight/coefficient tiles.
This is the hot path behind `core.engine.layer_step` — every network-level
timestep in the product routes here when ``impl="pallas"``.

FPGA -> TPU adaptation (DESIGN.md Sec. 2):
  * psum-stationary PE registers  -> fp32 accumulation inside the MXU dot;
    the full fan-in N is kept resident per tile (controller layers are
    <= a few K wide, so (N, bm) weight tiles fit VMEM comfortably).
  * wide packed {a,b,g,d} fetch   -> theta is ONE (4, N, bm) block => a
    single HBM->VMEM DMA streams all four coefficient planes per tile.
  * dual-engine overlap           -> fusion: w/theta tiles are read once and
    consumed by both engines before leaving VMEM; there is no second pass
    over HBM for the update (the FPGA hides update latency in time, we
    eliminate the traffic instead).

Layer modes mirror the network semantics:
  * ``spiking=True``  — LIF with hard reset; events are binary spikes.
  * ``spiking=False`` — leaky-integrator readout; the event driving the
    postsynaptic trace is ``tanh(V)`` (bounded continuous activity).
  * ``teach``         — optional teaching current added to the psum
    (supervised online learning on the output layer).
  * ``plastic=False`` — the theta/trace_pre operands are dropped entirely;
    no coefficient DMA is issued and weights pass through unchanged.
  * ``active``        — fleet-only (B,) slot mask (session serving): an
    inactive stream's weights/membrane/traces are written back unchanged
    (dw gated, not merely small) and its events are zeroed, so vacated
    slots of a fixed-shape fleet tensor are true no-ops.

Grid: (M // bm,) — one program per block of postsynaptic neurons.  Every
block sees the whole batch and the whole fan-in, so both matmuls (forward
x@w and Hebbian trace_pre^T@trace_post) are single MXU calls per tile.

FLEET MODE (`dual_engine_fleet_step_pallas`): weights carry a leading
request-stream rank (B, N, M) and the grid becomes (cdiv(M, bm),
cdiv(B, block_b)) — one program per block of ``block_b`` streams x
postsynaptic tile, iterating stream blocks INNERMOST so the shared theta
block's index is constant across the whole fleet and the Pallas pipeline's
block-revisit elision fetches each (4, N, bm) coefficient tile from HBM
once per tile, not once per stream block.  The stream axis of every
``(B, ·)`` block is ``block_b`` (a multiple of 8) or the whole fleet, which
is the TPU's sublane tiling rule.  Each stream rewrites its OWN synapses
with a per-sample dw (no batch averaging).  This is the many-user serving
path: B independent plastic memories advance in ONE kernel launch instead
of `vmap` stamping out B launches.

Contractions are written in forms Mosaic lowers on TPU (`psum`,
`shared_hebb`): float32 psums are MXU matmuls at ``Precision.HIGHEST``
(batched ``(bb, 1, N) x (bb, N, bm)`` in fleet mode); fixed-point psums and
Hebbian sums stay exact integer arithmetic — a VPU multiply-reduce per
stream in fleet mode, int8-limb MXU matmuls (`quant.int_matmul`) for shared
weights — because the MXU takes no int32 operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.plasticity import ALPHA, BETA, GAMMA, DELTA
from repro.kernels.plasticity import quant as Q
from repro.obs.telemetry import sat_threshold, sat_threshold_q


HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_B = 8     # fleet streams per per-step grid program (one sublane tile)


def psum(x, w, *, fleet: bool, quant: bool):
    """Forward-engine psum ``x @ w`` on a resident weight tile.

    Shared weights: x (B, N) @ w (N, bm).  Fleet: x (bb, N) against each
    stream's own w (bb, N, bm).  Float32 runs on the MXU at full float32
    precision; the fixed-point psum is exact int32 (``w`` int8 or int32
    holding int8-range values).
    """
    if quant:
        if fleet:   # one row per stream: integer multiply-reduce on the VPU
            return jnp.sum(x[:, :, None] * w.astype(jnp.int32), axis=1)
        return Q.int_matmul(x, w.astype(jnp.int8))
    if fleet:
        return jnp.einsum("bqn,bnm->bqm", x[:, None, :], w,
                          preferred_element_type=jnp.float32,
                          precision=HIGHEST)[:, 0]
    return jnp.dot(x, w, preferred_element_type=jnp.float32,
                   precision=HIGHEST)


def shared_hebb(tpre, tpost, *, quant: bool):
    """Unnormalized Hebbian sum ``trace_pre^T @ trace_post`` over the batch
    (shared weights): exact int32 in fixed point, float32 otherwise."""
    if quant:
        return Q.int_matmul(tpre, tpost, contract_lhs=0)
    return jnp.dot(tpre.T, tpost, preferred_element_type=jnp.float32,
                   precision=HIGHEST)


def stream_block(block_b: int, b: int, interpret: bool) -> int:
    """Streams per fleet grid program: ``min(block_b, B)``.  On the TPU a
    block's stream axis is its second-minor dim, which must be a multiple
    of 8 or the whole fleet; the interpreter takes any size."""
    bb = min(block_b, b)
    if not interpret and bb != b and bb % 8:
        raise ValueError(f"block_b={block_b} must be a multiple of 8 (or "
                         f">= the fleet size {b}) for the TPU's tiling")
    return bb


def _forward_engine(current, v_ref, tpost_ref, teach_ref, s_out, v_out,
                    tpost_out, *, tau_m, v_th, v_reset, trace_decay,
                    spiking, gate=None):
    """Shared Forward Engine body: psum -> neuron dynamics -> trace update.

    Used verbatim by BOTH the shared-weight and the fleet kernel so the
    LIF/readout/trace math cannot diverge between them; takes the psum
    ``current`` and returns the fresh postsynaptic trace the Plasticity
    Engine consumes.

    ``gate`` (fleet serving only) is the stream block's (bb, 1) active
    flag: where false the membrane and trace writes select the OLD values
    and the event output is zeroed — the slot is frozen bit-exactly, which
    is the `active`-mask contract fixed-shape continuous batching relies on.
    """
    if teach_ref is not None:
        current = current + teach_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    v_new = v + (current - v) * (1.0 / tau_m)   # leaky integration, tau_m = 2
    if spiking:
        spikes = (v_new >= v_th).astype(jnp.float32)
        v_upd = jnp.where(spikes > 0, v_reset, v_new)
    else:                                        # non-spiking leaky readout
        spikes = jnp.tanh(v_new)
        v_upd = v_new
    tpost = tpost_ref[...].astype(jnp.float32)
    tpost_new = trace_decay * tpost + spikes    # Trace Update Unit

    if gate is not None:
        spikes = jnp.where(gate, spikes, jnp.zeros_like(spikes))
        v_upd = jnp.where(gate, v_upd, v)
        tpost_new = jnp.where(gate, tpost_new, tpost)
    s_out[...] = spikes.astype(s_out.dtype)
    v_out[...] = v_upd.astype(v_out.dtype)
    tpost_out[...] = tpost_new.astype(tpost_out.dtype)
    return tpost_new


def columns3(a, b, c):
    """Three (bb, 1) columns -> one (bb, 3) row block, by lane select:
    Mosaic lowers that, not a concatenate of width-1 pieces."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (a.shape[0], 3), 1)
    return jnp.where(lane == 0, a, jnp.where(lane == 1, b, c))


def _tile_telemetry(events, sat, dw_abs, gate, *, bm, m_total,
                    ev_unit=None, dw_unit=None):
    """Per-tile telemetry partials of a stream block -> (bb, 3) float32
    [sum |events|, sum |dw|, saturated-membrane count], gated like the
    state writes.

    Columns past M are masked: a ragged final tile's padding lanes hold
    whatever the pipeline faulted in (NaN under interpret) and must not
    reach the reductions.  ``ev_unit`` / ``dw_unit`` rescale the event
    and |dw| sums to float units (fixed point: 1/one and the per-slot
    weight scale).
    """
    col_ok = (jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1)
              + pl.program_id(0) * bm) < m_total
    spike_sum = jnp.sum(jnp.where(col_ok, jnp.abs(events), 0.0), axis=1,
                        keepdims=True)
    if ev_unit is not None:
        spike_sum = spike_sum * ev_unit
    dw_sum = jnp.sum(jnp.sum(jnp.where(col_ok[None], dw_abs, 0.0), axis=2),
                     axis=1, keepdims=True)
    if dw_unit is not None:
        dw_sum = dw_sum * dw_unit
    sat_cnt = jnp.sum(jnp.where(col_ok & sat, 1.0, 0.0), axis=1,
                      keepdims=True)
    row = columns3(spike_sum, dw_sum, sat_cnt)
    if gate is not None:
        row = row * gate.astype(jnp.float32)
    return row


def _dual_engine_kernel(x_ref, w_ref, v_ref, tpost_ref, *refs,
                        tau_m, v_th, v_reset, trace_decay, w_clip,
                        plastic, spiking, has_teach, batch):
    # Optional operands, in order: theta/tpre (plastic), teach.
    rest = list(refs)
    theta_ref = rest.pop(0) if plastic else None
    tpre_ref = rest.pop(0) if plastic else None
    teach_ref = rest.pop(0) if has_teach else None
    s_out, v_out, tpost_out, w_out = rest

    # ---- Forward Engine ----------------------------------------------------
    x = x_ref[...].astype(jnp.float32)          # (B, N)
    w = w_ref[...].astype(jnp.float32)          # (N, bm)
    tpost_new = _forward_engine(
        psum(x, w, fleet=False, quant=False), v_ref, tpost_ref, teach_ref,
        s_out, v_out, tpost_out, tau_m=tau_m, v_th=v_th, v_reset=v_reset,
        trace_decay=trace_decay, spiking=spiking)

    # ---- Plasticity Engine (same tiles, still in VMEM) ---------------------
    if plastic:
        th = theta_ref[...].astype(jnp.float32)  # (4, N, bm) single wide fetch
        tpre = tpre_ref[...].astype(jnp.float32)  # (B, N)
        hebb = shared_hebb(tpre, tpost_new, quant=False) / batch
        pre_m = jnp.mean(tpre, axis=0)           # (N,)
        post_m = jnp.mean(tpost_new, axis=0)     # (bm,)
        dw = (th[ALPHA] * hebb + th[BETA] * pre_m[:, None]
              + th[GAMMA] * post_m[None, :] + th[DELTA])
        w_new = jnp.clip(w + dw, -w_clip, w_clip)
        w_out[...] = w_new.astype(w_out.dtype)
    else:
        w_out[...] = w.astype(w_out.dtype)


def dual_engine_step_pallas(x, w, theta, v, trace_pre, trace_post, *,
                            tau_m: float = 2.0, v_th: float = 1.0,
                            v_reset: float = 0.0, trace_decay: float = 0.8,
                            w_clip: float = 4.0, plastic: bool = True,
                            spiking: bool = True, teach=None,
                            block_m: int = 128, interpret: bool = False):
    """Pallas-call wrapper.  Shapes as in ref.dual_engine_step (batched)."""
    b, n = x.shape
    n2, m = w.shape
    assert n == n2, (x.shape, w.shape)
    bm = min(block_m, m)
    grid = (pl.cdiv(m, bm),)
    has_teach = teach is not None

    kernel = functools.partial(
        _dual_engine_kernel, tau_m=tau_m, v_th=v_th, v_reset=v_reset,
        trace_decay=trace_decay, w_clip=w_clip, plastic=plastic,
        spiking=spiking, has_teach=has_teach, batch=b)

    in_specs = [
        pl.BlockSpec((b, n), lambda j: (0, 0)),        # x: full batch/fan-in
        pl.BlockSpec((n, bm), lambda j: (0, j)),       # w tile
        pl.BlockSpec((b, bm), lambda j: (0, j)),       # v tile
        pl.BlockSpec((b, bm), lambda j: (0, j)),       # post trace tile
    ]
    operands = [x, w, v, trace_post]
    if plastic:
        in_specs += [
            pl.BlockSpec((4, n, bm), lambda j: (0, 0, j)),  # packed theta
            pl.BlockSpec((b, n), lambda j: (0, 0)),         # pre trace
        ]
        operands += [theta, trace_pre]
    if has_teach:
        in_specs.append(pl.BlockSpec((b, bm), lambda j: (0, j)))
        operands.append(teach)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b, bm), lambda j: (0, j)),       # events
            pl.BlockSpec((b, bm), lambda j: (0, j)),       # v out
            pl.BlockSpec((b, bm), lambda j: (0, j)),       # post trace out
            pl.BlockSpec((n, bm), lambda j: (0, j)),       # w out
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m), x.dtype),
            jax.ShapeDtypeStruct((b, m), v.dtype),
            jax.ShapeDtypeStruct((b, m), trace_post.dtype),
            jax.ShapeDtypeStruct((n, m), w.dtype),
        ],
        interpret=interpret,
        name="plasticity_step",
    )(*operands)


def _fleet_kernel(x_ref, w_ref, v_ref, tpost_ref, *refs,
                  tau_m, v_th, v_reset, trace_decay, w_clip,
                  plastic, spiking, has_teach, has_active, telemetry,
                  m_total, bm):
    """One program = one block of bb request streams x one postsynaptic
    tile.

    Per-sample semantics throughout: the Hebbian term is the outer product
    of EACH stream's own traces (no batch averaging) and every rewritten
    weight tile belongs to its stream alone; streams never interact, so a
    block of them is bit-identical to running them one by one.  With
    ``has_active`` each stream's slot flag gates every state write
    (weights, membrane, traces frozen; events zeroed) so vacated fleet
    slots are true no-ops.

    ``telemetry`` appends a per-tile (1, bb, 3) partial-sums output —
    [sum |events|, sum |dw|, saturated-membrane count], gated like the
    state writes — which the wrapper reduces over tiles to the raw (B, 3)
    row of `obs.telemetry`.  Computed from the already-written output
    tiles while they are still VMEM-resident: the telemetry variant adds
    three register reductions per program, never a second pass over HBM.
    """
    rest = list(refs)
    theta_ref = rest.pop(0) if plastic else None
    tpre_ref = rest.pop(0) if plastic else None
    teach_ref = rest.pop(0) if has_teach else None
    active_ref = rest.pop(0) if has_active else None
    tel_out = rest.pop() if telemetry else None
    s_out, v_out, tpost_out, w_out = rest
    gate = None if active_ref is None else active_ref[...] > 0    # (bb, 1)

    # ---- Forward Engine ----------------------------------------------------
    x = x_ref[...].astype(jnp.float32)           # (bb, N) streams' events
    w = w_ref[...].astype(jnp.float32)           # (bb, N, bm) own tiles
    tpost_new = _forward_engine(                 # (bb, bm); gated if inactive
        psum(x, w, fleet=True, quant=False), v_ref, tpost_ref, teach_ref,
        s_out, v_out, tpost_out, tau_m=tau_m, v_th=v_th, v_reset=v_reset,
        trace_decay=trace_decay, spiking=spiking, gate=gate)

    # ---- Plasticity Engine (same stream-resident tiles) --------------------
    if plastic:
        th = theta_ref[...].astype(jnp.float32)   # (4, N, bm) SHARED rule
        tpre = tpre_ref[...].astype(jnp.float32)[:, :, None]   # (bb, N, 1)
        tpost_b = tpost_new[:, None, :]                        # (bb, 1, bm)
        hebb = tpre * tpost_b                     # (bb, N, bm) outer products
        dw = (th[ALPHA] * hebb + th[BETA] * tpre
              + th[GAMMA] * tpost_b + th[DELTA])
        w_new = jnp.clip(w + dw, -w_clip, w_clip)
        if gate is not None:
            w_new = jnp.where(gate[:, :, None], w_new, w)  # dw gated: frozen
        w_out[...] = w_new.astype(w_out.dtype)
    else:
        w_new = w
        w_out[...] = w.astype(w_out.dtype)

    if telemetry:
        sat = jnp.abs(v_out[...].astype(jnp.float32)) >= sat_threshold(v_th)
        tel_out[0] = _tile_telemetry(
            s_out[...].astype(jnp.float32), sat, jnp.abs(w_new - w), gate,
            bm=bm, m_total=m_total)


def _fleet_specs(n, bm, bb, *, plastic, has_teach, has_active, quant):
    """Input block specs of the fleet kernels over the (tiles, stream
    blocks) grid.  Every ``(B, ·)`` operand is blocked ``bb`` streams at a
    time; the per-stream scalars (active flag, weight scale, seed) ride as
    ``(B, 1)`` columns.  Operand order: x, w, [scale], v, post trace,
    [seed], [theta, pre trace], [teach], [active]."""
    row = lambda width: pl.BlockSpec((bb, width), lambda j, i: (i, 0))
    tile = pl.BlockSpec((bb, bm), lambda j, i: (i, j))
    col = pl.BlockSpec((bb, 1), lambda j, i: (i, 0))
    specs = [row(n), pl.BlockSpec((bb, n, bm), lambda j, i: (i, 0, j))]
    if quant:
        specs.append(col)                                   # per-slot scale
    specs += [tile, tile]                                   # v, post trace
    if quant:
        specs.append(col)                                   # per-session seed
    if plastic:
        # Shared packed theta: every stream block's program indexes the
        # SAME (4, N, bm) block — the rule is never materialized per
        # stream (the vmap batching rule broadcasts it to (B, 4, N, M)).
        specs += [pl.BlockSpec((4, n, bm), lambda j, i: (0, 0, j)), row(n)]
    if has_teach:
        specs.append(tile)
    if has_active:
        specs.append(col)
    return specs


def _fleet_out(b, n, m, bm, bb, *, s_dtype, v_dtype, tp_dtype, w_dtype,
               telemetry):
    tile = pl.BlockSpec((bb, bm), lambda j, i: (i, j))
    out_specs = [tile, tile, tile,
                 pl.BlockSpec((bb, n, bm), lambda j, i: (i, 0, j))]
    out_shape = [jax.ShapeDtypeStruct((b, m), s_dtype),
                 jax.ShapeDtypeStruct((b, m), v_dtype),
                 jax.ShapeDtypeStruct((b, m), tp_dtype),
                 jax.ShapeDtypeStruct((b, n, m), w_dtype)]
    if telemetry:
        # Per-tile partial sums, tile-major so the stream block stays the
        # second-minor dim; each program writes its own (j, i) block.
        out_specs.append(pl.BlockSpec((1, bb, 3), lambda j, i: (j, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((pl.cdiv(m, bm), b, 3), jnp.float32))
    return out_specs, out_shape


def _fleet_call(kernel, operands, in_specs, out_specs, out_shape, *, grid,
                telemetry, interpret, name):
    res = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name=name)(*operands)
    if not telemetry:
        return res
    # Fold the tile axis of the partials -> raw (B, 3) telemetry row.
    return tuple(res[:4]) + (res[4].sum(axis=0),)


def _fleet_operands(b, teach, active):
    if teach is not None and teach.ndim == 1:
        # unbatched (M,) teach: same signal to every stream (see ref)
        teach = jnp.broadcast_to(teach, (b, teach.shape[0]))
    if active is not None:
        # (B,) -> (B, 1): each program reads its stream block's flags as a
        # (bb, 1) column indexed by the stream-block grid coordinate.
        active = active.reshape(b, 1).astype(jnp.float32)
    return teach, active


def dual_engine_fleet_step_pallas(x, w, theta, v, trace_pre, trace_post, *,
                                  tau_m: float = 2.0, v_th: float = 1.0,
                                  v_reset: float = 0.0,
                                  trace_decay: float = 0.8,
                                  w_clip: float = 4.0, plastic: bool = True,
                                  spiking: bool = True, teach=None,
                                  active=None, telemetry: bool = False,
                                  block_m: int = 128,
                                  interpret: bool = False):
    """Fleet pallas-call wrapper.  Shapes as in ref.dual_engine_fleet_step:
    x (B,N), w (B,N,M) per-request, theta (4,N,M) shared, v/traces (B,·),
    active (B,) slot mask (inactive slots frozen bit-exactly, events zero).
    `BLOCK_B` streams share one grid program (the whole fleet when B is
    smaller).

    ``telemetry`` appends a raw (B, 3) float32 per-slot sums output (the
    `obs.telemetry` schema): the kernel emits per-tile partials into a
    (tiles, B, 3) buffer — each grid program owns its own block, so no
    cross-program accumulation is assumed — and the wrapper folds the tile
    axis.  A static flag: off-trace is byte-identical to the 4-output
    program."""
    b, n = x.shape
    b2, n2, m = w.shape
    assert (b, n) == (b2, n2), (x.shape, w.shape)
    teach, active = _fleet_operands(b, teach, active)
    bm, bb = min(block_m, m), stream_block(BLOCK_B, b, interpret)
    # Stream blocks iterate INNERMOST (grid dim 1): the shared theta
    # block's index map is constant in the stream index, so consecutive
    # grid steps revisit the same coefficient tile and Pallas elides the
    # re-DMA — one theta fetch per tile for the whole fleet.
    grid = (pl.cdiv(m, bm), pl.cdiv(b, bb))
    kernel = functools.partial(
        _fleet_kernel, tau_m=tau_m, v_th=v_th, v_reset=v_reset,
        trace_decay=trace_decay, w_clip=w_clip, plastic=plastic,
        spiking=spiking, has_teach=teach is not None,
        has_active=active is not None, telemetry=telemetry, m_total=m,
        bm=bm)
    in_specs = _fleet_specs(n, bm, bb, plastic=plastic,
                            has_teach=teach is not None,
                            has_active=active is not None, quant=False)
    operands = [x, w, v, trace_post]
    if plastic:
        operands += [theta, trace_pre]
    operands += [a for a in (teach, active) if a is not None]
    out_specs, out_shape = _fleet_out(
        b, n, m, bm, bb, s_dtype=x.dtype, v_dtype=v.dtype,
        tp_dtype=trace_post.dtype, w_dtype=w.dtype, telemetry=telemetry)
    return _fleet_call(kernel, operands, in_specs, out_specs, out_shape,
                       grid=grid, telemetry=telemetry, interpret=interpret,
                       name="plasticity_fleet_step")


# ---- fixed-point (quantized) kernels ---------------------------------------
#
# FPGA-faithful datapath (scheme in quant.py, docstring in ops.py): the
# weight pool stays int8 in HBM and is promoted IN REGISTERS/VMEM only —
# an int8 fleet pool holds ~4x more resident sessions per byte of HBM than
# the float32 pool.  Both quant kernels call the SAME quant.py helpers as
# the oracle, and every reduction is an integer reduction (exact, order
# independent), so xla vs pallas(-interpret) parity is BIT equality on the
# int32/int8 outputs, not an allclose.


def _forward_engine_q(acc, scale, v_ref, tpost_ref, teach_ref,
                      s_out, v_out, tpost_out, *, qcfg, v_th, v_reset,
                      spiking, gate=None):
    """Quantized Forward Engine body (shared + fleet): exact integer psum
    ``acc`` -> integer neuron dynamics -> integer trace update.  Returns
    the fresh postsynaptic trace (int32) the Plasticity Engine consumes."""
    i_fx = Q.current_fx(acc, scale, qcfg)
    if teach_ref is not None:
        i_fx = i_fx + teach_ref[...].astype(jnp.int32)
    v = v_ref[...].astype(jnp.int32)
    events, v_upd = Q.neuron_update_q(v, i_fx, qcfg, v_th, v_reset, spiking)
    tpost = tpost_ref[...].astype(jnp.int32)
    tpost_new = Q.trace_update_q(tpost, events, qcfg)
    if gate is not None:
        events = jnp.where(gate, events, jnp.zeros_like(events))
        v_upd = jnp.where(gate, v_upd, v)
        tpost_new = jnp.where(gate, tpost_new, tpost)
    s_out[...] = events.astype(s_out.dtype)
    v_out[...] = v_upd.astype(v_out.dtype)
    tpost_out[...] = tpost_new.astype(tpost_out.dtype)
    return tpost_new


def _tile_flat_idx(n, bm, j, m_total):
    """Flat (row * M + col) index of this (n, bm) weight tile — the GLOBAL
    per-matrix index the deterministic stochastic round hashes, identical
    to the oracle's full-matrix iota (slot-independent in fleet mode)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, bm), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, bm), 1) + j * bm
    return rows * m_total + cols


def _dual_engine_kernel_q(x_ref, w_ref, scale_ref, v_ref, tpost_ref,
                          seed_ref, *refs, qcfg, v_th, v_reset, w_clip,
                          plastic, spiking, has_teach, batch, m_total, bm):
    rest = list(refs)
    theta_ref = rest.pop(0) if plastic else None
    tpre_ref = rest.pop(0) if plastic else None
    teach_ref = rest.pop(0) if has_teach else None
    s_out, v_out, tpost_out, w_out = rest
    scale = scale_ref[0, 0]
    seed = seed_ref[0, 0]

    x = x_ref[...].astype(jnp.int32)            # (B, N) fixed point
    w_q = w_ref[...]                            # (N, bm) int8
    w_i32 = w_q.astype(jnp.int32)               # int8 -> registers
    tpost_new = _forward_engine_q(
        psum(x, w_q, fleet=False, quant=True), scale, v_ref, tpost_ref,
        teach_ref, s_out, v_out, tpost_out, qcfg=qcfg, v_th=v_th,
        v_reset=v_reset, spiking=spiking)

    if plastic:
        tpre = tpre_ref[...].astype(jnp.int32)  # (B, N)
        hebb_i = shared_hebb(tpre, tpost_new, quant=True)      # exact
        dw = Q.dw_from_int_reductions(
            hebb_i, tpre.sum(0), tpost_new.sum(0),
            theta_ref[...].astype(jnp.float32), batch, qcfg)
        idx = _tile_flat_idx(tpre.shape[1], bm, pl.program_id(0), m_total)
        steps = Q.round_steps(dw / scale, seed, idx, qcfg)
        qmax = Q.qclip(w_clip, scale)
        w_out[...] = jnp.clip(w_i32 + steps, -qmax, qmax).astype(w_out.dtype)
    else:
        w_out[...] = w_i32.astype(w_out.dtype)


def dual_engine_step_q_pallas(x, w, scale, theta, v, trace_pre, trace_post,
                              *, qcfg, v_th: float = 1.0,
                              v_reset: float = 0.0, w_clip: float = 4.0,
                              plastic: bool = True, spiking: bool = True,
                              teach=None, seed=None, block_m: int = 128,
                              interpret: bool = False):
    """Quantized shared-weight pallas-call.  Shapes/dtypes as in
    ref.dual_engine_step_q (batched): x/v/traces int32 fixed point, w int8,
    scale () f32, seed () int32."""
    b, n = x.shape
    n2, m = w.shape
    assert n == n2, (x.shape, w.shape)
    bm = min(block_m, m)
    grid = (pl.cdiv(m, bm),)
    has_teach = teach is not None
    scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    seed = jnp.asarray(0 if seed is None else seed, jnp.int32).reshape(1, 1)

    kernel = functools.partial(
        _dual_engine_kernel_q, qcfg=qcfg, v_th=v_th, v_reset=v_reset,
        w_clip=w_clip, plastic=plastic, spiking=spiking,
        has_teach=has_teach, batch=b, m_total=m, bm=bm)

    in_specs = [
        pl.BlockSpec((b, n), lambda j: (0, 0)),        # x: full batch/fan-in
        pl.BlockSpec((n, bm), lambda j: (0, j)),       # int8 w tile
        pl.BlockSpec((1, 1), lambda j: (0, 0)),        # per-tile scale
        pl.BlockSpec((b, bm), lambda j: (0, j)),       # v tile
        pl.BlockSpec((b, bm), lambda j: (0, j)),       # post trace tile
        pl.BlockSpec((1, 1), lambda j: (0, 0)),        # stochastic-round seed
    ]
    operands = [x, w, scale, v, trace_post, seed]
    if plastic:
        in_specs += [
            pl.BlockSpec((4, n, bm), lambda j: (0, 0, j)),  # packed theta
            pl.BlockSpec((b, n), lambda j: (0, 0)),         # pre trace
        ]
        operands += [theta, trace_pre]
    if has_teach:
        in_specs.append(pl.BlockSpec((b, bm), lambda j: (0, j)))
        operands.append(teach)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b, bm), lambda j: (0, j)),       # events (int32)
            pl.BlockSpec((b, bm), lambda j: (0, j)),       # v out (int32)
            pl.BlockSpec((b, bm), lambda j: (0, j)),       # post trace (int32)
            pl.BlockSpec((n, bm), lambda j: (0, j)),       # w out (int8)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m), jnp.int32),
            jax.ShapeDtypeStruct((b, m), jnp.int32),
            jax.ShapeDtypeStruct((b, m), jnp.int32),
            jax.ShapeDtypeStruct((n, m), jnp.int8),
        ],
        interpret=interpret,
        name="plasticity_step_q",
    )(*operands)


def _fleet_kernel_q(x_ref, w_ref, scale_ref, v_ref, tpost_ref, seed_ref,
                    *refs, qcfg, v_th, v_reset, w_clip, plastic, spiking,
                    has_teach, has_active, m_total, bm, telemetry):
    """Quantized fleet program: one block of bb request streams x one
    postsynaptic tile.

    Each stream's int8 weight tile is promoted to int32 in registers (the
    (B, N, M) pool never leaves HBM as anything but int8); each stream's
    per-SESSION seed drives the stochastic round with the same
    slot-independent flat index the oracle uses, so a session's update
    stream is invariant to which slot it occupies."""
    rest = list(refs)
    theta_ref = rest.pop(0) if plastic else None
    tpre_ref = rest.pop(0) if plastic else None
    teach_ref = rest.pop(0) if has_teach else None
    active_ref = rest.pop(0) if has_active else None
    tel_out = rest.pop() if telemetry else None
    s_out, v_out, tpost_out, w_out = rest
    gate = None if active_ref is None else active_ref[...] > 0    # (bb, 1)
    scale = scale_ref[...]                      # (bb, 1) per-slot scale
    seed = seed_ref[...]                        # (bb, 1) per-session seed

    x = x_ref[...].astype(jnp.int32)            # (bb, N) streams' events
    w_i32 = w_ref[...].astype(jnp.int32)        # (bb, N, bm) int8 -> regs
    tpost_new = _forward_engine_q(
        psum(x, w_i32, fleet=True, quant=True), scale, v_ref, tpost_ref,
        teach_ref, s_out, v_out, tpost_out, qcfg=qcfg, v_th=v_th,
        v_reset=v_reset, spiking=spiking, gate=gate)

    if plastic:
        tpre = tpre_ref[...].astype(jnp.int32)  # (bb, N)
        hebb_i = tpre[:, :, None] * tpost_new[:, None, :]   # exact int outer
        dw = Q.dw_from_int_reductions(
            hebb_i, tpre, tpost_new, theta_ref[...].astype(jnp.float32), 1,
            qcfg)                                # (bb, N, bm)
        idx = _tile_flat_idx(tpre.shape[1], bm, pl.program_id(0), m_total)
        steps = Q.round_steps(dw / scale[:, :, None], seed[:, :, None], idx,
                              qcfg)
        qmax = Q.qclip(w_clip, scale)[:, :, None]
        w_new = jnp.clip(w_i32 + steps, -qmax, qmax)
        if gate is not None:
            w_new = jnp.where(gate[:, :, None], w_new, w_i32)  # dw gated
        w_out[...] = w_new.astype(w_out.dtype)
    else:
        w_new = w_i32
        w_out[...] = w_i32.astype(w_out.dtype)

    if telemetry:
        # Raw sums in the SAME units as the float datapath: 0/`one` events
        # divided back to event units, |dw| in int8 grid steps x scale.
        sat = (jnp.abs(v_out[...].astype(jnp.int32))
               >= sat_threshold_q(v_th, qcfg))
        tel_out[0] = _tile_telemetry(
            s_out[...].astype(jnp.float32), sat,
            jnp.abs(w_new - w_i32).astype(jnp.float32), gate, bm=bm,
            m_total=m_total, ev_unit=1.0 / qcfg.one, dw_unit=scale)


def dual_engine_fleet_step_q_pallas(x, w, scale, theta, v, trace_pre,
                                    trace_post, *, qcfg, v_th: float = 1.0,
                                    v_reset: float = 0.0, w_clip: float = 4.0,
                                    plastic: bool = True, spiking: bool = True,
                                    teach=None, seed=None, active=None,
                                    telemetry: bool = False,
                                    block_m: int = 128,
                                    interpret: bool = False):
    """Quantized fleet pallas-call.  Shapes as ref.dual_engine_fleet_step_q:
    x (B,N) int32, w (B,N,M) int8 (stays int8 in HBM), scale (B,) f32 per
    slot, theta (4,N,M) f32 shared, v/traces (B,.) int32, seed (B,) int32
    per-session step counters, active (B,) slot mask.  ``telemetry``
    appends the raw (B, 3) float32 per-slot sums (obs.telemetry schema,
    float units) exactly like the float fleet wrapper."""
    b, n = x.shape
    b2, n2, m = w.shape
    assert (b, n) == (b2, n2), (x.shape, w.shape)
    teach, active = _fleet_operands(b, teach, active)
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (b,))
    seed = jnp.broadcast_to(jnp.asarray(0 if seed is None else seed,
                                        jnp.int32), (b,))
    bm, bb = min(block_m, m), stream_block(BLOCK_B, b, interpret)
    grid = (pl.cdiv(m, bm), pl.cdiv(b, bb))  # stream blocks innermost
    kernel = functools.partial(
        _fleet_kernel_q, qcfg=qcfg, v_th=v_th, v_reset=v_reset,
        w_clip=w_clip, plastic=plastic, spiking=spiking,
        has_teach=teach is not None, has_active=active is not None,
        m_total=m, bm=bm, telemetry=telemetry)
    in_specs = _fleet_specs(n, bm, bb, plastic=plastic,
                            has_teach=teach is not None,
                            has_active=active is not None, quant=True)
    operands = [x, w, scale.reshape(b, 1), v, trace_post,
                seed.reshape(b, 1)]
    if plastic:
        operands += [theta, trace_pre]
    operands += [a for a in (teach, active) if a is not None]
    out_specs, out_shape = _fleet_out(
        b, n, m, bm, bb, s_dtype=jnp.int32, v_dtype=jnp.int32,
        tp_dtype=jnp.int32, w_dtype=jnp.int8, telemetry=telemetry)
    return _fleet_call(kernel, operands, in_specs, out_specs, out_shape,
                       grid=grid, telemetry=telemetry, interpret=interpret,
                       name="plasticity_fleet_step_q")
