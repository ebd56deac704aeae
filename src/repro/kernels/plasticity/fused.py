"""Time-fused rollout megakernel: K timesteps x N layers in ONE pallas_call.

The per-step kernels in `kernel.py` are faithful to the FPGA's dual-engine
*datapath*, but not to its *schedule*: FireFly-P streams every timestep
through a single pipeline whose membranes and traces never leave on-chip
BRAM, while the per-step path issues one `pallas_call` per layer per
timestep — K * L launches per control window, each re-reading and
re-writing the full state through HBM.  `benchmarks/results/
fleet_throughput.json` shows the cost: per-launch overhead collapses fleet
throughput super-linearly with B.  FireFly v2 (arXiv:2309.16158) fixes the
same problem in hardware with spatiotemporal fusion; this module is the
Pallas analogue.

One `rollout_pallas` call executes the ENTIRE window:

  * weights, membranes, and all L+1 population traces are loaded into
    VMEM/registers ONCE per grid program and written back ONCE — dw
    accumulates locally across all K steps (HBM traffic is K-independent);
  * the inter-layer event bus (layer i's spikes feeding layer i+1) is a
    register value, never a memory round-trip;
  * the K input drives / teach rows and the K readout rows are the only
    time-major staging buffers, streamed through the same block.

Modes — the same body serves all four datapaths:

  * SHARED weights (w (N, M), batched activations, batch-averaged dw):
    grid (1,), the whole batch in one program.
  * FLEET (w (B, N, M), per-sample dw, shared theta, optional `active`
    slot mask): grid (cdiv(B, block_b),) — `block_b` request streams per
    program, the stream axis carried *inside* the block (one batched
    matmul forward, broadcast outer-product Hebbian); on TPU ``block_b``
    is a multiple of 8 or the whole fleet (the sublane tiling rule).  It
    divides the per-grid-iteration overhead by block_b while computing each
    stream independently (streams never interact).
  * float32 and the PR-4 fixed-point datapath (int8 weights promoted to
    int32 registers for the window, int32 membranes/traces, deterministic
    stochastic rounding seeded per session and per STEP: step k of the
    window draws from ``fold_seed(base_seed + k, layer)`` — exactly the
    per-step kernels' seed sequence, so evict -> re-admit mid-window stays
    bit-identical).

Time iteration: `unroll_k` chunks the K-step loop — steps run in a
`lax.fori_loop` over chunks of `unroll_k` fully-unrolled steps (1 = rolled
loop, 0 or >= K = full unroll).  On the fixed-point datapath every setting
computes identical bits (integer arithmetic is association-free).  On
float32 the BIT-PINNED setting is the default ``unroll_k=1``: each loop
body holds exactly one timestep, matching the scanned oracle's computation
boundaries, so parity with `engine.rollout(impl="xla")` is bit equality at
controller-scale layer widths (tests/test_fused.py pins it).  Two float
caveats, both ULP-level (~1e-7) and both inherent FMA-contraction freedom
rather than kernel drift: unrolling several steps into one body lets XLA
contract FMAs ACROSS steps, and at wide layers (~64+) XLA may contract
the dw chain differently in the two programs even at ``unroll_k=1`` (the
same freedom the per-step float kernels have always had — their parity
tests are tolerance-based).  Where bit-reproducibility must be
unconditional, the fixed-point datapath is the contract.

No postsynaptic tiling: layer i+1's forward pass needs ALL of layer i's
output events, so a fused program must hold every layer's full (N_i, M_i)
extent — `block_m` does not apply here.  The VMEM budget is therefore
per-program working set
``block_b * sum_i(5 * N_i * M_i) * 4B  +  K * (N_0 + M_L) * block_b * 4B``
(w + 4 theta planes dominate); pick block_b/K to fit ~16 MB on real TPUs.
Bit-parity (K=1 vs the per-step kernels, K>1 vs the scanned xla oracle in
`engine.rollout`) is pinned by tests/test_fused.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.plasticity import ALPHA, BETA, GAMMA, DELTA
from repro.kernels.plasticity import quant as Q
from repro.kernels.plasticity import kernel as _K
from repro.obs.telemetry import sat_threshold, sat_threshold_q


def _rollout_kernel(*refs, n_layers, k_steps, spiking, plastic, fleet,
                    batch, tau_m, v_th, v_reset, trace_decay, w_clip, qcfg,
                    has_teach, has_active, unroll_k, telemetry):
    """One grid program = the FULL K-step window for its block of streams
    (fleet) or the whole batch (shared weights).

    ``telemetry`` (fleet only) extends the time-loop carry with a (bb, 2)
    [spike, saturation] accumulator and appends one finalized (bb, 3)
    output — the per-slot MEANS of `obs.FleetTelemetry`.  The |dw| column
    is NET weight motion |w_end - w_start| from the already-resident
    carry, not a per-step accumulation: per-step deltas would add a
    (bb, N, M) reduction to every iteration of the hot loop (and on the
    fixed-point grid per-step dw is mostly sub-quantum noise anyway),
    while net motion costs one reduction per WINDOW on registers the
    write-back touches regardless.
    """
    it = iter(refs)
    drives_ref = next(it)
    w_refs = [next(it) for _ in range(n_layers)]
    th_refs = [next(it) if plastic[i] else None for i in range(n_layers)]
    v_refs = [next(it) for _ in range(n_layers)]
    tr_refs = [next(it) for _ in range(n_layers + 1)]
    teach_ref = next(it) if has_teach else None
    active_ref = next(it) if has_active else None
    if qcfg is not None:
        scale_refs = [next(it) for _ in range(n_layers)]
        seed_ref = next(it)
    out_ref = next(it)
    w_outs = [next(it) for _ in range(n_layers)]
    v_outs = [next(it) for _ in range(n_layers)]
    tr_outs = [next(it) for _ in range(n_layers + 1)]
    tel_out = next(it) if telemetry else None

    compute = jnp.float32 if qcfg is None else jnp.int32
    # Load the window's whole working set ONCE: weight tiles, membranes and
    # every population trace stay VMEM/register-resident across all K steps
    # (the paper's on-chip state residency); HBM sees one read and one
    # write per state tensor regardless of K.
    ws0 = tuple(w_refs[i][...].astype(compute) for i in range(n_layers))
    vs0 = tuple(v_refs[i][...].astype(compute) for i in range(n_layers))
    trs0 = tuple(tr_refs[i][...].astype(compute)
                 for i in range(n_layers + 1))
    ths = [None if th_refs[i] is None
           else th_refs[i][...].astype(jnp.float32) for i in range(n_layers)]
    gate = None if active_ref is None else active_ref[...] > 0   # (bb, 1)
    if qcfg is not None:
        if fleet:
            scales = [scale_refs[i][...] for i in range(n_layers)]  # (bb, 1)
            base_seed = seed_ref[...]                               # (bb, 1)
        else:
            scales = [scale_refs[i][0, 0] for i in range(n_layers)]
            base_seed = seed_ref[0, 0]

    def one_step(k, carry):
        if telemetry:
            ws, vs, trs, tel = carry
        else:
            (ws, vs, trs), tel = carry, None
        ws, vs, trs = list(ws), list(vs), list(trs)
        x = drives_ref[pl.ds(k, 1)][0].astype(compute)   # (bb, N0) event bus
        # input-population Trace Update Unit (gated exactly as snn.timestep)
        if qcfg is None:
            tr0_new = trace_decay * trs[0] + x
        else:
            tr0_new = Q.trace_update_q(trs[0], x, qcfg)
        if gate is not None:
            tr0_new = jnp.where(gate, tr0_new, trs[0])
        trs[0] = tr0_new
        for i in range(n_layers):
            w, v, tpost = ws[i], vs[i], trs[i + 1]
            # ---- Forward Engine: psum on the resident weight tile --------
            acc = _K.psum(x, w, fleet=fleet, quant=qcfg is not None)
            current = acc if qcfg is None else Q.current_fx(acc, scales[i],
                                                            qcfg)
            if teach_ref is not None and i == n_layers - 1:
                current = current + teach_ref[pl.ds(k, 1)][0].astype(compute)
            if qcfg is None:
                v_new = v + (current - v) * (1.0 / tau_m)
                if spiking[i]:
                    events = (v_new >= v_th).astype(jnp.float32)
                    v_upd = jnp.where(events > 0, v_reset, v_new)
                else:                       # non-spiking leaky readout
                    events = jnp.tanh(v_new)
                    v_upd = v_new
                tpost_new = trace_decay * tpost + events
            else:
                events, v_upd = Q.neuron_update_q(v, current, qcfg, v_th,
                                                  v_reset, spiking[i])
                tpost_new = Q.trace_update_q(tpost, events, qcfg)
            # Plasticity consumes the UNGATED post-trace, exactly like the
            # xla oracle (ref gates outputs after the vmapped step): for
            # active slots the values are identical and inactive slots'
            # dw is discarded by the weight gate below — but keeping the
            # oracle's dataflow keeps XLA's FMA contraction identical, so
            # float parity stays BITWISE rather than ulp-close.
            tpost_raw = tpost_new
            if gate is not None:
                events = jnp.where(gate, events, jnp.zeros_like(events))
                v_upd = jnp.where(gate, v_upd, v)
                tpost_new = jnp.where(gate, tpost_new, tpost)
            # ---- Plasticity Engine (same resident tiles, no HBM pass) ----
            if plastic[i]:
                th, tpre = ths[i], trs[i]
                tpost_p = tpost_raw
                if qcfg is None:
                    if fleet:   # per-stream outer-product dw, shared rule
                        hebb = tpre[:, :, None] * tpost_p[:, None, :]
                        dw = (th[ALPHA] * hebb + th[BETA] * tpre[:, :, None]
                              + th[GAMMA] * tpost_p[:, None, :]
                              + th[DELTA])
                    else:       # shared weights: batch-averaged dw
                        hebb = _K.shared_hebb(tpre, tpost_p,
                                           quant=False) / batch
                        pre_m = jnp.mean(tpre, axis=0)
                        post_m = jnp.mean(tpost_p, axis=0)
                        dw = (th[ALPHA] * hebb + th[BETA] * pre_m[:, None]
                              + th[GAMMA] * post_m[None, :] + th[DELTA])
                    w_new = jnp.clip(w + dw, -w_clip, w_clip)
                else:
                    if fleet:
                        hebb_i = tpre[:, :, None] * tpost_p[:, None, :]
                        dw = Q.dw_from_int_reductions(hebb_i, tpre,
                                                      tpost_p, th, 1, qcfg)
                        scale = scales[i][:, :, None]             # (bb,1,1)
                        seed_i = Q.fold_seed(base_seed + k, i)[:, :, None]
                    else:
                        hebb_i = _K.shared_hebb(tpre, tpost_p, quant=True)
                        dw = Q.dw_from_int_reductions(
                            hebb_i, tpre.sum(0), tpost_p.sum(0), th,
                            batch, qcfg)
                        scale = scales[i]
                        seed_i = Q.fold_seed(base_seed + k, i)
                    n_i, m_i = w.shape[-2], w.shape[-1]
                    idx = (jax.lax.broadcasted_iota(jnp.int32,
                                                    (n_i, m_i), 0) * m_i
                           + jax.lax.broadcasted_iota(jnp.int32,
                                                      (n_i, m_i), 1))
                    steps = Q.round_steps(dw / scale, seed_i, idx, qcfg)
                    qmax = Q.qclip(w_clip, scale)
                    w_new = jnp.clip(w + steps, -qmax, qmax)
                if gate is not None:
                    w_new = jnp.where(gate[:, :, None], w_new, w)
                ws[i] = w_new
            vs[i] = v_upd
            trs[i + 1] = tpost_new
            if telemetry:
                # Per-layer means accumulate step by step; events are
                # already gated (zeros for vacant slots), the saturation
                # term is gated once at finalize.
                m_i = events.shape[-1]
                ev_f = jnp.abs(events).astype(jnp.float32)
                if qcfg is not None:
                    ev_f = ev_f * (1.0 / qcfg.one)
                    sat = jnp.abs(v_upd) >= sat_threshold_q(v_th, qcfg)
                else:
                    sat = jnp.abs(v_upd) >= sat_threshold(v_th)
                tel = (tel[0] + jnp.sum(ev_f, axis=1, keepdims=True) / m_i,
                       tel[1] + jnp.sum(sat.astype(jnp.float32), axis=1,
                                        keepdims=True) / m_i)
            out = events if spiking[i] else v_upd
            if gate is not None and not spiking[i]:
                # readout output IS the membrane; inactive slots must still
                # emit zero events (same contract as engine.layer_step)
                out = jnp.where(gate, out, jnp.zeros_like(out))
            x = out
        out_ref[pl.ds(k, 1)] = x[None].astype(out_ref.dtype)
        new = (tuple(ws), tuple(vs), tuple(trs))
        return new + ((tel,) if telemetry else ())

    carry = (ws0, vs0, trs0)
    if telemetry:   # (bb, 1) columns: Mosaic has no 1-D vector layouts
        zero = jnp.zeros((ws0[0].shape[0], 1), jnp.float32)
        carry = carry + ((zero, zero),)
    if unroll_k <= 0 or unroll_k >= k_steps:
        for k in range(k_steps):                      # full unroll
            carry = one_step(k, carry)
    else:
        n_chunks = k_steps // unroll_k

        def chunk(c, carry):
            for j in range(unroll_k):
                carry = one_step(c * unroll_k + j, carry)
            return carry

        carry = jax.lax.fori_loop(0, n_chunks, chunk, carry)
        for k in range(n_chunks * unroll_k, k_steps):  # remainder
            carry = one_step(k, carry)
    ws, vs, trs = carry[0], carry[1], carry[2]
    # single write-back: K steps of dw land in HBM as ONE weight store
    for i in range(n_layers):
        w_outs[i][...] = ws[i].astype(w_outs[i].dtype)
        v_outs[i][...] = vs[i].astype(v_outs[i].dtype)
    for i in range(n_layers + 1):
        tr_outs[i][...] = trs[i].astype(tr_outs[i].dtype)

    if telemetry:
        tel_spk, tel_sat = carry[3]
        kl = float(k_steps * n_layers)
        spike_rate = tel_spk / kl                     # (bb, 1)
        sat_frac = tel_sat / kl
        plast = [i for i in range(n_layers) if plastic[i]]
        if plast:
            dw_sum = jnp.zeros_like(spike_rate)
            for i in plast:
                n_i, m_i = ws[i].shape[-2], ws[i].shape[-1]
                d = jnp.abs(ws[i] - ws0[i]).astype(jnp.float32)
                per_slot = jnp.sum(jnp.sum(d, axis=2), axis=1, keepdims=True)
                if qcfg is not None:
                    per_slot = per_slot * scales[i]
                dw_sum = dw_sum + per_slot / (n_i * m_i)
            mean_dw = dw_sum / float(k_steps * len(plast))
        else:
            mean_dw = jnp.zeros_like(spike_rate)
        row = _K.columns3(spike_rate, mean_dw, sat_frac)          # (bb, 3)
        if gate is not None:
            row = row * gate.astype(jnp.float32)      # (bb, 1) broadcast
        tel_out[...] = row


def rollout_pallas(drives, ws, thetas, vs, traces, *, spiking, plastic,
                   tau_m: float = 2.0, v_th: float = 1.0,
                   v_reset: float = 0.0, trace_decay: float = 0.8,
                   w_clip: float = 4.0, qcfg=None, scales=None, seed=None,
                   teach=None, active=None, telemetry: bool = False,
                   block_b: int = 8, unroll_k: int = 1,
                   interpret: bool = False):
    """K fused timesteps of the whole layer stack in one pallas_call.

    Args:
      drives:  (K, B, N0) time-major input window (int32 fixed point when
               ``qcfg``; float otherwise).
      ws:      per-layer weights — (N_i, M_i) shared or (B, N_i, M_i) fleet
               (int8 in quant mode).
      thetas:  per-layer packed (4, N_i, M_i) rules; None for non-plastic
               layers.
      vs:      per-layer membranes (B, M_i).
      traces:  L+1 population traces (B, N_i); traces[0] is the input
               population.
      spiking/plastic: per-layer static bool tuples.
      qcfg/scales/seed: fixed-point mode — per-layer weight scales
               ((B,)/() f32) and the base step counter ((B,)/() int32);
               step k of the window draws its stochastic round from
               ``fold_seed(seed + k, layer)``.
      teach:   optional (K, B, M_last) teaching current (already
               normalized by engine.rollout).
      active:  fleet-only (B,) slot mask; inactive streams are bit-frozen
               across the whole window and emit zero events.
      telemetry: fleet-only static flag — append a finalized (B, 3)
               float32 output of per-slot means [spike_rate, mean |dw|
               (net window motion), sat_frac] (`obs.telemetry` schema;
               vacant slots all-zero).  Off keeps the program
               byte-identical to the unistrumented one.
      block_b: fleet streams per grid program (stream-blocked execution).
      unroll_k: time-loop chunking (see module docstring); bit-pinned vs
               the oracle at 1 (and at every setting in quant mode).

    Returns ``(outs, ws, vs, traces)`` with outs (K, B, M_last), plus the
    (B, 3) telemetry row when ``telemetry=True``.
    """
    k_steps, b, n0 = drives.shape
    n_layers = len(ws)
    fleet = ws[0].ndim == 3
    sizes = [n0] + [w.shape[-1] for w in ws]
    spiking = tuple(bool(s) for s in spiking)
    plastic = tuple(bool(p) for p in plastic)
    for i in range(n_layers):
        if plastic[i] and thetas[i] is None:
            raise ValueError(f"layer {i} marked plastic but theta is None")
    has_teach = teach is not None
    has_active = active is not None
    if telemetry and not fleet:
        raise ValueError("telemetry is a fleet-mode contract "
                         "(per-slot rows need a leading stream rank)")

    if fleet:
        bb = _K.stream_block(block_b, b, interpret)
        grid = (pl.cdiv(b, bb),)
        tmap = lambda i: (0, i, 0)      # time-major staging (K, bb, n)
        wmap = lambda i: (i, 0, 0)      # per-stream weight block
        thmap = lambda i: (0, 0, 0)     # shared rule: constant index =>
        rmap = lambda i: (i, 0)         # one theta DMA for the whole fleet
    else:
        bb = b
        grid = (1,)
        tmap = lambda i: (0, 0, 0)
        wmap = lambda i: (0, 0)
        thmap = lambda i: (0, 0, 0)
        rmap = lambda i: (0, 0)

    in_specs = [pl.BlockSpec((k_steps, bb, n0), tmap)]
    operands = [drives]
    for i in range(n_layers):
        shape = ((bb, sizes[i], sizes[i + 1]) if fleet
                 else (sizes[i], sizes[i + 1]))
        in_specs.append(pl.BlockSpec(shape, wmap))
        operands.append(ws[i])
    for i in range(n_layers):
        if plastic[i]:
            in_specs.append(
                pl.BlockSpec((4, sizes[i], sizes[i + 1]), thmap))
            operands.append(thetas[i])
    for i in range(n_layers):
        in_specs.append(pl.BlockSpec((bb, sizes[i + 1]), rmap))
        operands.append(vs[i])
    for i in range(n_layers + 1):
        in_specs.append(pl.BlockSpec((bb, sizes[i]), rmap))
        operands.append(traces[i])
    if has_teach:
        in_specs.append(pl.BlockSpec((k_steps, bb, sizes[-1]), tmap))
        operands.append(teach)
    if has_active:
        in_specs.append(pl.BlockSpec((bb, 1), rmap))
        operands.append(
            jnp.asarray(active).reshape(b, 1).astype(jnp.float32))
    if qcfg is not None:
        for i in range(n_layers):
            sc = jnp.asarray(scales[i], jnp.float32)
            if fleet:
                if sc.ndim == 0:
                    sc = jnp.broadcast_to(sc, (b,))
                sc = sc.reshape(b, 1)
                in_specs.append(pl.BlockSpec((bb, 1), rmap))
            else:
                sc = sc.reshape(1, 1)
                in_specs.append(pl.BlockSpec((1, 1), rmap))
            operands.append(sc)
        sd = jnp.asarray(0 if seed is None else seed, jnp.int32)
        if fleet:
            if sd.ndim == 0:
                sd = jnp.broadcast_to(sd, (b,))
            sd = sd.reshape(b, 1)
            in_specs.append(pl.BlockSpec((bb, 1), rmap))
        else:
            sd = sd.reshape(1, 1)
            in_specs.append(pl.BlockSpec((1, 1), rmap))
        operands.append(sd)

    out_dtype = jnp.int32 if qcfg is not None else drives.dtype
    out_specs = [pl.BlockSpec((k_steps, bb, sizes[-1]), tmap)]
    out_shape = [jax.ShapeDtypeStruct((k_steps, b, sizes[-1]), out_dtype)]
    for i in range(n_layers):
        shape = ((bb, sizes[i], sizes[i + 1]) if fleet
                 else (sizes[i], sizes[i + 1]))
        out_specs.append(pl.BlockSpec(shape, wmap))
        out_shape.append(jax.ShapeDtypeStruct(ws[i].shape, ws[i].dtype))
    for i in range(n_layers):
        out_specs.append(pl.BlockSpec((bb, sizes[i + 1]), rmap))
        out_shape.append(jax.ShapeDtypeStruct(vs[i].shape, vs[i].dtype))
    for i in range(n_layers + 1):
        out_specs.append(pl.BlockSpec((bb, sizes[i]), rmap))
        out_shape.append(
            jax.ShapeDtypeStruct(traces[i].shape, traces[i].dtype))
    if telemetry:
        out_specs.append(pl.BlockSpec((bb, 3), rmap))
        out_shape.append(jax.ShapeDtypeStruct((b, 3), jnp.float32))

    kernel = functools.partial(
        _rollout_kernel, n_layers=n_layers, k_steps=k_steps,
        spiking=spiking, plastic=plastic, fleet=fleet, batch=b,
        tau_m=tau_m, v_th=v_th, v_reset=v_reset, trace_decay=trace_decay,
        w_clip=w_clip, qcfg=qcfg, has_teach=has_teach,
        has_active=has_active, unroll_k=int(unroll_k),
        telemetry=telemetry)
    res = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        name="plasticity_fused_rollout")(*operands)
    outs = res[0]
    ws_new = tuple(res[1:1 + n_layers])
    vs_new = tuple(res[1 + n_layers:1 + 2 * n_layers])
    trs_new = tuple(res[1 + 2 * n_layers:2 + 3 * n_layers])
    base = (outs, ws_new, vs_new, trs_new)
    return base + ((res[2 + 3 * n_layers],) if telemetry else ())
