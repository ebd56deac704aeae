"""PlasticEngine: the backend-dispatched fused layer step (product hot path).

One `layer_step` = one SNN timestep for ONE synaptic layer, running the
Forward Engine (psum matmul -> neuron dynamics -> trace update) and the
Plasticity Engine (four-term dw, weights rewritten in place) as a single
fused program — the FireFly-P dual-engine overlap (Secs. III-B/C).

Every consumer of the rule — `core/snn.timestep`, the adaptation loops, the
LM plastic adapter, serving, examples, and benchmarks — routes layer steps
through this module, so the Pallas kernel is the single source of truth for
the hot path rather than a benchmark artifact.

Backends (`impl`):

  * ``"xla"``              — pure-jnp oracle (kernels/plasticity/ref).  What
                             CPU runs and dry-runs lower; bit-stable with the
                             historical hand-rolled jnp layer loop.
  * ``"pallas"``           — the fused Pallas TPU kernel
                             (kernels/plasticity/kernel).
  * ``"pallas-interpret"`` — same kernel body executed by the Pallas
                             interpreter; validates the TPU program on CPU.

`layer_step` accepts unbatched ``(N,)`` or batched ``(B, N)`` state.  Two
batched semantics, selected by the weight rank:

  * SHARED weights ``w (N, M)`` with batched activations — the dw is
    batch-averaged (delta_w semantics; e.g. batched MNIST online learning).
  * FLEET mode, ``w (B, N, M)`` — every request stream owns and rewrites
    its OWN synapses with a per-sample dw under one shared rule theta.
    All three backends run the whole fleet as ONE fused program (the Pallas
    kernel launches a ``(cdiv(M, bm), cdiv(B, block_b))`` grid, stream
    blocks innermost so the shared theta tile is fetched once per tile);
    this replaces the old
    recipe of `jax.vmap`-ing `layer_step` per stream, which broadcast the
    shared rule theta B-fold and never lowered through `pallas_call` at
    all (the batching rule rejects unmapped operands).

Fleet mode additionally accepts an ``active (B,)`` slot mask (the session-
serving contract, `repro.serving`): streams whose flag is false are frozen
bit-exactly — weights, membrane, and traces unchanged, events zero — so a
fixed-shape slot pool under continuous batching never drifts in its vacant
slots and occupancy changes never recompile.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import plasticity as _P
from repro.kernels.plasticity import fused as _fused
from repro.kernels.plasticity import kernel as _kernel
from repro.kernels.plasticity import quant as _Q
from repro.kernels.plasticity import ref as _ref
from repro.kernels.plasticity.quant import QuantConfig
from repro.obs.telemetry import (FleetTelemetry, sat_threshold,
                                 sat_threshold_q)

IMPLS = ("xla", "pallas", "pallas-interpret")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LayerState:
    """State slice the dual-engine step reads and rewrites for one layer.

    ``trace_pre`` is the *already-updated* presynaptic trace for the current
    timestep (the predecessor layer's Trace Update Unit runs upstream);
    ``trace_post`` is the previous timestep's postsynaptic trace, which
    `layer_step` advances and returns.  ``theta`` is the packed
    ``(4, n_pre, n_post)`` rule; ``None`` for non-plastic layers.

    A leading batch rank on ``w`` (``(B, N, M)``) puts the layer in FLEET
    mode: per-request weights, per-sample dw (see `layer_step`).
    """

    w: jax.Array                        # (N, M) | (B, N, M) synaptic weights
    v: jax.Array                        # (M,) | (B, M) membrane potential
    trace_pre: jax.Array                # (N,) | (B, N)
    trace_post: jax.Array               # (M,) | (B, M)
    theta: Optional[jax.Array] = None   # (4, N, M) packed rule coefficients
    w_scale: Optional[jax.Array] = None  # () | (B,) int8 weight scale (quant)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class NetworkState:
    """Whole-network state: per-layer weights/membranes, per-population traces.

    Replaces the historical raw ``{"w": [...], "v": [...], "trace": [...]}``
    dict; registered as a pytree so it threads through jit/scan/vmap.
    ``trace`` has ``num_layers + 1`` entries — ``trace[i]`` is layer i's
    presynaptic population (``trace[0]`` is the input drive's trace).
    """

    w: Tuple[jax.Array, ...]
    v: Tuple[jax.Array, ...]
    trace: Tuple[jax.Array, ...]
    t: jax.Array
    # Fixed-point mode only: per-layer int8 weight scales (() shared /
    # (B,) fleet — one scale per slot).  Empty tuple in float mode, so the
    # pytree stays leaf-compatible with pre-quant states and checkpoints.
    w_scale: Tuple[jax.Array, ...] = ()

    @property
    def num_layers(self) -> int:
        return len(self.w)

    def layer(self, i: int, theta=None) -> LayerState:
        """View layer i as a LayerState (traces must be current-timestep)."""
        return LayerState(w=self.w[i], v=self.v[i], trace_pre=self.trace[i],
                          trace_post=self.trace[i + 1], theta=theta,
                          w_scale=self.w_scale[i] if self.w_scale else None)


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """Static per-layer parameters of the fused step (hashable; jit-static)."""

    tau_m: float = 2.0
    v_th: float = 1.0
    v_reset: float = 0.0
    trace_decay: float = 0.8
    w_clip: float = 4.0
    plastic: bool = True
    spiking: bool = True        # False => leaky readout (event = tanh(V))
    block_m: int = 128          # Pallas postsynaptic tile width
    quant: Optional[QuantConfig] = None  # fixed-point mode (None = float32)


def _occupancy(active, b) -> jax.Array:
    if active is None:
        return jnp.ones((b,), jnp.float32)
    return active.reshape(-1).astype(jnp.float32)


def layer_step(state: LayerState, x: jax.Array, *,
               params: EngineParams = EngineParams(),
               impl: str = "xla",
               teach: Optional[jax.Array] = None,
               active: Optional[jax.Array] = None,
               seed: Optional[jax.Array] = None,
               telemetry: bool = False
               ) -> tuple[LayerState, jax.Array]:
    """One fused forward+plasticity step for one layer.

    Args:
      state: layer state; rewritten functionally (w, v, trace_post advance).
             ``state.w`` of rank 3 (``(B, N, M)``) selects FLEET mode: one
             fused launch steps B per-request weight sets with per-sample dw.
      x:     presynaptic events ``(N,)`` or ``(B, N)``.
      params: static engine parameters.
      impl:  ``"xla"`` | ``"pallas"`` | ``"pallas-interpret"``.
      teach: optional teaching current added to the psum ``(M,)``/``(B, M)``
             (supervised online learning on the output layer).  In fleet
             mode an unbatched ``(M,)`` teach broadcasts to every stream.
      active: optional fleet-only ``(B,)`` slot mask (bool or 0/1).  Streams
             with a false flag are TRUE no-ops: weights, membrane, and
             traces come back bit-identical and their events are zero.
             This is the contract the session-serving scheduler uses to run
             a partially occupied fixed-shape slot pool without recompiling
             or letting vacant slots drift.
      seed:  fixed-point mode only — the step counter driving the
             deterministic stochastic round of dw (scalar; fleet mode takes
             a ``(B,)`` vector of per-SESSION counters so a session's
             update stream is invariant to its slot).  Defaults to 0.
      telemetry: fleet-only STATIC flag — the backends emit one extra
             reduced output (per-slot raw sums) inside the same fused
             program, returned here normalized as an `obs.FleetTelemetry`
             third result.  Because the flag is static, telemetry-off
             traces are byte-identical to the uninstrumented program and
             telemetry-on is exactly one additional stable executable per
             entry point (never per-step churn).

    Returns:
      ``(new_state, out)`` — ``out`` is the layer's output events: spikes for
      spiking layers, the membrane potential for the leaky readout.  With
      ``telemetry=True``: ``(new_state, out, FleetTelemetry)`` (vacant
      slots report zeros in every telemetry field).
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    plastic = params.plastic and state.theta is not None
    qc = params.quant
    if qc is not None:
        # Loud contracts: the fixed-point datapath implements power-of-two
        # dynamics; a float EngineParams that silently disagrees would make
        # "float vs quant" comparisons measure the wrong thing.
        if params.tau_m != qc.tau_m:
            raise ValueError(
                f"quant mode implements tau_m = 2**tau_shift = {qc.tau_m}; "
                f"set EngineParams.tau_m to match (got {params.tau_m})")
        if abs(params.trace_decay - qc.decay) > 1e-9:
            raise ValueError(
                f"quant mode implements trace_decay = 1 - 2**-trace_shift "
                f"= {qc.decay}; set EngineParams.trace_decay to match "
                f"(got {params.trace_decay})")
        checks = [("w", state.w, jnp.int8), ("x", x, jnp.int32),
                  ("v", state.v, jnp.int32),
                  ("trace_pre", state.trace_pre, jnp.int32),
                  ("trace_post", state.trace_post, jnp.int32)]
        if teach is not None:
            # a float teach would be silently truncated toward zero by the
            # fixed-point cast (|teach| < 1 -> exactly 0); demand the same
            # int32 event-bus format as every other operand
            checks.append(("teach", teach, jnp.int32))
        for name, arr, want in checks:
            if arr.dtype != want:
                raise ValueError(
                    f"quant mode needs {name} of dtype {jnp.dtype(want).name} "
                    f"(build state with snn.init_state on a quant config or "
                    f"snn.quantize_state; quantize drive/teach with "
                    f"kernels.plasticity.quant.to_fixed); got {arr.dtype}")
        kw = dict(qcfg=qc, v_th=params.v_th, v_reset=params.v_reset,
                  w_clip=params.w_clip, plastic=plastic,
                  spiking=params.spiking, seed=seed)
    else:
        kw = dict(tau_m=params.tau_m, v_th=params.v_th,
                  v_reset=params.v_reset, trace_decay=params.trace_decay,
                  w_clip=params.w_clip, plastic=plastic,
                  spiking=params.spiking)

    fleet = state.w.ndim == 3                   # fleet: per-request weights
    if fleet:
        b, n, m = state.w.shape
        if x.ndim != 2 or x.shape[0] != b:
            raise ValueError(
                f"fleet mode needs x of shape (B, N) matching w (B, N, M); "
                f"got x {x.shape} vs w {state.w.shape}")
        # Per-stream state must be batched too: an unbatched (M,) membrane
        # or trace would silently broadcast ONE user's state across every
        # stream (and, for M == B, transpose the axes without an error).
        for name, arr, want in (("v", state.v, (b, m)),
                                ("trace_pre", state.trace_pre, (b, n)),
                                ("trace_post", state.trace_post, (b, m))):
            if tuple(arr.shape) != want:
                raise ValueError(
                    f"fleet mode needs {name} of shape {want} matching "
                    f"w (B, N, M) = {state.w.shape}; got {name} "
                    f"{tuple(arr.shape)}")
        if active is not None and tuple(active.shape) != (b,):
            raise ValueError(
                f"active slot mask must have shape (B,) = ({b},); got "
                f"{tuple(active.shape)}")
        # an unbatched (M,) teach broadcasts to every stream inside the
        # fleet wrappers (ref.dual_engine_fleet_step / the Pallas wrapper)
        kw["active"] = active
    elif active is not None:
        raise ValueError(
            "active slot masks are a fleet-mode (w (B, N, M)) contract; "
            f"got w {state.w.shape} with an active mask")
    if telemetry:
        if not fleet:
            raise ValueError(
                "telemetry is a fleet-mode (w (B, N, M)) contract: per-slot "
                f"rows need a leading stream rank; got w {state.w.shape}")
        kw["telemetry"] = True

    # Select the backend function; the quant variants take the per-tile
    # weight scale as an extra positional between w and theta.
    if qc is not None:
        w_scale = (state.w_scale if state.w_scale is not None
                   else jnp.float32(qc.w_scale))
        scale_args = (w_scale,)
        fn = {("xla", False): _ref.dual_engine_step_q,
              ("xla", True): _ref.dual_engine_fleet_step_q,
              ("pallas", False): _kernel.dual_engine_step_q_pallas,
              ("pallas", True): _kernel.dual_engine_fleet_step_q_pallas}
    else:
        scale_args = ()
        fn = {("xla", False): _ref.dual_engine_step,
              ("xla", True): _ref.dual_engine_fleet_step,
              ("pallas", False): _kernel.dual_engine_step_pallas,
              ("pallas", True): _kernel.dual_engine_fleet_step_pallas}
    if impl == "xla":
        fn = fn[("xla", fleet)]
        res = fn(
            x, state.w, *scale_args, state.theta, state.v, state.trace_pre,
            state.trace_post, teach=teach, **kw)
    else:
        # The Pallas kernels are rank-(B, N); promote unbatched state to B=1.
        unbatched = not fleet and x.ndim == 1
        up = (lambda a: a[None]) if unbatched else (lambda a: a)
        fn = fn[("pallas", fleet)]
        res = fn(
            up(x), state.w, *scale_args, state.theta, up(state.v),
            up(state.trace_pre), up(state.trace_post),
            teach=None if teach is None else up(teach),
            block_m=params.block_m, interpret=(impl == "pallas-interpret"),
            **kw)
        if unbatched:
            res = (res[0][0], res[1][0], res[2][0]) + tuple(res[3:])
    spikes, v, tpost, w = res[:4]

    new_state = dataclasses.replace(state, w=w, v=v, trace_post=tpost)
    out = spikes if params.spiking else v
    if active is not None and not params.spiking:
        # The readout's output IS the membrane; the state gate correctly
        # freezes v to its OLD value for inactive slots, but the output
        # contract ("inactive events are zero") must hold for readout
        # layers too — a pooled consumer must never see a stale membrane.
        out = jnp.where(active.astype(bool)[:, None], out,
                        jnp.zeros_like(out))
    if not telemetry:
        return new_state, out
    # Normalize the raw per-slot sums into per-neuron / per-synapse means.
    b, n, m = state.w.shape
    raw = res[4]
    tel = FleetTelemetry(
        spike_rate=raw[:, 0] / m,
        mean_abs_dw=raw[:, 1] / (n * m),
        sat_frac=raw[:, 2] / m,
        occupancy=_occupancy(active, b))
    return new_state, out, tel


def _validate_rollout_params(params) -> None:
    """Rollout params must agree on everything a single fused window shares
    (dynamics scalars + datapath); only spiking/plastic may vary by layer."""
    p0 = params[0]
    for i, p in enumerate(params):
        for f in ("tau_m", "v_th", "v_reset", "trace_decay", "w_clip",
                  "quant"):
            if getattr(p, f) != getattr(p0, f):
                raise ValueError(
                    f"rollout fuses all layers into one window and needs "
                    f"uniform EngineParams.{f}; layer {i} has "
                    f"{getattr(p, f)!r} vs layer 0's {getattr(p0, f)!r}")


def rollout(state: NetworkState, theta, drives: jax.Array, *,
            params, impl: str = "xla",
            teach: Optional[jax.Array] = None,
            active: Optional[jax.Array] = None,
            seed: Optional[jax.Array] = None,
            unroll_k: int = 1, block_b: int = 8,
            telemetry: bool = False
            ) -> tuple[NetworkState, jax.Array]:
    """K fused timesteps of the WHOLE layer stack (the rollout megakernel).

    The time-fused analogue of calling `layer_step` K * num_layers times:
    on the Pallas backends the entire window executes as ONE `pallas_call`
    (kernels/plasticity/fused) with membranes, traces, the active-slot
    mask, and the weight tiles VMEM-resident across all K steps; on
    ``impl="xla"`` a `lax.scan` over the per-step `layer_step` oracle
    defines the semantics the kernel is pinned against bit-for-bit.

    Args:
      state:  `NetworkState` — shared weights (N, M) (activations unbatched
              or batched (B, ·)) or a fleet pool (B, N, M).
      theta:  per-layer packed (4, N_i, M_i) rules (entries may be None for
              non-plastic layers).
      drives: time-major input window — (K, N0), (K, B, N0); int32 fixed
              point when the params carry a QuantConfig, float otherwise.
      params: per-layer `EngineParams` sequence (or a single EngineParams
              applied to every layer); must agree on the dynamics scalars
              and quant mode (see `_validate_rollout_params`).
      teach:  optional teaching current for the LAST layer.  Rank selects
              the semantics: ``teach.ndim == drives.ndim`` is a per-step
              (K, ·, M) window; ``drives.ndim - 1`` is one held signal
              broadcast over the window (the classify_window protocol).
      active: fleet-only (B,) slot mask, constant across the window
              (admissions/evictions happen BETWEEN windows); inactive
              slots are bit-frozen for all K steps.
      seed:   fixed-point mode — base step counter (scalar, or (B,)
              per-session counters in fleet mode); step k draws its
              stochastic round from ``fold_seed(seed + k, layer)``, the
              exact per-step sequence.  Defaults to ``state.t``.
      unroll_k: Pallas time-loop chunking (0 / >= K = full unroll).  Quant
              mode computes identical bits at every setting; float mode is
              bit-pinned against the oracle at the default 1 for
              controller-scale layers and drifts by ULPs when several
              steps share one unrolled body or layers are wide (~64+) —
              FMA-contraction freedom, see kernels/plasticity/fused.  The
              xla oracle ignores it.
      block_b: fleet streams per Pallas grid program.
      telemetry: fleet-only STATIC flag — emit an `obs.FleetTelemetry` of
              per-slot WINDOW means as a third result: spike_rate/sat_frac
              accumulate per step inside the window (averaged over steps
              and layers), mean_abs_dw is the NET weight motion
              ``|w_end - w_start| / (N*M) / (K * n_plastic)`` — the
              activity measure that survives the fixed-point grid, and
              the one that costs one reduction per window rather than one
              per step.  Off-path traces stay byte-identical.

    Returns ``(new_state, outs)`` with outs (K, ·, M_last) and
    ``new_state.t = state.t + K``; with ``telemetry=True``:
    ``(new_state, outs, FleetTelemetry)``.
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if isinstance(params, EngineParams):
        params = [params] * state.num_layers
    params = list(params)
    if len(params) != state.num_layers:
        raise ValueError(f"need one EngineParams per layer "
                         f"({state.num_layers}), got {len(params)}")
    _validate_rollout_params(params)
    theta = list(theta)
    if len(theta) != state.num_layers:
        raise ValueError(f"need one theta entry per layer "
                         f"({state.num_layers}; None for non-plastic), "
                         f"got {len(theta)}")
    qc = params[0].quant
    fleet = state.w[0].ndim == 3
    if drives.ndim not in (2, 3):
        raise ValueError(f"drives must be (K, N0) or (K, B, N0); got "
                         f"{drives.shape}")
    if fleet and drives.ndim != 3:
        raise ValueError(f"fleet rollout needs drives (K, B, N0); got "
                         f"{drives.shape}")
    if active is not None and not fleet:
        raise ValueError("active slot masks are a fleet-mode contract")
    if telemetry and not fleet:
        raise ValueError(
            "telemetry is a fleet-mode (w (B, N, M)) contract: per-slot "
            "rows need a leading stream rank")
    k_steps = drives.shape[0]
    if k_steps < 1:
        raise ValueError("rollout needs K >= 1 timesteps")
    if fleet:
        b = state.w[0].shape[0]
        if drives.shape[1] != b:
            raise ValueError(f"fleet rollout needs drives (K, B, N0) with "
                             f"B = {b}; got {drives.shape}")
        if active is not None and tuple(active.shape) != (b,):
            raise ValueError(f"active slot mask must have shape ({b},); "
                             f"got {tuple(active.shape)}")
    if qc is not None:
        # same loud contracts as layer_step (the Pallas path skips it)
        if params[0].tau_m != qc.tau_m:
            raise ValueError(
                f"quant mode implements tau_m = 2**tau_shift = {qc.tau_m}; "
                f"set EngineParams.tau_m to match (got {params[0].tau_m})")
        if abs(params[0].trace_decay - qc.decay) > 1e-9:
            raise ValueError(
                f"quant mode implements trace_decay = 1 - 2**-trace_shift "
                f"= {qc.decay}; set EngineParams.trace_decay to match "
                f"(got {params[0].trace_decay})")
        checks = [("w", state.w[0], jnp.int8), ("drives", drives, jnp.int32),
                  ("v", state.v[0], jnp.int32),
                  ("trace", state.trace[0], jnp.int32)]
        if teach is not None:
            checks.append(("teach", teach, jnp.int32))
        for name, arr, want in checks:
            if arr.dtype != want:
                raise ValueError(
                    f"quant rollout needs {name} of dtype "
                    f"{jnp.dtype(want).name} (build state via snn.init_state"
                    f"/quantize_state; quantize drives/teach with "
                    f"kernels.plasticity.quant.to_fixed); got {arr.dtype}")
    # teach rank disambiguation: same rank as drives => per-step window;
    # one less => held signal broadcast over the K steps.
    if teach is not None:
        if teach.ndim == drives.ndim - 1:
            teach = jnp.broadcast_to(teach[None], (k_steps, *teach.shape))
        elif teach.ndim != drives.ndim:
            raise ValueError(
                f"teach must be per-step (K, ..., M) of rank {drives.ndim} "
                f"or held of rank {drives.ndim - 1}; got {teach.shape}")
    base_seed = None
    if qc is not None:
        base_seed = (jnp.asarray(seed, jnp.int32) if seed is not None
                     else state.t.astype(jnp.int32))

    if impl == "xla":
        res = _rollout_xla(state, theta, drives, params, teach,
                           active, base_seed, telemetry=telemetry)
    else:
        res = _rollout_pallas(
            state, theta, drives, params, teach, active, base_seed,
            unroll_k=unroll_k, block_b=block_b,
            interpret=(impl == "pallas-interpret"), telemetry=telemetry)
    new_state, outs = res[0], res[1]
    new_state = dataclasses.replace(new_state, t=state.t + k_steps)
    if not telemetry:
        return new_state, outs
    return new_state, outs, res[2]


def _rollout_xla(state, theta, drives, params, teach, active, base_seed,
                 *, telemetry=False):
    """Scanned per-step oracle: the semantic ground truth for the fused
    kernel (body = snn.timestep's dataflow, layer steps via `layer_step`).

    With ``telemetry`` the scan carry grows a (B, 2) [spike, saturation]
    accumulator mirroring the fused kernel's in-register one; the |dw|
    column is the NET window motion computed ONCE post-scan from the
    weight carry — the scan body never touches per-step weight deltas, so
    the telemetry variant adds two cheap reductions per step, not an
    O(B*N*M) pass.
    """
    qc = params[0].quant
    decay = params[0].trace_decay
    n_layers = state.num_layers
    ks = jnp.arange(drives.shape[0], dtype=jnp.int32)
    xs = (drives, ks) if teach is None else (drives, teach, ks)

    def _event_units(out, spiking):
        """|events| in event units from a layer's gated output (the readout
        membrane maps back through its event nonlinearity; inactive slots'
        zeroed outputs stay zero under both)."""
        if qc is not None:
            ev = out if spiking else jnp.clip(out, -qc.one, qc.one)
            return jnp.abs(ev).astype(jnp.float32) / qc.one
        return jnp.abs(out if spiking else jnp.tanh(out))

    def body(carry, inp):
        if telemetry:
            w, v, tr, acc = carry
        else:
            (w, v, tr), acc = carry, None
        if teach is None:
            x, k = inp
            teach_k = None
        else:
            x, teach_k, k = inp
        w, v, tr = list(w), list(v), list(tr)
        if qc is not None:
            tr0_new = _Q.trace_update_q(tr[0], x, qc)
        else:
            tr0_new = _P.update_trace(tr[0], x, decay)
        if active is not None:
            tr0_new = jnp.where(active.astype(bool)[:, None], tr0_new,
                                tr[0])
        tr[0] = tr0_new
        out = None
        for i in range(n_layers):
            layer = LayerState(
                w=w[i], v=v[i], trace_pre=tr[i], trace_post=tr[i + 1],
                theta=theta[i],
                w_scale=state.w_scale[i] if state.w_scale else None)
            layer, out = layer_step(
                layer, x, params=params[i], impl="xla",
                teach=teach_k if i == n_layers - 1 else None,
                active=active,
                seed=(None if base_seed is None
                      else _Q.fold_seed(base_seed + k, i)))
            w[i], v[i], tr[i + 1] = layer.w, layer.v, layer.trace_post
            if telemetry:
                m_i = out.shape[-1]
                ev_f = _event_units(out, params[i].spiking)
                if qc is not None:
                    sat = jnp.abs(layer.v) >= sat_threshold_q(
                        params[i].v_th, qc)
                else:
                    sat = jnp.abs(layer.v) >= sat_threshold(params[i].v_th)
                acc = acc + jnp.stack(
                    [jnp.sum(ev_f, axis=1) / m_i,
                     jnp.sum(sat.astype(jnp.float32), axis=1) / m_i],
                    axis=1)
            x = out
        new = (tuple(w), tuple(v), tuple(tr))
        return (new + (acc,) if telemetry else new), out

    carry0 = (state.w, state.v, state.trace)
    if telemetry:
        carry0 = carry0 + (jnp.zeros((state.w[0].shape[0], 2), jnp.float32),)
    carry, outs = jax.lax.scan(body, carry0, xs)
    w, v, tr = carry[0], carry[1], carry[2]
    new_state = dataclasses.replace(state, w=w, v=v, trace=tr)
    if not telemetry:
        return new_state, outs

    k_steps = drives.shape[0]
    kl = float(k_steps * n_layers)
    acc = carry[3]
    spike_rate, sat_frac = acc[:, 0] / kl, acc[:, 1] / kl
    plast = [i for i in range(n_layers)
             if params[i].plastic and theta[i] is not None]
    if plast:
        dw_sum = jnp.zeros_like(spike_rate)
        for i in plast:
            n_i, m_i = state.w[i].shape[-2], state.w[i].shape[-1]
            d = jnp.abs(w[i].astype(jnp.int32)
                        - state.w[i].astype(jnp.int32)).astype(jnp.float32) \
                if qc is not None else jnp.abs(w[i] - state.w[i])
            per_slot = jnp.sum(d, axis=(1, 2))
            if qc is not None:
                sc = (state.w_scale[i] if state.w_scale
                      else jnp.float32(qc.w_scale))
                per_slot = per_slot * jnp.asarray(sc).reshape(-1)
            dw_sum = dw_sum + per_slot / (n_i * m_i)
        mean_dw = dw_sum / float(k_steps * len(plast))
    else:
        mean_dw = jnp.zeros_like(spike_rate)
    occ = _occupancy(active, state.w[0].shape[0])
    gate = occ if active is not None else jnp.ones_like(occ)
    tel = FleetTelemetry(spike_rate=spike_rate * gate,
                         mean_abs_dw=mean_dw * gate,
                         sat_frac=sat_frac * gate,
                         occupancy=occ)
    return new_state, outs, tel


def _rollout_pallas(state, theta, drives, params, teach, active, base_seed,
                    *, unroll_k, block_b, interpret, telemetry=False):
    """Dispatch the fused megakernel; promotes unbatched shared state to
    B=1 (the kernel is rank-(B, ·) like the per-step Pallas wrappers)."""
    qc = params[0].quant
    fleet = state.w[0].ndim == 3
    unbatched = not fleet and drives.ndim == 2
    up = (lambda a: a[None]) if unbatched else (lambda a: a)
    up_t = (lambda a: a[:, None]) if unbatched else (lambda a: a)
    p0 = params[0]
    thetas = [theta[i] if params[i].plastic else None
              for i in range(state.num_layers)]
    scales = None
    if qc is not None:
        scales = [state.w_scale[i] if state.w_scale
                  else jnp.float32(qc.w_scale)
                  for i in range(state.num_layers)]
    res = _fused.rollout_pallas(
        up_t(drives), state.w, thetas,
        tuple(up(x) for x in state.v), tuple(up(x) for x in state.trace),
        spiking=tuple(p.spiking for p in params),
        plastic=tuple(p.plastic and thetas[i] is not None
                      for i, p in enumerate(params)),
        tau_m=p0.tau_m, v_th=p0.v_th, v_reset=p0.v_reset,
        trace_decay=p0.trace_decay, w_clip=p0.w_clip, qcfg=qc,
        scales=scales, seed=base_seed,
        teach=None if teach is None else up_t(teach), active=active,
        telemetry=telemetry,
        block_b=block_b, unroll_k=unroll_k, interpret=interpret)
    outs, w, v, tr = res[:4]
    if unbatched:
        outs = outs[:, 0]
        v = tuple(x[0] for x in v)
        tr = tuple(x[0] for x in tr)
    new_state = dataclasses.replace(state, w=w, v=v, trace=tr)
    if not telemetry:
        return new_state, outs
    raw = res[4]                       # finalized, already gated (B, 3)
    tel = FleetTelemetry(spike_rate=raw[:, 0], mean_abs_dw=raw[:, 1],
                         sat_frac=raw[:, 2],
                         occupancy=_occupancy(active, raw.shape[0]))
    return new_state, outs, tel


# ---- sharding-transparent fleet dispatch (multi-device slot pools) ---------


def fleet_spmd(fn, mesh, in_axes, out_axes, axis_name: str = "data"):
    """Wrap a fleet-mode function in `shard_map` over the slot axis.

    The fleet tensors are slot-major and slot rows are mutually independent
    (the whole point of fleet mode), so a pool of B slots on a D-device mesh
    is pure data parallelism: every device runs the SAME program — the same
    `layer_step`/`rollout` lowering, the same Pallas kernel body — on its
    B/D local slots, with zero cross-device collectives in the hot path.
    Because the per-slot math is untouched, a sharded pool is bit-identical
    to the unmeshed pool (tests/test_distributed.py pins it, float and int8,
    xla and pallas-interpret).

    `shard_map` rather than sharded jit because GSPMD has no partitioning
    rule for `pallas_call` — manual SPMD is what lets the megakernel run
    per-shard unchanged.  ``check_vma=False`` for the same reason (Pallas
    calls carry no varying-manual-axes rule).

    Args:
      fn:       positional-argument function over fleet pytrees.
      mesh:     a Mesh with `axis_name` (e.g. `distributed.sharding.
                fleet_mesh()`).
      in_axes:  one entry per positional argument: an int — the slot axis
                every leaf of that argument carries (0 for ``(B, ...)``
                state, 1 for time-major ``(K, B, ...)`` windows) — or None
                for replicated inputs (scalars, shared rule state).
      out_axes: same, per output; every output must be slot-mapped (an
                int): with ``check_vma=False`` a replicated output cannot
                be verified, so compute pool-global outputs OUTSIDE the
                wrapped call.
    """
    from jax.sharding import PartitionSpec

    def spec(ax, kind):
        if ax is None:
            if kind == "out":
                raise ValueError(
                    "fleet_spmd outputs must be slot-mapped (int axis); "
                    "compute replicated outputs outside the wrapped fn")
            return PartitionSpec()
        return PartitionSpec(*((None,) * ax), axis_name)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(spec(a, "in") for a in in_axes),
        out_specs=tuple(spec(a, "out") for a in out_axes),
        check_vma=False)
