"""Fixed-point (FPGA-faithful) arithmetic for the dual-engine step.

FireFly-P's 8 us / 0.713 W / ~10K-LUT result rests entirely on fixed-point
arithmetic: multiplier-free tau_m = 2 (a shift), hard-reset LIF, power-of-two
trace decays, and integer weight updates.  This module is the single source
of truth for that datapath on JAX: the quantized oracle
(`ref.dual_engine_step_q`) and the quantized Pallas kernels
(`kernel.dual_engine_step_q_pallas`) both call the helpers below, so the
elementwise math literally cannot diverge between backends — and every
reduction in the quantized path is an INTEGER reduction (exact, order
independent), which is what makes the whole path bit-deterministic across
``impl="xla"`` and ``impl="pallas-interpret"`` (pinned in tests/test_quant.py).

Representation (see also the scheme writeup in `ops.py`):

  * weights    — int8 ``w_q`` with a per-tile fp32 scale ``s`` (one scale per
                 (N, M) weight matrix; the fleet pool carries one per slot):
                 ``w = w_q * s``.  The default scale is the power of two
                 ``2**-w_frac_bits`` so the int8 grid spans the clip range
                 and dequant is a shift on hardware.
  * membrane & traces — int32 fixed point with ``frac_bits`` fractional
                 bits: ``value = q * 2**-frac_bits``.
  * events     — same fixed point: a spike is ``one = 2**frac_bits``; the
                 readout event is the SATURATING-LINEAR activation
                 ``clip(v, -one, one)`` (the piecewise-linear tanh an FPGA
                 ships instead of the transcendental).
  * dw         — computed elementwise in f32 from exact integer trace
                 reductions, then converted to INTEGER grid steps with a
                 deterministic stochastic round (counter-hash PRNG below);
                 ``w_q`` advances by whole int8 steps.

Determinism contract: everything after the integer reductions is elementwise
(IEEE-reproducible), and the stochastic round draws its uniform from
`uniform_hash(seed, index)` — a pure function of the SESSION step counter
and the weight's flat (N*M) index, never of the fleet slot, the neighbours,
or wall-clock.  That is exactly what makes evict -> persist -> re-admit of a
quantized session bit-identical to an uninterrupted run.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.plasticity import ALPHA, BETA, GAMMA, DELTA


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Static fixed-point parameters (hashable; threaded as a jit-static).

    ``frac_bits``   — fractional bits of the int32 membrane/trace format.
    ``w_frac_bits`` — weight grid: default scale is ``2**-w_frac_bits``
                      (1/32 -> int8 range +-127/32 ~= +-3.97, pairing with
                      the paper's w_clip = 4).
    ``trace_shift`` — power-of-two trace decay ``1 - 2**-trace_shift``
                      (shift-and-subtract on hardware; 2 -> 0.75).
    ``tau_shift``   — membrane time constant ``tau_m = 2**tau_shift``
                      (1 -> the paper's multiplier-free tau_m = 2).
    ``stoch_round`` — deterministic stochastic rounding of dw to grid steps
                      (False = round-half-even).
    """

    frac_bits: int = 8
    w_frac_bits: int = 5
    trace_shift: int = 2
    tau_shift: int = 1
    stoch_round: bool = True

    def __post_init__(self):
        for name in ("frac_bits", "w_frac_bits", "trace_shift", "tau_shift"):
            v = getattr(self, name)
            if not (isinstance(v, int) and 0 <= v <= 24):
                raise ValueError(f"{name} must be an int in [0, 24], got {v!r}")

    @property
    def one(self) -> int:
        """Fixed-point 1.0 of the membrane/trace format."""
        return 1 << self.frac_bits

    @property
    def w_scale(self) -> float:
        """Default (power-of-two) weight scale."""
        return 2.0 ** -self.w_frac_bits

    @property
    def decay(self) -> float:
        """Effective trace decay ``1 - 2**-trace_shift``."""
        return 1.0 - 2.0 ** -self.trace_shift

    @property
    def tau_m(self) -> float:
        return float(1 << self.tau_shift)


# ---- fixed-point conversion (network boundary) -----------------------------

def to_fixed(x, qc: QuantConfig):
    """float -> int32 fixed point (round-half-even, the hardware quantizer)."""
    return jnp.round(x.astype(jnp.float32) * float(qc.one)).astype(jnp.int32)


def from_fixed(q, qc: QuantConfig):
    """int32 fixed point -> float32 (exact for |q| < 2**24)."""
    return q.astype(jnp.float32) * jnp.float32(2.0 ** -qc.frac_bits)


# ---- integer datapath (shared verbatim by oracle AND Pallas kernels) -------

def neuron_update_q(v_fx, i_fx, qc: QuantConfig, v_th: float, v_reset: float,
                    spiking: bool):
    """Integer LIF / readout update.  Returns ``(event_fx, v_out_fx)``.

    ``v += (I - v) >> tau_shift`` is the paper's multiplier-free leaky
    integration (arithmetic shift = floor division, same as the RTL).
    Spiking: hard reset, event = fixed-point 1.0.  Readout: the event is
    ``clip(v, -1, 1)`` — the saturating-linear stand-in for tanh.
    """
    one = qc.one
    vth_fx = jnp.int32(int(round(v_th * one)))
    vres_fx = jnp.int32(int(round(v_reset * one)))
    v_new = v_fx + jnp.right_shift(i_fx - v_fx, qc.tau_shift)
    if spiking:
        sp = v_new >= vth_fx
        event = jnp.where(sp, jnp.int32(one), jnp.int32(0))
        v_out = jnp.where(sp, vres_fx, v_new)
    else:
        event = jnp.clip(v_new, -one, one)
        v_out = v_new
    return event, v_out


def trace_update_q(tp_fx, event_fx, qc: QuantConfig):
    """Integer trace decay + accumulate: ``tp - (tp >> k) + event``."""
    return tp_fx - jnp.right_shift(tp_fx, qc.trace_shift) + event_fx


def current_fx(acc_i32, scale, qc: QuantConfig):
    """Integer psum accumulator -> membrane fixed point.

    ``acc = x_fx @ w_q`` carries units ``2**-frac_bits * scale``; one
    elementwise multiply by the (per-tile) scale converts to membrane units.
    (With the default power-of-two scale this is a shift on hardware.)
    """
    del qc  # units cancel: acc * 2^-F * s * 2^F = acc * s
    return jnp.round(acc_i32.astype(jnp.float32) * scale).astype(jnp.int32)


def dw_from_int_reductions(hebb_i32, pre_sum_i32, post_sum_i32, theta,
                           batch: int, qc: QuantConfig):
    """Four-term dw (f32) from EXACT integer trace reductions.

    ``hebb_i32 = trace_pre_fx^T @ trace_post_fx`` and the pre/post sums are
    int32 (order-independent => bit-identical between the oracle's einsum
    and the kernel's per-tile dot); everything below is elementwise.

    A leading stream axis broadcasts: ``hebb (S, N, M)`` with sums
    ``(S, N)`` / ``(S, M)`` yields a per-stream ``(S, N, M)`` dw — the
    layout the fused rollout kernel uses for a block of fleet streams
    (elementwise identical to S separate unbatched calls).
    """
    inv1 = jnp.float32(1.0 / (qc.one * batch))
    inv2 = jnp.float32(1.0 / (qc.one * qc.one * batch))
    hebb = hebb_i32.astype(jnp.float32) * inv2
    pre_m = pre_sum_i32.astype(jnp.float32) * inv1
    post_m = post_sum_i32.astype(jnp.float32) * inv1
    th = theta.astype(jnp.float32)
    return (th[ALPHA] * hebb + th[BETA] * pre_m[..., :, None]
            + th[GAMMA] * post_m[..., None, :] + th[DELTA])


def _int8_limbs(a):
    """int32 -> five int8 limbs with ``a == sum_k limb_k * 2**(7k)``:
    four unsigned 7-bit limbs and a signed top limb ``a >> 28``."""
    limbs = [((a >> (7 * k)) & 127).astype(jnp.int8) for k in range(4)]
    return limbs + [(a >> 28).astype(jnp.int8)]


def int_matmul(a, b, contract_lhs: int = 1):
    """Exact int32 matrix product from int8 x int8 -> int32 MXU matmuls.

    Contracts ``a``'s dim `contract_lhs` (1: ``a @ b``; 0: ``a.T @ b``)
    with ``b``'s dim 0.  An int8 operand is used as is; an int32 one is
    split into 7-bit int8 limbs (`_int8_limbs`), and the limb products are
    shifted back into place.  Every limb product is exact (|sum| <=
    K * 127**2 < 2**31 for K < 133k), and the shifted sum equals the int32
    product modulo 2**32 — the same two's-complement result as an int32
    ``jnp.dot`` — so the result is bitwise the oracle's.  Needed because
    the TPU MXU takes no int32 operands.
    """
    dims = (((contract_lhs,), (0,)), ((), ()))
    la = [a] if a.dtype == jnp.int8 else _int8_limbs(a)
    lb = [b] if b.dtype == jnp.int8 else _int8_limbs(b)
    acc = None
    for i, x in enumerate(la):
        for j, y in enumerate(lb):
            shift = 7 * (i + j)
            if shift >= 32:              # vanishes modulo 2**32
                continue
            term = jax.lax.dot_general(x, y, dims,
                                       preferred_element_type=jnp.int32)
            if shift:
                term = jnp.left_shift(term, jnp.int32(shift))
            acc = term if acc is None else acc + term
    return acc


# ---- deterministic stochastic rounding -------------------------------------

def uniform_hash(seed, idx):
    """Counter-based uniform in [0, 1): avalanche hash of (seed, index).

    Pure elementwise uint32 arithmetic (wrapping mul/xor/shift) — identical
    on every backend, no PRNG state, no key threading.  ``seed`` is the
    session's step counter (scalar int32); ``idx`` the weight's flat index
    within its own (N, M) matrix, NEVER including the fleet slot.
    """
    h = idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
    h = h ^ ((jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
              + jnp.uint32(0x7F4A7C15)) * jnp.uint32(0x85EBCA6B))
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0x27D4EB2F)
    h = h ^ (h >> jnp.uint32(16))
    # via int32: the 24-bit value is exact in both, and TPU kernels have no
    # uint32 -> float32 conversion
    return ((h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(2.0 ** -24))


def round_steps(steps_f32, seed, idx, qc: QuantConfig):
    """dw in units of the weight grid -> integer int8 steps.

    Stochastic: round up with probability = fractional part, drawn from
    `uniform_hash` — unbiased in expectation, so sub-grid updates still
    accumulate, yet fully deterministic given (seed, index).
    """
    if not qc.stoch_round:
        return jnp.round(steps_f32).astype(jnp.int32)
    fl = jnp.floor(steps_f32)
    frac = steps_f32 - fl
    return (fl + (frac > uniform_hash(seed, idx))).astype(jnp.int32)


def qclip(w_clip: float, scale):
    """Largest admissible |w_q|: ``min(floor(w_clip / scale), 127)``."""
    return jnp.minimum(jnp.floor(jnp.float32(w_clip) / scale),
                       jnp.float32(127.0)).astype(jnp.int32)


def fold_seed(seed, layer: int):
    """Per-layer seed: wrap-multiply fold so layers draw distinct uniforms."""
    return jnp.asarray(seed, jnp.int32) * jnp.int32(1000003) + jnp.int32(layer)
