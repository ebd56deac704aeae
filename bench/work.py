"""Operations and bytes that one call of a kernel or step needs, from shapes.

These are the least the algorithm has to do: every operand read once from
HBM and every result written once, and the arithmetic of the published
equations.  They feed the roofline shares (`peaks.least_time_s`).
"""
from __future__ import annotations

from typing import Sequence

F32 = 4


def fused_rollout(layer_sizes: Sequence[int], slots: int, k: int) -> dict:
    """One fused fleet rollout call (`kernels/plasticity/fused.py`): K
    timesteps of a float32 plastic layer stack for `slots` sessions, each
    with its own weights, under one shared four-term rule.

    Bytes: weights, membranes and every population trace read and written
    once per call (they stay resident across the K steps); the shared rule
    (4 planes per layer) read once; the K drive rows read and the K readout
    rows written.  Operations per layer, step and slot: the psum (2NM) and
    the rule, dw = a*pre*post + b*pre + c*post + d, then w + dw (8NM);
    neuron and trace updates are O(M) and left out.
    """
    sizes = list(layer_sizes)
    nm = sum(sizes[i] * sizes[i + 1] for i in range(len(sizes) - 1))
    weight_bytes = 2 * slots * nm * F32
    state_bytes = 2 * slots * (sum(sizes[1:]) + sum(sizes)) * F32
    rule_bytes = 4 * nm * F32
    io_bytes = k * slots * (sizes[0] + sizes[-1]) * F32
    flops = k * slots * 10 * nm
    return {"flops": float(flops),
            "bytes": float(weight_bytes + state_bytes + rule_bytes
                           + io_bytes),
            "weight_bytes": float(weight_bytes)}


def decode_step(*, layers: int, d_model: int, heads: int, kv_heads: int,
                head_dim: int, d_ff: int, vocab: int, slots: int,
                cached_positions: int, adapter_neurons: int,
                param_bytes: int = 2, kv_bytes: int = 2) -> dict:
    """One greedy decode step of a dense GQA transformer with a plastic
    fast-weight adapter, for `slots` streams whose caches hold
    `cached_positions` positions in all.

    Bytes: every layer's weights and the output head read once (the
    embedding gather reads `slots` rows, left out); the cached keys and
    values read once; each stream's adapter W_fast (float32) read and
    written, with its projections and rule.  Operations: 2 per weight per
    stream, and 4 * heads * head_dim per cached position per layer for
    the scores and the weighted sum.
    """
    per_layer = (d_model * heads * head_dim            # q
                 + 2 * d_model * kv_heads * head_dim   # k, v
                 + heads * head_dim * d_model          # o
                 + 3 * d_model * d_ff)                 # gate, up, down
    weights = layers * per_layer + vocab * d_model
    n = adapter_neurons
    adapter_bytes = (2 * slots * n * n * F32           # W_fast in and out
                     + 2 * d_model * n * param_bytes   # p_in, p_out
                     + 4 * n * n * F32)                # rule
    kv = 2 * layers * kv_heads * head_dim * cached_positions * kv_bytes
    flops = (2 * slots * weights
             + 4 * heads * head_dim * layers * cached_positions
             + slots * (4 * d_model * n + 10 * n * n))
    return {"flops": float(flops),
            "bytes": float(weights * param_bytes + kv + adapter_bytes),
            "weight_bytes": float(weights * param_bytes)}
