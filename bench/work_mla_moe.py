"""Operations and bytes that one decode step of a latent-attention MoE
decoder needs, from shapes: the least the algorithm has to do, as in
`bench/work.py`, for one chip's share of the routed experts.
"""
from __future__ import annotations

F32 = 4


def decode_step(*, layers: int, first_dense: int, d_model: int, heads: int,
                kv_lora_rank: int, nope: int, rope: int, v_head_dim: int,
                d_ff: int, moe_ff: int, held: int, router: int, top_k: int,
                shared: int, vocab: int, slots: int,
                cached_positions: float, adapter_neurons: int,
                param_bytes: int = 2, cache_bytes: int = 2) -> dict:
    """One greedy decode step for `slots` streams whose latent caches hold
    `cached_positions` positions in all, on a chip that holds `held` of
    the router's `router` experts in each MoE layer, with a plastic
    fast-weight adapter per stream.

    Bytes: every weight of this chip's share read once (the router in
    float32; the embedding gather reads `slots` rows, left out); the
    latent cache (c and k_pe, ``kv_lora_rank + rope`` values a position
    and layer) read once; each stream's adapter W_fast (float32) read and
    written, with its projections and rule.  Operations: 2 per weight per
    stream for every weight but the routed experts'; 2 per weight per
    routed assignment for the held experts, of which a step has
    ``slots * top_k * held / router`` per MoE layer under even routing;
    the absorbed attention's 2 * (kv_lora_rank + rope + kv_lora_rank) per
    head per cached position per layer (the scores against c and k_pe,
    the weighted sum of c); and the adapter's as in `work.decode_step`.
    """
    moe_layers = layers - first_dense
    attn = (d_model * heads * (nope + rope)              # wq
            + d_model * (kv_lora_rank + rope)            # wkv_a
            + kv_lora_rank * heads * (nope + v_head_dim)  # wkv_b
            + heads * v_head_dim * d_model)              # wo
    expert = 3 * d_model * moe_ff
    dense_w = (layers * attn + first_dense * 3 * d_model * d_ff
               + moe_layers * shared * expert + vocab * d_model)
    routed_w = moe_layers * held * expert
    router_w = moe_layers * d_model * router
    n = adapter_neurons
    adapter_bytes = (2 * slots * n * n * F32
                     + 2 * d_model * n * param_bytes
                     + 4 * n * n * F32)
    cache = layers * (kv_lora_rank + rope) * cached_positions * cache_bytes
    assignments = slots * top_k * held / router          # per MoE layer
    flops = (2 * slots * (dense_w + router_w)
             + 2 * assignments * moe_layers * expert
             + 2 * (2 * kv_lora_rank + rope) * heads * layers
             * cached_positions
             + slots * (4 * d_model * n + 10 * n * n))
    weight_bytes = (dense_w + routed_w) * param_bytes + router_w * F32
    return {"flops": float(flops),
            "bytes": float(weight_bytes + cache + adapter_bytes),
            "weight_bytes": float(weight_bytes)}
