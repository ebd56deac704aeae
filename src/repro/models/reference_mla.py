"""Plain reference of DeepSeek-V2's forward pass (arXiv 2405.04434; hf
`DeepseekV2ForCausalLM`), for the tests of `models.mla` and
`models.moe.apply_held`.

Straightforward `jax.numpy` in float32 under
``jax.default_matmul_precision("highest")``: one sequence, no kernels,
no cache, no batching, and nothing of the program imported but its
config.  It reads the program's parameter layout (`transformer.plan`).
Per layer:

    x = x + MLA(RMSNorm(x))                      (the plain, unabsorbed form)
    x = x + FFN(RMSNorm(x))                      (SwiGLU in layer 0, MoE after)

MLA without q compression: q = Wq h split into nope and rope parts per
head; [c_kv, k_pe] = Wkv_a h; [k_nope, v] = Wkv_b RMSNorm(c_kv); the rope
parts are rotated (k_pe once, shared by every head); scores
(q_nope.k_nope + q_pe.k_pe) times (nope + rope)^-1/2 * mscale^2; causal
softmax; o = Wo (p v).  MoE: softmax over the router's experts, greedy
top-k, gates renormalised only with ``norm_topk_prob``; the sum of the
chosen experts' SwiGLU outputs weighted by their gates, plus the shared
experts.  A chip's share (``cfg.moe.first_held``, ``num_experts``) sums
only the chosen experts it holds, as the program does.

Departures from the published code, none of which changes the equations:
  * rotary embedding: the published code gathers each head's interleaved
    pairs into halves, then applies the half-split rotation; this does
    the same.  Its cos and sin are cached in the model's dtype (bf16);
    here they stay float32;
  * YaRN's frequencies and mscale are computed from the config's
    `YarnScaling` by the published formulas (`_yarn_inv_freq`);
  * ``routed_scaling_factor`` is 1 in DeepSeek-V2-Lite and not applied.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _yarn_mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _yarn_inv_freq(cfg: ModelConfig):
    """(inverse frequencies (rope/2,), cos/sin factor) of the rope part."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freq_extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ys = cfg.rope_scaling
    if ys is None:
        return freq_extra, 1.0
    freq_inter = 1.0 / (ys.factor * base ** (jnp.arange(0, dim, 2,
                                                        dtype=F32) / dim))

    def corr_dim(n_rot):
        return (dim * math.log(ys.original_max_position
                               / (n_rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(ys.beta_fast)), 0)
    high = min(math.ceil(corr_dim(ys.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    mask = 1.0 - ramp
    inv = freq_inter * (1 - mask) + freq_extra * mask
    return inv, (_yarn_mscale(ys.factor, ys.mscale)
                 / _yarn_mscale(ys.factor, ys.mscale_all_dim))


def _rope(x, cfg: ModelConfig):
    """x (S, heads, rope): interleaved pairs into halves, then rotated."""
    s, nh, d = x.shape
    x = x.reshape(s, nh, d // 2, 2).transpose(0, 1, 3, 2).reshape(s, nh, d)
    inv, msc = _yarn_inv_freq(cfg)
    freqs = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)               # (S, rope)
    cos, sin = (jnp.cos(emb) * msc)[:, None], (jnp.sin(emb) * msc)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _softmax_scale(cfg: ModelConfig):
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        m = _yarn_mscale(ys.factor, ys.mscale_all_dim)
        scale = scale * m * m
    return scale


def mla(p, x, cfg: ModelConfig):
    """The attention sublayer's output (without the residual), x (S, D)."""
    s = x.shape[0]
    h_, nope, rope_d, vd, r = (cfg.n_heads, cfg.qk_nope_head_dim,
                               cfg.qk_rope_head_dim, cfg.v_head_dim,
                               cfg.kv_lora_rank)
    h = _rms(x, p["norm"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(s, h_, nope + rope_d)
    kv_a = h @ p["wkv_a"]
    c = _rms(kv_a[:, :r], p["kv_norm"], cfg.norm_eps)
    kv = (c @ p["wkv_b"]).reshape(s, h_, nope + vd)
    q_pe = _rope(q[..., nope:], cfg)
    k_pe = _rope(kv_a[:, None, r:], cfg)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    kf = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_pe, (s, h_, rope_d))], -1)
    sc = jnp.einsum("qhd,khd->hqk", qf, kf) * _softmax_scale(cfg)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), kv[..., nope:])
    return o.reshape(s, h_ * vd) @ p["wo"]


def moe(p, x, cfg: ModelConfig, shared: bool = True):
    """The MoE sublayer's output (without the residual), x (S, D): the
    held experts' part, plus the shared experts where `shared`."""
    m = cfg.moe
    h = _rms(x, p["norm"], cfg.norm_eps)
    probs = jax.nn.softmax(h @ p["router"], -1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gates = gates / gates.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(m.num_experts):
        w = jnp.where(idx == m.first_held + e, gates, 0.0).sum(-1)
        out = out + w[:, None] * _swiglu(h, p["w_gate"][e], p["w_up"][e],
                                         p["w_down"][e])
    if shared and m.n_shared:
        out = out + _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])
    return out


def forward(params, tokens, cfg: ModelConfig):
    """tokens (S,) -> logits (S, V), float32 at `highest`."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        x = w["embed"][tokens]
        for seg in w["segments"]:
            n = jax.tree.leaves(seg)[0].shape[0]
            for i in range(n):
                p = jax.tree.map(lambda a: a[i], seg)
                x = x + mla(p["attn"], x, cfg)
                if "moe" in p:
                    x = x + moe(p["moe"], x, cfg)
                else:
                    m = p["mlp"]
                    x = x + _swiglu(_rms(x, m["norm"], cfg.norm_eps),
                                    m["w_gate"], m["w_up"], m["w_down"])
        return _rms(x, w["final_norm"], cfg.norm_eps) @ w["lm_head"]
