"""Plain reference of DeepSeek-V2 (latent attention, routed experts) with a
plastic fast-weight adapter, for one chip's share of the experts.

Written from the published description (arXiv 2405.04434; hf
`DeepseekV2ForCausalLM`, `config.json` of DeepSeek-V2-Lite), in
straightforward `jax.numpy` and float32 at `highest` precision, importing
nothing of the program: no kernels, no cache, no batching.  Per layer

    x = x + Wo . MLA(RMSNorm(x))
    x = x + FFN(RMSNorm(x))        SwiGLU in the first `first_k_dense_replace`
                                   layers, the MoE below after them

MLA without q compression, per head: q = Wq h split into a nope part and a
rope part; [c_kv, k_pe] = Wkv_a h; [k_nope, v] = Wkv_b RMSNorm(c_kv); the
rope parts rotated (k_pe once, shared by every head); causal softmax of
(q_nope.k_nope + q_pe.k_pe) * (nope + rope)^-1/2 * mscale^2, with YaRN's
mscale = 0.1 * mscale_all_dim * ln(factor) + 1; o = Wo (p v).  This is the
plain, unabsorbed form: k and v are built for every position.  MoE:
softmax over the router's published width, greedy top-k, no
renormalisation where `norm_topk_prob` is false; the chosen experts'
SwiGLU outputs weighted by their gates, plus the shared experts (one
SwiGLU of n_shared * moe_intermediate_size).  As on the chip, only the
held experts (`first_held_expert` and the `n_routed_experts` after it)
add their part: the reference is given the same share.  Logits =
RMSNorm(x) . W_head, untied.

Departures from the published code, none of which changes the equations:
  * the rotary embedding gathers each head's interleaved pairs into
    halves, then applies the half-split rotation, as the published code
    does; its cos and sin stay float32 where the published code caches
    them in bf16;
  * YaRN's frequencies follow the published formulas (`_inv_freq`);
  * `routed_scaling_factor` is 1 in this configuration and not applied;
  * attention runs in query blocks of 1024, so the scores of a 3072-long
    sequence fit beside the weights.

The adapter, the served-token gap and the control are `lm_pool`'s
(`bench/reference/lm_pool.py`): `adapter_rollout` and `served_gap` are
imported from it, and ``quant=True`` rounds every weight matrix to int8
with one scale per output channel, one precision step below bf16.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from bench.harness import load_module

_LM = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "lm_pool.py"), "bench_reference_lm_pool")
served_gap = _LM.served_gap
adapter_rollout = _LM.adapter_rollout
_mm, _rms, _q8 = _LM._mm, _LM._rms, _LM._q8

F32 = jnp.float32
BLOCK = 1024


def dims(cfg: dict) -> tuple:
    """The sizes the reference needs, as a hashable static argument."""
    ys = cfg["rope_scaling"]
    return (("layers", cfg["num_hidden_layers"]), ("d", cfg["hidden_size"]),
            ("heads", cfg["num_attention_heads"]),
            ("r", cfg["kv_lora_rank"]), ("nope", cfg["qk_nope_head_dim"]),
            ("rope", cfg["qk_rope_head_dim"]), ("vd", cfg["v_head_dim"]),
            ("ff", cfg["intermediate_size"]),
            ("moe_ff", cfg["moe_intermediate_size"]),
            ("first_dense", cfg["first_k_dense_replace"]),
            ("held", cfg["n_routed_experts"]),
            ("first_held", cfg["first_held_expert"]),
            ("router", cfg["router_experts"]),
            ("top_k", cfg["num_experts_per_tok"]),
            ("norm_topk", cfg["norm_topk_prob"]),
            ("shared", cfg["n_shared_experts"]),
            ("vocab", cfg["vocab_size"]), ("eps", cfg["rms_norm_eps"]),
            ("theta", float(cfg["rope_theta"])),
            ("yarn", (float(ys["factor"]),
                      int(ys["original_max_position_embeddings"]),
                      float(ys["beta_fast"]), float(ys["beta_slow"]),
                      float(ys["mscale"]), float(ys["mscale_all_dim"]))),
            ("n", cfg["adapter_neurons"]),
            ("lam", cfg["adapter_trace_decay"]),
            ("w_clip", cfg["adapter_w_clip"]))


# ---- weights --------------------------------------------------------------


def _shapes(cfg: dict) -> dict:
    """(shape, dtype, std) of every weight, in the program's layout; std
    None means ones, 0 zeros."""
    c = dict(dims(cfg))
    d, h, r, v = c["d"], c["heads"], c["r"], c["vocab"]
    bf = jnp.dtype(cfg["torch_dtype"])
    n = cfg["adapter_neurons"]

    def mat(L, i, o, dt=bf):
        return ((L, i, o), dt, i ** -0.5)

    def attn(L):
        return {"norm": ((L, d), bf, None),
                "wq": mat(L, d, h * (c["nope"] + c["rope"])),
                "wkv_a": mat(L, d, r + c["rope"]),
                "kv_norm": ((L, r), bf, None),
                "wkv_b": mat(L, r, h * (c["nope"] + c["vd"])),
                "wo": mat(L, h * c["vd"], d)}

    L0, L1 = c["first_dense"], c["layers"] - c["first_dense"]
    e, f, fs = c["held"], c["moe_ff"], c["shared"] * c["moe_ff"]
    return {
        "embed": ((v, d), bf, d ** -0.5),
        "segments": [
            {"attn": attn(L0),
             "mlp": {"norm": ((L0, d), bf, None), "w_gate": mat(L0, d, c["ff"]),
                     "w_up": mat(L0, d, c["ff"]),
                     "w_down": mat(L0, c["ff"], d)}},
            {"attn": attn(L1),
             "moe": {"norm": ((L1, d), bf, None),
                     "router": mat(L1, d, c["router"], F32),
                     "w_gate": ((L1, e, d, f), bf, d ** -0.5),
                     "w_up": ((L1, e, d, f), bf, d ** -0.5),
                     "w_down": ((L1, e, f, d), bf, f ** -0.5),
                     "ws_gate": mat(L1, d, fs), "ws_up": mat(L1, d, fs),
                     "ws_down": mat(L1, fs, d)}}],
        "final_norm": ((d,), bf, None),
        "lm_head": ((d, v), bf, d ** -0.5),
        "adapter": {"p_in": ((d, n), bf, d ** -0.5),
                    "p_out": ((n, d), bf, n ** -0.5),
                    "theta": ((4, n, n), F32,
                              cfg["adapter_theta_scale"] * n ** -0.5),
                    "scale": ((), F32, 0)},
    }


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def make_weights(cfg: dict, key):
    """Every weight from `key`, on the device, in ONE jitted call, in the
    dtype it is served in (`_shapes`).  The adapter's readout gain is the
    configuration's ``adapter_scale``."""
    leaves, treedef = jax.tree.flatten(_shapes(cfg), is_leaf=_is_spec)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (shape, dt, std), k in zip(leaves, keys):
            if std is None:
                out.append(jnp.ones(shape, dt))
            elif std == 0:
                out.append(jnp.zeros(shape, dt))
            else:
                out.append((jax.random.normal(k, shape, dt)
                            * jnp.asarray(std, dt)).astype(dt))
        return jax.tree.unflatten(treedef, out)

    w = jax.jit(build)(key)
    w["adapter"]["scale"] = jnp.asarray(cfg["adapter_scale"], F32)
    return w


# ---- the decoder ------------------------------------------------------------


def _yarn_mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _inv_freq(c):
    """YaRN inverse frequencies of the rope part and the cos/sin factor."""
    dim, base = c["rope"], c["theta"]
    factor, orig, beta_fast, beta_slow, msc, msc_all = c["yarn"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    inter = 1.0 / (factor * base ** (jnp.arange(0, dim, 2, dtype=F32) / dim))

    def corr_dim(n_rot):
        return (dim * math.log(orig / (n_rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask,
            _yarn_mscale(factor, msc) / _yarn_mscale(factor, msc_all))


def _rope(x, c):
    """x (S, heads, rope): interleaved pairs into halves, then rotated."""
    s, nh, d = x.shape
    x = x.reshape(s, nh, d // 2, 2).transpose(0, 1, 3, 2).reshape(s, nh, d)
    inv, msc = _inv_freq(c)
    emb = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    emb = jnp.concatenate([emb, emb], -1)
    cos, sin = (jnp.cos(emb) * msc)[:, None], (jnp.sin(emb) * msc)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _f32(w, quant, axis=-2):
    w = w.astype(F32)
    return _q8(w, axis) if quant else w


def _swiglu(h, gate, up, down):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", h, gate))
               * _mm("sd,df->sf", h, up), down)


def _mla(a, x, c, quant):
    s = x.shape[0]
    nh, nope, rope, vd, r = c["heads"], c["nope"], c["rope"], c["vd"], c["r"]
    h = _rms(x, a["norm"].astype(F32), c["eps"])
    q = _mm("sd,dk->sk", h, _f32(a["wq"], quant)).reshape(s, nh, nope + rope)
    kv_a = _mm("sd,dk->sk", h, _f32(a["wkv_a"], quant))
    ckv = _rms(kv_a[:, :r], a["kv_norm"].astype(F32), c["eps"])
    kv = _mm("sr,rk->sk", ckv, _f32(a["wkv_b"], quant)).reshape(
        s, nh, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], c)], -1)
    k_pe = jnp.broadcast_to(_rope(kv_a[:, None, r:], c), (s, nh, rope))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    m = _yarn_mscale(c["yarn"][0], c["yarn"][5]) if c["yarn"][5] else 1.0
    scale = scale * m * m
    outs = []
    for lo in range(0, s, BLOCK):              # query blocks
        hi = min(lo + BLOCK, s)
        sc = _mm("qhd,khd->hqk", q[lo:hi], k[:hi]) * scale
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        outs.append(_mm("hqk,khd->qhd", jax.nn.softmax(sc, -1), v[:hi]))
    o = jnp.concatenate(outs, 0).reshape(s, nh * vd)
    return x + _mm("sk,kd->sd", o, _f32(a["wo"], quant))


def _moe(m, x, c, quant):
    h = _rms(x, m["norm"].astype(F32), c["eps"])
    probs = jax.nn.softmax(_mm("sd,de->se", h, _f32(m["router"], quant)), -1)
    gates, idx = jax.lax.top_k(probs, c["top_k"])
    if c["norm_topk"]:
        gates = gates / gates.sum(-1, keepdims=True)
    out = _swiglu(h, _f32(m["ws_gate"], quant), _f32(m["ws_up"], quant),
                  _f32(m["ws_down"], quant))
    # the held experts only: each one's gate where chosen, else 0
    held = c["first_held"] + jnp.arange(c["held"])
    w = jnp.where(idx[:, :, None] == held, gates[:, :, None], 0.0).sum(1)
    g = jax.nn.silu(_mm("sd,edf->sef", h, _f32(m["w_gate"], quant)))
    u = _mm("sd,edf->sef", h, _f32(m["w_up"], quant))
    y = _mm("sef,efd->sed", g * u, _f32(m["w_down"], quant))
    return x + out + _mm("sed,se->sd", y, w)


@functools.partial(jax.jit, static_argnames=("dims_", "quant"))
def hidden(w, tokens, *, dims_, quant=False):
    """tokens (S,) -> the hidden state before the final norm, (S, D)."""
    c = dict(dims_)
    x = _f32(w["embed"], quant, axis=-1)[tokens]
    dense, moe = w["segments"]

    def dense_layer(x, p):
        x = _mla(p["attn"], x, c, quant)
        m = p["mlp"]
        hn = _rms(x, m["norm"].astype(F32), c["eps"])
        return x + _swiglu(hn, _f32(m["w_gate"], quant),
                           _f32(m["w_up"], quant),
                           _f32(m["w_down"], quant)), None

    def moe_layer(x, p):
        return _moe(p["moe"], _mla(p["attn"], x, c, quant), c, quant), None

    x, _ = jax.lax.scan(dense_layer, x, dense)
    x, _ = jax.lax.scan(moe_layer, x, moe)
    return x


@functools.partial(jax.jit, static_argnames=("dims_", "quant"))
def logits(w, h, *, dims_, quant=False):
    """h (S, D) before the final norm -> logits (S, V), untied head."""
    c = dict(dims_)
    hn = _rms(h, w["final_norm"].astype(F32), c["eps"])
    return _mm("sd,dv->sv", hn, _f32(w["lm_head"], quant))
