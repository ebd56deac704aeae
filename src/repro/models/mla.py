"""Multi-head latent attention (DeepSeek-V2, arXiv 2405.04434 §2.1).

Without query compression, per layer and position:

    q            = Wq . h                      (H heads of nope + rope)
    [c_kv, k_pe] = Wkv_a . h                   (kv_lora_rank + rope)
    c            = RMSNorm(c_kv)
    [k_nope, v]  = Wkv_b . c                   (H heads of nope + v)
    q_pe, k_pe   = RoPE(q_pe), RoPE(k_pe)      (k_pe: one head, shared)
    o            = Wo . softmax(scale * (q_nope.k_nope + q_pe.k_pe)) v

RoPE acts on the 64-wide parts as the published code does: each head's
interleaved pairs are first gathered into halves, then rotated by the
half-split rule, under YaRN frequencies when ``cfg.rope_scaling`` is set.
The softmax scale is (nope + rope)^-1/2 times YaRN's mscale squared.

The cache holds per position only c (kv_lora_rank wide) and the rotated
k_pe (rope wide), 576 values at DeepSeek-V2's widths.  Prefill (`apply`)
runs the plain form above; decode (`decode_step`) the absorbed form: the
query's nope part goes through W_uk into the latent, scores are taken
against the cached c and k_pe, and the weighted sum of c leaves through
W_uv, so no position's k or v is ever rebuilt.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_constraint
from repro.models.attention import _write_at
from repro.models.config import ModelConfig
from repro.models.layers import ParamDesc, rms_norm


def plan(cfg: ModelConfig, stack: int = 0) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim

    def desc(shape, spec, **kw):
        if stack:
            shape, spec = (stack, *shape), (None, *spec)
        return ParamDesc(shape, spec, dtype=cfg.dtype, **kw)

    return {
        "norm": desc((d,), (None,), init="ones"),
        "wq": desc((d, h * (nope + rope_d)), ("data", "model"), fan_in=d),
        "wkv_a": desc((d, r + rope_d), ("data", None), fan_in=d),
        "kv_norm": desc((r,), (None,), init="ones"),
        "wkv_b": desc((r, h * (nope + vd)), (None, "model"), fan_in=r),
        "wo": desc((h * vd, d), ("model", "data"), fan_in=h * vd),
    }


def plan_cache(cfg: ModelConfig, batch: int, max_len: int,
               n_layers: int) -> dict:
    """The latent cache of one attention stack: the normed c and the
    rotated k_pe of every position."""
    spec_b = None if batch == 1 else "data"
    spec_s = ("data", "model") if batch == 1 else "model"

    def desc(width):
        return ParamDesc((n_layers, batch, max_len, width),
                         (None, spec_b, spec_s, None), init="zeros",
                         dtype=cfg.dtype)

    return {"ckv": desc(cfg.kv_lora_rank), "kpe": desc(cfg.qk_rope_head_dim)}


def _yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_freqs(cfg: ModelConfig):
    """Inverse frequencies of the rope part, (rope/2,), and the factor on
    cos and sin (YaRN's mscale over mscale_all_dim; 1 without YaRN)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ys = cfg.rope_scaling
    if ys is None:
        return extra, 1.0
    inter = extra / ys.factor

    def corr(rot):
        return (dim * math.log(ys.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(ys.beta_fast)), 0)
    high = min(math.ceil(corr(ys.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra_mask = 1.0 - ramp
    inv = inter * (1.0 - extra_mask) + extra * extra_mask
    return inv, (_yarn_get_mscale(ys.factor, ys.mscale)
                 / _yarn_get_mscale(ys.factor, ys.mscale_all_dim))


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        m = _yarn_get_mscale(ys.factor, ys.mscale_all_dim)
        scale = scale * m * m
    return scale


def _rope(x, positions, cfg: ModelConfig):
    """x (B,S,heads,rope), positions (B,S): interleaved pairs gathered into
    halves, then the half-split rotation."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*x.shape[:-2], d)
    inv, msc = rope_freqs(cfg)
    ang = positions[..., None].astype(jnp.float32) * inv        # (B,S,d/2)
    cos = (jnp.cos(ang) * msc)[..., None, :]
    sin = (jnp.sin(ang) * msc)[..., None, :]
    x1 = x[..., :d // 2].astype(jnp.float32)
    x2 = x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _project(params, h, cfg: ModelConfig, positions):
    """h (B,S,D) normed -> q_nope (B,S,H,nope), q_pe (B,S,H,rope) rotated,
    c (B,S,r) normed, k_pe (B,S,rope) rotated."""
    b, s, _ = h.shape
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = jnp.einsum("bsd,dk->bsk", h, params["wq"]).reshape(
        b, s, cfg.n_heads, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv_a = jnp.einsum("bsd,dk->bsk", h, params["wkv_a"])
    c = rms_norm(kv_a[..., :cfg.kv_lora_rank], params["kv_norm"],
                 cfg.norm_eps)
    k_pe = kv_a[..., cfg.kv_lora_rank:][:, :, None, :]
    q_pe = _rope(q_pe, positions, cfg)
    k_pe = _rope(k_pe, positions, cfg)[:, :, 0]
    return q_nope, q_pe, c, k_pe


def apply(params, x, cfg: ModelConfig, positions=None):
    """Full-sequence causal MLA (prefill), plain form.  x (B,S,D) ->
    (x + attn, (c (B,S,r), k_pe (B,S,rope))) — the latent cache rows."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    q_nope, q_pe, c, k_pe = _project(params, h, cfg, positions)
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    kv = jnp.einsum("bsr,rk->bsk", c, params["wkv_b"]).reshape(
        b, s, cfg.n_heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    sc = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe,
                       preferred_element_type=jnp.float32))
    sc = sc * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bsk,kd->bsd", o.reshape(b, s, -1), params["wo"])
    return x + shard_constraint(o, cfg.act_spec), (c, k_pe)


def decode_step(params, x, ckv, kpe, index, cfg: ModelConfig, active=None):
    """One token per stream, absorbed form.  x (B,1,D); ckv (B,Smax,r) and
    kpe (B,Smax,rope) the latent cache; `index` scalar or per-slot (B,),
    `active` as in `attention.decode_step`.  Returns (out, ckv, kpe)."""
    b = x.shape[0]
    index = jnp.asarray(index, jnp.int32)
    positions = (jnp.full((b, 1), index, jnp.int32) if index.ndim == 0
                 else index[:, None])
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    q_nope, q_pe, c, k_pe = _project(params, h, cfg, positions)
    ckv = _write_at(ckv, c, index, active)
    kpe = _write_at(kpe, k_pe, index, active)
    nope, vd, r = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    wkv_b = params["wkv_b"].reshape(r, cfg.n_heads, nope + vd)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
    # the query's nope part in the latent: q_nope . W_uk^T per head
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    sc = (jnp.einsum("bhr,bkr->bhk", q_lat, ckv,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bhp,bkp->bhk", q_pe[:, 0], kpe,
                       preferred_element_type=jnp.float32))
    sc = sc * softmax_scale(cfg)
    smax = ckv.shape[1]
    if index.ndim == 0:
        valid = (jnp.arange(smax) <= index)[None, None, :]
    else:
        valid = (jnp.arange(smax)[None, :] <= index[:, None])[:, None, :]
    p = jax.nn.softmax(jnp.where(valid, sc, -1e30), axis=-1)
    o_lat = jnp.einsum("bhk,bkr->bhr", p.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bk,kd->bd", o.reshape(b, -1), params["wo"])[:, None]
    return x + shard_constraint(o, ("data", None, None)), ckv, kpe
