"""Plain reference of a dense GQA decoder with a plastic fast-weight adapter.

Written from the published descriptions, in straightforward `jax.numpy`
and float32 at `highest` precision, importing nothing of the program: no
kernels, no cache, no batching.  Qwen3 (hf `Qwen3ForCausalLM`): per layer

    x = x + Wo . attn(RoPE(RMSNorm_hd(Wq . RMSNorm(x))),
                      RoPE(RMSNorm_hd(Wk . RMSNorm(x))), Wv . RMSNorm(x))
    x = x + Wdown . (silu(Wgate . RMSNorm(x)) * Wup . RMSNorm(x))

with grouped KV heads (query head h reads KV head h // (H / KV)), causal
softmax attention, rotary embedding on the two halves of each head, then
logits = RMSNorm(x) . E^T with the tied embedding E.

The plastic adapter (FireFly-P, paper Sec. II) runs once per decoded
token on the hidden state h before the final norm: a presynaptic LIF
population driven by h . P_in, then one spiking plastic layer with its own
W_fast under the four-term rule, tau_m 2, threshold 1, reset 0:

    v1 += (h . P_in - v1) / 2;  s1 = [v1 >= 1];  v1 = 0 where s1
    S1  = lam S1 + s1
    v2 += (s1 . W - v2) / 2;    s2 = [v2 >= 1];  v2 = 0 where s2
    S2  = lam S2 + s2
    W   = clip(W + a S1 S2^T + b S1 + c S2 + d, -w_clip, w_clip)

Its readout, h += scale * s2 . P_out, adds nothing at the configuration's
scale 0, so the logits do not depend on it; W_fast is compared directly.

The weights are the benchmark's own (`make_weights`), in the layout the
program is handed.  The control is one precision step below the
configuration: ``quant=True`` rounds every weight matrix to int8 with one
scale per output channel (below bfloat16), and the adapter's ``low=True``
holds W_fast in bfloat16 (below float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def dims(cfg: dict) -> tuple:
    """The sizes the reference needs, as a hashable static argument."""
    return (("layers", cfg["num_hidden_layers"]), ("d", cfg["hidden_size"]),
            ("heads", cfg["num_attention_heads"]),
            ("kv", cfg["num_key_value_heads"]), ("hd", cfg["head_dim"]),
            ("ff", cfg["intermediate_size"]), ("vocab", cfg["vocab_size"]),
            ("eps", cfg["rms_norm_eps"]), ("theta", float(cfg["rope_theta"])),
            ("n", cfg["adapter_neurons"]),
            ("lam", cfg["adapter_trace_decay"]),
            ("w_clip", cfg["adapter_w_clip"]))


# ---- weights --------------------------------------------------------------


def _shapes(cfg: dict) -> dict:
    """(shape, dtype, std) of every weight, in the program's layout; std
    None means ones, 0 zeros."""
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    f, v, n, L = (cfg["intermediate_size"], cfg["vocab_size"],
                  cfg["adapter_neurons"], cfg["num_hidden_layers"])
    bf = jnp.dtype(cfg["torch_dtype"])

    def mat(i, o):
        return ((L, i, o), bf, i ** -0.5)

    return {
        "embed": ((v, d), bf, d ** -0.5),
        "segments": [{
            "attn": {"wq": mat(d, h * hd), "wk": mat(d, kv * hd),
                     "wv": mat(d, kv * hd), "wo": mat(h * hd, d),
                     "norm": ((L, d), bf, None),
                     "q_norm": ((L, hd), bf, None),
                     "k_norm": ((L, hd), bf, None)},
            "mlp": {"norm": ((L, d), bf, None), "w_gate": mat(d, f),
                    "w_up": mat(d, f), "w_down": mat(f, d)}}],
        "final_norm": ((d,), bf, None),
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
    spec = _shapes(cfg)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_spec)

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


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (S, heads, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freqs                 # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _q8(w, axis):
    """Symmetric int8 rounding with one scale per output channel (the
    contraction runs over `axis`), dequantized to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _f32(w, quant, axis=-2):
    w = w.astype(F32)
    return _q8(w, axis) if quant else w


@functools.partial(jax.jit, static_argnames=("dims_", "quant"))
def hidden(w, tokens, *, dims_, quant=False):
    """tokens (S,) -> the hidden state before the final norm, (S, D)."""
    c = dict(dims_)
    s = tokens.shape[0]
    g = c["heads"] // c["kv"]
    emb = _f32(w["embed"], quant, axis=-1)
    x = emb[tokens]
    pos = jnp.arange(s)
    causal = jnp.tril(jnp.ones((s, s), bool))
    seg = w["segments"][0]

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        hn = _rms(x, a["norm"].astype(F32), c["eps"])
        q = _mm("sd,dk->sk", hn, _f32(a["wq"], quant)).reshape(
            s, c["heads"], c["hd"])
        k = _mm("sd,dk->sk", hn, _f32(a["wk"], quant)).reshape(
            s, c["kv"], c["hd"])
        v = _mm("sd,dk->sk", hn, _f32(a["wv"], quant)).reshape(
            s, c["kv"], c["hd"])
        q = _rope(_rms(q, a["q_norm"].astype(F32), c["eps"]), pos,
                  c["theta"])
        k = _rope(_rms(k, a["k_norm"].astype(F32), c["eps"]), pos,
                  c["theta"])
        k = jnp.repeat(k, g, axis=1)                       # (S, H, hd)
        v = jnp.repeat(v, g, axis=1)
        sc = _mm("qhd,khd->hqk", q, k) * (c["hd"] ** -0.5)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = _mm("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        x = x + _mm("sk,kd->sd", o.reshape(s, -1), _f32(a["wo"], quant))
        hn = _rms(x, m["norm"].astype(F32), c["eps"])
        gate = jax.nn.silu(_mm("sd,df->sf", hn, _f32(m["w_gate"], quant)))
        up = _mm("sd,df->sf", hn, _f32(m["w_up"], quant))
        return x + _mm("sf,fd->sd", gate * up,
                       _f32(m["w_down"], quant)), None

    x, _ = jax.lax.scan(layer, x, seg)
    return x


@functools.partial(jax.jit, static_argnames=("dims_", "quant"))
def logits(w, h, *, dims_, quant=False):
    """h (S, D) before the final norm -> logits (S, V), tied head."""
    c = dict(dims_)
    hn = _rms(h, w["final_norm"].astype(F32), c["eps"])
    return _mm("sd,vd->sv", hn, _f32(w["embed"], quant, axis=-1))


@jax.jit
def served_gap(ref_logits, chosen, mask):
    """Per position, how far the logit of `chosen` lies below the best
    (0 where the chosen token is the reference's argmax); 0 where
    `mask` is off.  ref_logits (T, V), chosen (T,), mask (T,)."""
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], -1)[:, 0]
    return jnp.where(mask, best - got, 0.0)


def _bf16_round(a):
    """float32 -> the nearest bfloat16 value (ties to even), kept in
    float32.  Integer bit arithmetic, so no compiler may skip the rounding
    as excess precision, as it may a float32 -> bfloat16 -> float32 cast."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), F32)


@functools.partial(jax.jit, static_argnames=("dims_", "low"))
def adapter_rollout(w, hs, mask, *, dims_, low=False):
    """The adapter from a fresh session over the hidden states `hs` (T, D)
    of its decode steps; steps where `mask` is off leave the state as it
    is.  Returns the final W_fast (N, N).  ``low=True`` is the control:
    W_fast held in bfloat16, one step below the configuration's float32."""
    c = dict(dims_)
    n, lam = c["n"], c["lam"]
    p_in = w["adapter"]["p_in"].astype(F32)
    a, b, cc, d = w["adapter"]["theta"].astype(F32)
    z = jnp.zeros((n,), F32)

    def step(carry, xs):
        wf, v1, tr1, v2, tr2 = carry
        h, on = xs
        v1n = v1 + (_mm("d,dn->n", h, p_in) - v1) * 0.5
        s1 = (v1n >= 1.0).astype(F32)
        v1n = jnp.where(s1 > 0, 0.0, v1n)
        tr1n = lam * tr1 + s1
        v2n = v2 + (_mm("n,nm->m", s1, wf) - v2) * 0.5
        s2 = (v2n >= 1.0).astype(F32)
        v2n = jnp.where(s2 > 0, 0.0, v2n)
        tr2n = lam * tr2 + s2
        pre, post = tr1n[:, None], tr2n[None, :]
        wn = jnp.clip(wf + a * (pre * post) + b * pre + cc * post + d,
                      -c["w_clip"], c["w_clip"])
        if low:
            wn = _bf16_round(wn)
        new = (wn, v1n, tr1n, v2n, tr2n)
        return tuple(jnp.where(on, x, o) for x, o in zip(new, carry)), None

    init = (jnp.zeros((n, n), F32), z, z, z, z)
    (wf, *_), _ = jax.lax.scan(step, init, (hs, mask))
    return wf
