"""Plain reference of a fleet of plastic LIF controllers (paper Sec. II-III).

Written from the published equations, in straightforward `jax.numpy`,
importing nothing of the program.  Every session owns its weights; one
four-term rule is shared.  Per timestep, for the input drive x:

    S_0   = lam * S_0 + x                        (input trace)
    for each layer i (weights W_i, membrane V_i, post trace S_{i+1}):
      I     = x @ W_i                            (psum)
      V     = V + (I - V) / tau_m
      hidden:  s = [V >= v_th];  V = v_reset where s
      readout: s = tanh(V);      V unchanged, output = V
      S_{i+1} = lam * S_{i+1} + s
      dW    = a * S_i S_{i+1}^T + b * S_i + c * S_{i+1} + d
      W     = clip(W + dW, -w_clip, w_clip)
      x     = s (hidden) | V (readout)

The psum runs at float32 `highest` precision, as the configuration states.
``precision="high"`` is the control: the same psum in three bfloat16
passes (each operand split into a bfloat16 head and tail, the tail-by-tail
product dropped), which is what `Precision.HIGH` computes on a TPU.  It is
emulated here so that it means the same on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def make_theta(cfg: dict, key) -> list:
    """The shared rule, one packed (4, N, M) array per layer, from `key`."""
    sizes = cfg["layer_sizes"]
    keys = jax.random.split(key, len(sizes) - 1)
    return [cfg["theta_scale"] * jax.random.normal(
        keys[i], (4, sizes[i], sizes[i + 1]), jnp.float32)
        for i in range(len(sizes) - 1)]


def _bf16_round(a):
    """float32 -> the nearest bfloat16 value (ties to even), kept in
    float32.  Integer bit arithmetic, so no compiler may skip the rounding
    as excess precision, as it may a float32 -> bfloat16 -> float32 cast."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(a):
    hi = _bf16_round(a)
    return hi, _bf16_round(a - hi)


def psum(x, w, precision: str):
    """Per-session x (B, N) @ w (B, N, M) -> (B, M)."""
    dot = functools.partial(jnp.einsum, "bn,bnm->bm", precision=HIGHEST)
    if precision == "highest":
        return dot(x, w)
    if precision == "high":
        xh, xl = _split(x)
        wh, wl = _split(w)
        return dot(xh, wh) + (dot(xh, wl) + dot(xl, wh))
    raise ValueError(f"precision must be 'highest' or 'high', got "
                     f"{precision!r}")


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _rollout(theta, w, v, tr, drives, *, cfg_items, precision):
    cfg = dict(cfg_items)
    lam, tau = cfg["trace_decay"], cfg["tau_m"]
    n_layers = len(w)

    def step(carry, x):
        w, v, tr = (list(c) for c in carry)
        tr[0] = lam * tr[0] + x
        for i in range(n_layers):
            v_new = v[i] + (psum(x, w[i], precision) - v[i]) * (1.0 / tau)
            if i < n_layers - 1:
                s = (v_new >= cfg["v_th"]).astype(jnp.float32)
                v[i] = jnp.where(s > 0, cfg["v_reset"], v_new)
                out = s
            else:
                s = jnp.tanh(v_new)
                v[i] = v_new
                out = v_new
            tr[i + 1] = lam * tr[i + 1] + s
            pre, post = tr[i][:, :, None], tr[i + 1][:, None, :]
            a, b, c, d = theta[i]
            dw = a * (pre * post) + b * pre + c * post + d
            w[i] = jnp.clip(w[i] + dw, -cfg["w_clip"], cfg["w_clip"])
            x = out
        return (tuple(w), tuple(v), tuple(tr)), x

    (w, v, tr), outs = jax.lax.scan(step, (tuple(w), tuple(v), tuple(tr)),
                                    drives)
    return w, v, tr, outs


def rollout(cfg: dict, theta, w, v, tr, drives, precision: str = "highest"):
    """K timesteps for every session.

    w: per-layer (B, N_i, M_i); v: per-layer (B, M_i); tr: the L + 1
    population traces (B, n_i); drives: (K, B, n_0), held or per step.
    Returns (w, v, tr, outs) with outs (K, B, M_last), the readout's
    membrane at each step.
    """
    keys = ("layer_sizes", "trace_decay", "tau_m", "v_th", "v_reset",
            "w_clip")
    items = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                  for k in keys)
    return _rollout(tuple(theta), tuple(w), tuple(v), tuple(tr),
                    jnp.asarray(drives, jnp.float32), cfg_items=items,
                    precision=precision)


def zero_state(cfg: dict, slots: int):
    """The state a fresh session starts from (paper Sec. II-B: zero
    weights, the rule builds the connectivity), for `slots` sessions."""
    s = cfg["layer_sizes"]
    w = tuple(jnp.zeros((slots, s[i], s[i + 1])) for i in range(len(s) - 1))
    v = tuple(jnp.zeros((slots, m)) for m in s[1:])
    tr = tuple(jnp.zeros((slots, n)) for n in s)
    return w, v, tr


@jax.jit
def compare(ref, prog, flip_at):
    """Per-session agreement of one call: `ref` and `prog` are
    (w, v, tr, outs) after the same call from the same state.

    Returns (flipped (B,), gap (B,)): a session is flipped where a hidden
    population trace differs by at least `flip_at` (its spike train
    differs), and its gap is the largest
    |prog - ref| / max(1, |ref|) over every weight, membrane, trace and
    readout of the call.
    """
    (rw, rv, rtr, routs), (pw, pv, ptr, pouts) = ref, prog
    b = routs.shape[1]

    def rel(r, p):                      # slot-major (B, ...) leaves
        r, p = r.reshape(b, -1), p.reshape(b, -1)
        return jnp.max(jnp.abs(p - r) / jnp.maximum(1.0, jnp.abs(r)), axis=1)

    flipped = jnp.zeros((b,), bool)
    for r, p in zip(rtr[1:-1], ptr[1:-1]):
        flipped |= jnp.max(jnp.abs(p - r), axis=1) >= flip_at
    gap = rel(jnp.swapaxes(routs, 0, 1), jnp.swapaxes(pouts, 0, 1))
    for r, p in zip(rw + rv + rtr, pw + pv + ptr):
        gap = jnp.maximum(gap, rel(r, p))
    return flipped, gap
