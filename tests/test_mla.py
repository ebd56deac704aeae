"""DeepSeek-V2's latent attention and the held-share expert layer, against
the plain reference (`models.reference_mla`) at SMOKE size on the CPU.

The serving model is `held_share(SMOKE, 4, 8)`: 8 of the router's 16
routed experts, as one chip of an expert-parallel group holds them, with
the plastic adapter on (its readout gain is 0, the program's init, so the
logits are the backbone's).  Tolerances on the served logits (mean size
0.8), each with its reason:

  * float32, 2e-5 largest gap (2.7e-6 read): the program and the
    reference compute the same equations in float32 and differ only in
    summation order, the absorbed decode's association (q through W_uk,
    the latent sum through W_uv) and XLA's fusions; one bf16 rounding
    anywhere moves them by ~1e-2;
  * bfloat16, 0.3 largest and 0.03 mean gap (0.157 and 0.0137 read): the
    program stores weights, activations and the latent cache in bf16 (8
    bits of mantissa, an ulp of 0.016 at a logit of 3) through 3 layers;
    a wrong mask, position, rotation or expert moves the mean by tenths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_v2_lite as dsv2
from repro.models import factory, mla, moe, reference_mla as R
from repro.models import transformer as T
from repro.serving import LMScheduler, SessionStore

SHARE = dsv2.held_share(dsv2.SMOKE, 4, 8)


def _model(dtype="float32", neurons=8, impl="xla"):
    cfg = SHARE.with_(dtype=dtype, plastic_adapter=True,
                      adapter_neurons=neurons, adapter_impl=impl)
    model = factory.build(cfg)
    return model, model.init(jax.random.PRNGKey(3))


def _tokens(n, vocab, seed):
    return np.random.RandomState(seed).randint(0, vocab, n).astype(np.int32)


@pytest.mark.parametrize("dtype,largest,mean", [("float32", 2e-5, 2e-5),
                                                 ("bfloat16", 0.3, 0.03)])
def test_pool_prefill_then_decode_matches_reference(dtype, largest, mean):
    """Two streams of different prompt lengths are prefilled and admitted
    into a 3-slot pool; teacher-forced decode through the pool's latent
    cache gives, per slot, the reference's full-forward logits."""
    model, params = _model(dtype)
    sched = LMScheduler(model, params, slots=3, max_len=16)
    vocab = model.cfg.vocab
    seqs = {"a": _tokens(12, vocab, 1), "b": _tokens(12, vocab, 2)}
    plen = {"a": 3, "b": 6}
    for uid in seqs:
        sched.admit_prompt(uid, seqs[uid][:plen[uid]])
    ref = {u: np.asarray(R.forward(params, jnp.asarray(s), model.cfg))
           for u, s in seqs.items()}
    cache = sched.pool["cache"]
    active = sched._active_mask()
    step = jax.jit(model.decode_step)
    gaps = []
    for i in range(5):
        toks = np.zeros((3, 1), np.int32)
        for uid, slot in sched.user_slot.items():
            toks[slot, 0] = seqs[uid][plen[uid] + i]
        logits, cache = step(params, cache, jnp.asarray(toks),
                             active=active)
        for uid, slot in sched.user_slot.items():
            gaps.append(np.abs(np.asarray(logits[slot], np.float32)
                               - ref[uid][plen[uid] + i]))
    gaps = np.concatenate(gaps)
    assert gaps.max() <= largest and gaps.mean() <= mean, \
        (gaps.max(), gaps.mean())


def test_scheduler_tokens_follow_the_reference():
    """`LMScheduler.step` (greedy, float32) serves the reference's argmax
    wherever the reference's top two logits are apart; and it counts the
    held experts' load."""
    model, params = _model()
    sched = LMScheduler(model, params, slots=2, max_len=16)
    prompt = _tokens(4, model.cfg.vocab, 5)
    sched.admit_prompt("u", prompt)
    served = [sched.pending("u")]
    for _ in range(6):
        served.append(sched.step()["u"])
    seq = np.concatenate([prompt, served[:-1]])
    lg = np.asarray(R.forward(params, jnp.asarray(seq), model.cfg))[3:]
    top2 = np.sort(lg, -1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] > 1e-3
    assert clear.sum() >= 4
    np.testing.assert_array_equal(np.asarray(served)[clear],
                                  lg.argmax(-1)[clear])
    steps = sched.metrics.counter("lm_moe_steps").value
    held = sched.metrics.counter("lm_moe_held_assignments").value
    assert steps == 6
    # per step and MoE layer, the one stream routes top_k = 4 assignments
    # over 16 experts, of which this share holds 8
    assert 0 < held <= 6 * 4


def test_absorbed_decode_matches_the_plain_form():
    cfg = SHARE.with_(dtype="float32")
    p = jax.tree.map(lambda a: a[0],
                     T.init(cfg, jax.random.PRNGKey(4))["segments"][1]
                     ["attn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model))
    plain, (c, k_pe) = mla.apply(p, x, cfg)
    ckv = jnp.zeros((2, 12, cfg.kv_lora_rank)).at[:, :8].set(c[:, :8])
    kpe = jnp.zeros((2, 12, cfg.qk_rope_head_dim)).at[:, :8].set(k_pe[:, :8])
    out, ckv2, kpe2 = mla.decode_step(p, x[:, 8:9], ckv, kpe,
                                      jnp.array([8, 8]), cfg)
    np.testing.assert_allclose(out[:, 0], plain[:, 8], atol=1e-5)
    np.testing.assert_allclose(ckv2[:, 8], c[:, 8], atol=1e-6)
    np.testing.assert_allclose(kpe2[:, 8], k_pe[:, 8], atol=1e-6)


def _share_params(p, first, count):
    return dict(p, **{k: p[k][first:first + count]
                      for k in ("w_gate", "w_up", "w_down")})


def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight disjoint shares of 2 experts each: their held-range parts,
    with the shared experts (which every chip computes) counted once, sum
    to the reference's whole layer."""
    cfg = dsv2.SMOKE.with_(dtype="float32")
    p = jax.tree.map(lambda a: a[0],
                     T.init(cfg, jax.random.PRNGKey(6))["segments"][1]
                     ["moe"])
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 20, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole = R.moe(p, x[0], cfg)
        shared = whole - R.moe(p, x[0], cfg, shared=False)
    total = jnp.zeros_like(whole)
    counts = 0
    for first in range(0, 16, 2):
        part_cfg = dsv2.held_share(cfg, first, 2)
        out, n = moe.apply_held(_share_params(p, first, 2), x, part_cfg)
        total = total + (out - x)[0]
        counts = counts + n.sum()
    total = total - 7 * shared
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert int(counts) == 20 * cfg.moe.top_k      # every assignment, once


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    """A zero router ties every expert, so all 64 tokens choose experts
    0..3: each of them still gets all four experts' part."""
    cfg = dsv2.SMOKE.with_(dtype="float32")
    p = jax.tree.map(lambda a: a[0],
                     T.init(cfg, jax.random.PRNGKey(8))["segments"][1]
                     ["moe"])
    p["router"] = jnp.zeros_like(p["router"])
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, cfg.d_model))
    out, n = moe.apply_held(p, x, cfg)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([R.moe(p, x[i], cfg) for i in range(2)])
    np.testing.assert_allclose(out - x, want, atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(n).sum(0), [64] * 4 + [0] * 12)


def test_latent_session_evict_persist_restore_is_bitwise():
    """Evict mid-generation (persist), let a rival take the slot, re-admit
    into another slot: tokens and the whole latent session are bit-equal
    to an uninterrupted run."""
    model, params = _model("bfloat16")
    vocab = model.cfg.vocab
    prompt = _tokens(5, vocab, 11)

    base = LMScheduler(model, params, slots=2, max_len=16)
    base.admit_prompt("u", prompt)
    want = [base.step()["u"] for _ in range(6)]
    want_sess = jax.tree.map(np.asarray, base.session_view("u"))

    s = LMScheduler(model, params, slots=2, max_len=16,
                    store=SessionStore())
    s.admit_prompt("u", prompt)
    got = [s.step()["u"] for _ in range(3)]
    s.evict("u")
    s.admit_prompt("rival", _tokens(7, vocab, 12))
    s.step()
    s.admit_prompt("u", prompt)           # restored, the prompt ignored
    assert s.user_slot["u"] == 1
    got += [s.step()["u"] for _ in range(3)]
    assert got == want
    got_sess = jax.tree.map(np.asarray, s.session_view("u"))
    got_sess["cache"]["moe_load"] = want_sess["cache"]["moe_load"]
    jax.tree.map(np.testing.assert_array_equal, got_sess, want_sess)
    # the latent cache is what the session carries: c and k_pe per layer
    seg = got_sess["cache"]["segments"][1]
    assert set(seg) == {"ckv", "kpe"}
    assert seg["ckv"].shape == (2, 16, model.cfg.kv_lora_rank)


def test_published_share_plan_and_cache_sizes():
    """CONFIG with the 8-expert share: every layer and width as published;
    3.11 B parameters; 576 cached values a position and layer."""
    cfg = dsv2.held_share(dsv2.CONFIG, 0, 8)
    model = factory.build(cfg.with_(plastic_adapter=False))
    assert model.n_params() == pytest.approx(3.11e9, rel=0.01)
    plan = model.cache_plan(1, 1)
    per_pos = sum(int(np.prod(d.shape)) for seg in plan["segments"]
                  for d in seg.values())
    assert per_pos == 27 * 576
    assert plan["moe_load"].shape == (1, 8)
    assert factory.build(dsv2.CONFIG).n_params() == pytest.approx(
        15.7e9, rel=0.01)


def test_yarn_matches_the_reference_at_published_widths():
    inv, msc = mla.rope_freqs(dsv2.CONFIG)
    inv_r, msc_r = R._yarn_inv_freq(dsv2.CONFIG)
    np.testing.assert_allclose(inv, inv_r, rtol=1e-6)
    assert msc == msc_r == 1.0
    m = 0.1 * 0.707 * np.log(40) + 1
    assert mla.softmax_scale(dsv2.CONFIG) == pytest.approx(
        192 ** -0.5 * m * m)


def test_held_range_is_validated():
    bad = dsv2.SMOKE.with_(moe=dataclasses.replace(
        dsv2.SMOKE.moe, num_experts=8, first_held=12, router_experts=16))
    with pytest.raises(ValueError, match="outside the router"):
        factory.build(bad)
