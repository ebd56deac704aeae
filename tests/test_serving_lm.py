"""LM decode pool under mixed occupancy + the windowed resume contract.

Pins, mirroring tests/test_serving.py for the LM path:

  1. Mixed occupancy: vacant slots during LM decode are TRUE no-ops (the
     whole session row — KV/SSM cache, adapter fast weights, index, pending
     token — bit-frozen), and an active stream's greedy tokens and final
     session are invariant to neighbour churn, on xla and pallas-interpret,
     float32 and int8 adapter pools.
  2. `decode_window` (the plastic.decode_rollout route) is bit-identical to
     K sequential `step` calls on the same tokens — same cache writes, same
     adapter plasticity, same stochastic-round stream in quant mode.
  3. Resume bit-identity ACROSS a rollout-window boundary: evict ->
     persist -> displacement by a rival -> re-admit into a different slot
     between two decode windows leaves the second window's logits and the
     final session bit-equal to an uninterrupted run.
  4. `launch/serve.py`'s scheduler-admit path: the AdapterPool round-trips
     through a durable on-disk SessionStore bit-exactly, and resumed
     sessions keep learning with cumulative step counters.
  5. COMPILE AUDIT: `compiled_programs()` pins the exact per-entry-point
     executable counts after a canonical serve sequence — one program per
     op (per window length for the windowed path, per prompt length for
     prefill), telemetry variants one each, and ONLY the entry point whose
     shape legitimately changed may grow.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import factory
from repro.serving import AdapterPool, LMScheduler, SessionStore

IMPLS = ["xla", "pallas-interpret"]
DATAPATHS = ["float32", "int8"]
# the full matrix where the satellite demands it (mixed occupancy); a
# cheaper diagonal elsewhere — the benchmark sweeps the whole cube
DIAG = [("xla", "float32"), ("xla", "int8"), ("pallas-interpret", "int8")]

LAYOUT_ARCH = {"dense": "qwen3-4b", "ssm": "mamba2-1.3b",
               "moe": "deepseek-moe-16b"}


def _model(layout, impl, datapath, neurons=8):
    cfg = factory.build(LAYOUT_ARCH[layout], smoke=True).cfg
    cfg = cfg.with_(plastic_adapter=True, adapter_neurons=neurons,
                    adapter_impl=impl, adapter_quant=(datapath == "int8"))
    model = factory.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params["adapter"]["scale"] = jnp.float32(0.5)
    return model, params


def _prompt(uid, n, vocab):
    rng = np.random.RandomState(abs(hash(uid)) % (2 ** 31))
    return rng.randint(0, vocab, size=n).astype(np.int32)


def _np(tree):
    return jax.tree.map(np.asarray, tree)


def _assert_trees_equal(a, b):
    jax.tree.map(np.testing.assert_array_equal, a, b)


class TestMixedOccupancy:
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("datapath", DATAPATHS)
    def test_churn_invariance_and_vacant_freeze(self, impl, datapath):
        """An active stream's trajectory is invariant to neighbours
        admitting/evicting around it, and a vacant slot's entire session
        row is bit-unchanged by pool steps."""
        model, params = _model("dense", impl, datapath)
        vocab = model.cfg.vocab
        # reference: the stream decodes alone
        ref = LMScheduler(model, params, slots=3, max_len=24)
        ref.admit_prompt("keep", _prompt("keep", 6, vocab))
        ref_toks = [ref.step()["keep"] for _ in range(8)]
        ref_sess = _np(ref.session_view("keep"))

        # churn: a rival is admitted and evicted around every early step
        churn = LMScheduler(model, params, slots=3, max_len=24)
        churn.admit_prompt("keep", _prompt("keep", 6, vocab))
        toks = []
        for t in range(5):
            churn.admit_prompt(f"r{t}", _prompt(f"r{t}", 6, vocab))
            toks.append(churn.step()["keep"])
            churn.evict(f"r{t}")
        # the rivals' slot is now vacant: its row must be bit-frozen (not
        # just ignored) across further decode steps
        vslot = jnp.int32(1)
        vacant_before = _np(churn._take(churn.pool, vslot))
        for _ in range(3):
            toks.append(churn.step()["keep"])
        _assert_trees_equal(vacant_before, _np(churn._take(churn.pool,
                                                           vslot)))
        assert toks == ref_toks
        _assert_trees_equal(ref_sess, _np(churn.session_view("keep")))


class TestWindowedDecode:
    @pytest.mark.parametrize("impl,datapath", DIAG)
    def test_window_equals_sequential_steps(self, impl, datapath):
        """decode_window(K) == K step() calls, bitwise: tokens, pending
        token, and every session leaf (cache rows, adapter W_fast/traces,
        per-session counter — the quant dither stream included)."""
        model, params = _model("ssm", impl, datapath)
        vocab, k = model.cfg.vocab, 3
        a = LMScheduler(model, params, slots=2, max_len=16)
        a.admit_prompt("u", _prompt("u", 5, vocab))
        first = a.pending("u")
        seq_toks = [a.step()["u"] for _ in range(k)]
        sess_a = _np(a.session_view("u"))

        b = LMScheduler(model, params, slots=2, max_len=16)
        b.admit_prompt("u", _prompt("u", 5, vocab))
        window = np.array([first] + seq_toks[:-1], np.int32)
        logits = np.asarray(b.decode_window({"u": window})["u"])
        assert logits.shape == (k, vocab)
        assert [int(t) for t in logits.argmax(-1)] == seq_toks
        assert b.pending("u") == seq_toks[-1]
        _assert_trees_equal(sess_a, _np(b.session_view("u")))

    @pytest.mark.parametrize("impl,datapath", DIAG)
    def test_resume_across_window_boundary(self, impl, datapath):
        """Evict -> persist (archive) -> displacement -> re-admit into a
        DIFFERENT slot between two rollout windows: the second window's
        logits and the final session are bit-equal to an uninterrupted
        run."""
        model, params = _model("dense", impl, datapath)
        vocab, k = model.cfg.vocab, 3
        prompt = _prompt("u", 5, vocab)
        forced = _prompt("forced", 2 * (k - 1), vocab)

        ref = LMScheduler(model, params, slots=3, max_len=24,
                          store=SessionStore())
        ref.admit_prompt("u", prompt)
        w1 = np.concatenate([[ref.pending("u")], forced[:k - 1]]
                            ).astype(np.int32)
        ref.decode_window({"u": w1})
        w2 = np.concatenate([[ref.pending("u")], forced[k - 1:]]
                            ).astype(np.int32)
        ref_logits = np.asarray(ref.decode_window({"u": w2})["u"])
        ref_sess = _np(ref.session_view("u"))

        s = LMScheduler(model, params, slots=3, max_len=24,
                        store=SessionStore())
        s.admit_prompt("u", prompt)
        s.decode_window({"u": w1})
        s.evict("u")                       # persist mid-generation
        s.store._warm.pop("u", None)       # force the archive restore path
        s.admit_prompt("rival", _prompt("rival", 5, vocab))  # takes slot 0
        s.step()                           # pool advances while u is parked
        slot = s.admit_prompt("u", prompt)  # restored; prompt ignored
        assert slot != s.user_slot["rival"]
        assert s.pending("u") == w2[0]
        out = s.decode_window({
            "u": w2,
            "rival": np.full((k,), s.pending("rival"), np.int32)})
        np.testing.assert_array_equal(np.asarray(out["u"]), ref_logits)
        _assert_trees_equal(ref_sess, _np(s.session_view("u")))


class TestCompileAudit:
    @pytest.mark.parametrize("impl,datapath", DIAG)
    def test_pinned_program_counts(self, impl, datapath):
        """The full per-entry-point executable dict after a canonical serve
        sequence, pinned exactly: any helper that silently becomes its own
        jitted program (or any shape leak that splits an existing one)
        changes a number here."""
        model, params = _model("dense", impl, datapath)
        vocab = model.cfg.vocab
        s = LMScheduler(model, params, slots=3, max_len=24)
        # untraced audit: every program registered before first use; only
        # slot_take has compiled (the session factory gathers slot 0 of
        # the initial pool to build the fresh-session template)
        assert s.compiled_programs() == {
            "slot_put": 0, "slot_take": 1, "recorder_reset": 0,
            "prefill": 0, "decode_step": 0,
            "decode_window": 0, "decode_step_telemetry": 0,
            "decode_window_telemetry": 0,
            "decode_step_record": 0, "decode_window_record": 0}

        s.admit_prompt("a", _prompt("a", 6, vocab))
        s.admit_prompt("b", _prompt("b", 4, vocab))   # 2nd prompt LENGTH
        for _ in range(2):
            s.step()                                  # cached after 1st
        s.step(telemetry=True)
        k2 = {u: np.full((2,), s.pending(u), np.int32) for u in ("a", "b")}
        s.decode_window(k2)
        s.decode_window(k2, telemetry=True)
        s.evict("b")
        expected = {
            "slot_put": 1, "slot_take": 1,
            "recorder_reset": 0,          # health not enabled: never traced
            "prefill": 2,                 # one per distinct prompt length
            "decode_step": 1, "decode_step_telemetry": 1,
            "decode_window": 1, "decode_window_telemetry": 1,
            "decode_step_record": 0, "decode_window_record": 0,
        }
        assert s.compiled_programs() == expected
        assert s.compile_count() == sum(expected.values())

        # a NEW window length is the one legitimate growth: exactly the
        # windowed entry point gains one executable, nothing else moves
        s.decode_window({"a": np.full((3,), s.pending("a"), np.int32)})
        assert s.compiled_programs() == dict(expected, decode_window=2)


class TestServeAdapterPool:
    """launch/serve.py's scheduler-admit path (the old per-row slot_put
    loop): AdapterPool sessions persist and resume bit-exactly."""

    @pytest.mark.parametrize("datapath", DATAPATHS)
    def test_durable_roundtrip_and_resume(self, datapath, tmp_path):
        from repro.launch.serve import generate

        cfg = factory.build("qwen3-4b", smoke=True).cfg.with_(
            plastic_adapter=True, adapter_neurons=8, adapter_impl="xla",
            adapter_quant=(datapath == "int8"))
        model = factory.build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        params["adapter"]["scale"] = jnp.float32(0.5)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                     cfg.vocab)
        users = ["user0", "user1"]

        store = SessionStore(root=str(tmp_path), capacity=2)
        pool = AdapterPool(cfg, slots=2, store=store)
        for u in users:
            pool.admit(u)
        generate(cfg, params, prompts, max_len=12, gen=3, adapters=pool)
        learned = [_np(pool._take(pool.pool, jnp.int32(s))) for s in (0, 1)]
        assert [int(pool._steps[s]) for s in (0, 1)] == [3, 3]
        for u in users:
            pool.evict(u)

        # "second run": fresh store over the same directory, fresh pool —
        # admission must restore every user's learned rows bit-exactly
        store2 = SessionStore(root=str(tmp_path), capacity=2)
        pool2 = AdapterPool(cfg, slots=2, store=store2)
        for u in users:
            pool2.admit(u)
        assert store2.restores == 2 and store2.creates == 0
        for s in (0, 1):
            _assert_trees_equal(learned[s],
                                _np(pool2._take(pool2.pool, jnp.int32(s))))
            assert int(pool2._steps[s]) == 3

        # resumed sessions keep learning: counters accumulate and the
        # learned rows move on from (not back to) the restored state
        generate(cfg, params, prompts, max_len=12, gen=2, adapters=pool2)
        assert [int(pool2._steps[s]) for s in (0, 1)] == [5, 5]
        resumed = [_np(pool2._take(pool2.pool, jnp.int32(s)))
                   for s in (0, 1)]
        changed = any(
            not np.array_equal(x, y)
            for s in (0, 1)
            for x, y in zip(jax.tree.leaves(learned[s]),
                            jax.tree.leaves(resumed[s])))
        assert changed, "resumed sessions did not learn"
