"""Fixed-point engine (quant mode): the FPGA-faithful datapath's contracts.

Pins, in order of load-bearing-ness:

  1. BIT-determinism across backends: the quantized layer step returns
     IDENTICAL int32/int8 outputs on "xla" and "pallas-interpret" (not
     allclose — array_equal), across shapes, padded tiles, teach/readout
     modes, and per-slot scales.  Same style as test_fleet.py parity, but
     exact because every reduction in the quant path is an integer
     reduction.
  2. The quantized fleet step is bit-equal to B independent unbatched
     quantized steps (per-sample semantics), and the active mask freezes
     inactive slots bit-exactly.
  3. Serving: evict -> persist -> re-admit of an int8 session (different
     slot, rival traffic in between) is bit-identical to an uninterrupted
     quantized run — the deterministic stochastic round follows the
     SESSION's step counter, not the pool clock or the slot.
  4. SessionStore.checkout validates restored payloads against the pool
     mode (the satellite bugfix): a float32 session can no longer be
     silently cast into an int8 slot.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, snn
from repro.kernels.plasticity import ops
from repro.kernels.plasticity import quant as Q
from repro.serving import FleetScheduler, SessionStore

IMPLS = ["xla", "pallas-interpret"]
QC = Q.QuantConfig()


def _qparams(qc=QC, **over):
    return engine.EngineParams(tau_m=qc.tau_m, trace_decay=qc.decay,
                               quant=qc, **over)


def _qlayer(key, b, n, m, fleet=False, plastic=True, scale=None):
    """Random fixed-point layer state + binary-spike input."""
    ks = jax.random.split(key, 6)
    wshape = (b, n, m) if fleet else (n, m)
    spikes = (jax.random.uniform(ks[0], (b, n)) > 0.5).astype(jnp.float32)
    state = engine.LayerState(
        w=jax.random.randint(ks[1], wshape, -100, 100, jnp.int8),
        v=jax.random.randint(ks[2], (b, m), -500, 500, jnp.int32),
        trace_pre=jax.random.randint(ks[3], (b, n), 0, 3 * QC.one, jnp.int32),
        trace_post=jax.random.randint(ks[4], (b, m), 0, 3 * QC.one,
                                      jnp.int32),
        theta=(0.05 * jax.random.normal(ks[5], (4, n, m))).astype(jnp.float32)
        if plastic else None,
        w_scale=scale if scale is not None else (
            jnp.full((b,), QC.w_scale, jnp.float32) if fleet
            else jnp.float32(QC.w_scale)))
    return state, Q.to_fixed(spikes, QC)


def _assert_bits(a, b, names=("out", "w", "v", "trace_post")):
    for name, x, y in zip(names, a, b):
        assert x.dtype == y.dtype, name
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


class TestQuantConfig:
    def test_defaults_are_the_papers_dynamics(self):
        qc = Q.QuantConfig()
        assert qc.tau_m == 2.0                 # multiplier-free tau_m = 2
        assert qc.decay == 0.75                # 1 - 2**-2
        assert qc.one == 256
        assert qc.w_scale == 1.0 / 32.0

    def test_invalid_fields_raise(self):
        with pytest.raises(ValueError, match="frac_bits"):
            Q.QuantConfig(frac_bits=-1)
        with pytest.raises(ValueError, match="trace_shift"):
            Q.QuantConfig(trace_shift=99)

    def test_hashable_jit_static(self):
        assert hash(Q.QuantConfig()) == hash(Q.QuantConfig())

    def test_fixed_point_round_trip_exact_on_grid(self):
        x = jnp.asarray([0.0, 1.0, -1.0, 0.25, -3.5])
        np.testing.assert_array_equal(
            np.asarray(Q.from_fixed(Q.to_fixed(x, QC), QC)), np.asarray(x))

    def test_uniform_hash_deterministic_and_sensitive(self):
        idx = jnp.arange(1024, dtype=jnp.int32)
        u1 = Q.uniform_hash(jnp.int32(7), idx)
        u2 = Q.uniform_hash(jnp.int32(7), idx)
        u3 = Q.uniform_hash(jnp.int32(8), idx)
        np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))
        assert not np.array_equal(np.asarray(u1), np.asarray(u3))
        assert float(u1.min()) >= 0.0 and float(u1.max()) < 1.0
        # roughly uniform (loose sanity, not a statistical test)
        assert 0.35 < float(u1.mean()) < 0.65


class TestQuantBackendBitParity:
    """xla vs pallas-interpret: IDENTICAL ints, not allclose."""

    def _run(self, state, x, impl, params=None, teach=None, active=None,
             seed=None):
        return engine.layer_step(state, x, params=params or _qparams(),
                                 impl=impl, teach=teach, active=active,
                                 seed=seed)

    @pytest.mark.parametrize("b,n,m", [(1, 8, 8), (4, 10, 30), (3, 17, 257),
                                       (8, 128, 128)])
    def test_shared_weights(self, b, n, m):
        state, x = _qlayer(jax.random.PRNGKey(b + n + m), b, n, m)
        rs, ro = self._run(state, x, "xla", seed=jnp.int32(5))
        ps, po = self._run(state, x, "pallas-interpret", seed=jnp.int32(5))
        _assert_bits((ro, rs.w, rs.v, rs.trace_post),
                     (po, ps.w, ps.v, ps.trace_post))
        assert rs.w.dtype == jnp.int8 and ro.dtype == jnp.int32

    # the tile-padding edge: m deliberately NOT a multiple of block_m
    @pytest.mark.parametrize("m,block_m", [(48, 32), (130, 128), (40, 16),
                                           (257, 64)])
    @pytest.mark.parametrize("fleet", [False, True])
    def test_padded_postsynaptic_tiles(self, m, block_m, fleet):
        state, x = _qlayer(jax.random.PRNGKey(m + block_m), 3, 24, m,
                           fleet=fleet)
        params = _qparams(block_m=block_m)
        seed = jnp.arange(3, dtype=jnp.int32) if fleet else jnp.int32(3)
        rs, ro = self._run(state, x, "xla", params=params, seed=seed)
        ps, po = self._run(state, x, "pallas-interpret", params=params,
                           seed=seed)
        _assert_bits((ro, rs.w, rs.v, rs.trace_post),
                     (po, ps.w, ps.v, ps.trace_post))

    @pytest.mark.parametrize("spiking", [True, False])
    def test_fleet_teach_and_readout(self, spiking):
        b, n, m = 3, 12, 20
        state, x = _qlayer(jax.random.PRNGKey(7), b, n, m, fleet=True)
        teach = Q.to_fixed(2.0 * jax.random.normal(jax.random.PRNGKey(8),
                                                   (b, m)), QC)
        params = _qparams(spiking=spiking)
        seeds = jnp.array([1, 2, 3], jnp.int32)
        rs, ro = self._run(state, x, "xla", params=params, teach=teach,
                           seed=seeds)
        ps, po = self._run(state, x, "pallas-interpret", params=params,
                           teach=teach, seed=seeds)
        _assert_bits((ro, rs.w, rs.v, rs.trace_post),
                     (po, ps.w, ps.v, ps.trace_post))

    def test_heterogeneous_per_slot_scales(self):
        """Each slot's int8 payload is interpreted through ITS scale."""
        b, n, m = 3, 10, 16
        scale = jnp.array([1 / 32, 1 / 16, 1 / 64], jnp.float32)
        state, x = _qlayer(jax.random.PRNGKey(9), b, n, m, fleet=True,
                           scale=scale)
        rs, ro = self._run(state, x, "xla")
        ps, po = self._run(state, x, "pallas-interpret")
        _assert_bits((ro, rs.w, rs.v, rs.trace_post),
                     (po, ps.w, ps.v, ps.trace_post))
        # a coarser scale means the same fixed psum maps to larger currents:
        # slot dynamics must actually DIFFER across scales for equal payloads
        # (every other per-slot operand equal too; the membrane carries the
        # scaled current, where binary spikes may saturate alike)
        row0 = lambda a: jnp.broadcast_to(a[0], a.shape)
        state_eq = dataclasses.replace(
            state, w=row0(state.w), v=row0(state.v),
            trace_pre=row0(state.trace_pre),
            trace_post=row0(state.trace_post))
        s_eq, _ = self._run(state_eq, row0(x), "xla")
        v_eq = np.asarray(s_eq.v)
        assert not np.array_equal(v_eq[0], v_eq[1])
        assert not np.array_equal(v_eq[0], v_eq[2])

    def test_plastic_off_passes_weights_through(self):
        state, x = _qlayer(jax.random.PRNGKey(13), 3, 16, 16, fleet=True,
                           plastic=False)
        params = _qparams(plastic=False)
        for impl in IMPLS:
            ns, _ = self._run(state, x, impl, params=params)
            np.testing.assert_array_equal(np.asarray(ns.w),
                                          np.asarray(state.w))


class TestQuantFleetSemantics:
    def test_fleet_equals_independent_unbatched_steps(self):
        """Per-sample semantics: fleet == B separate quantized steps."""
        b, n, m = 4, 10, 14
        state, x = _qlayer(jax.random.PRNGKey(2), b, n, m, fleet=True)
        seeds = jnp.array([3, 1, 4, 1], jnp.int32)
        fs, fo = engine.layer_step(state, x, params=_qparams(), impl="xla",
                                   seed=seeds)
        for i in range(b):
            ev, v, tp, w = ops.dual_engine_step(
                x[i], state.w[i], state.theta, state.v[i],
                state.trace_pre[i], state.trace_post[i],
                w_scale=state.w_scale[i], seed=seeds[i], quant=QC,
                v_th=1.0, v_reset=0.0, w_clip=4.0, impl="xla")
            np.testing.assert_array_equal(np.asarray(fo[i]), np.asarray(ev))
            np.testing.assert_array_equal(np.asarray(fs.w[i]), np.asarray(w))
            np.testing.assert_array_equal(np.asarray(fs.v[i]), np.asarray(v))

    @pytest.mark.parametrize("impl", IMPLS)
    def test_active_mask_freezes_bitwise(self, impl):
        state, x = _qlayer(jax.random.PRNGKey(5), 4, 10, 30, fleet=True)
        act = jnp.array([True, False, True, False])
        seeds = jnp.arange(4, dtype=jnp.int32)
        ns, out = engine.layer_step(state, x, params=_qparams(), impl=impl,
                                    active=act, seed=seeds)
        ns0, out0 = engine.layer_step(state, x, params=_qparams(), impl=impl,
                                      seed=seeds)
        for i in range(4):
            if bool(act[i]):
                np.testing.assert_array_equal(np.asarray(ns.w[i]),
                                              np.asarray(ns0.w[i]))
                np.testing.assert_array_equal(np.asarray(out[i]),
                                              np.asarray(out0[i]))
            else:
                for fld in ("w", "v", "trace_post"):
                    np.testing.assert_array_equal(
                        np.asarray(getattr(ns, fld)[i]),
                        np.asarray(getattr(state, fld)[i]), err_msg=fld)
                assert (np.asarray(out[i]) == 0).all()

    def test_stochastic_round_is_seeded(self):
        """Same seed -> identical weights; different seed -> different."""
        state, x = _qlayer(jax.random.PRNGKey(11), 2, 16, 16)
        s1, _ = engine.layer_step(state, x, params=_qparams(), impl="xla",
                                  seed=jnp.int32(10))
        s2, _ = engine.layer_step(state, x, params=_qparams(), impl="xla",
                                  seed=jnp.int32(10))
        s3, _ = engine.layer_step(state, x, params=_qparams(), impl="xla",
                                  seed=jnp.int32(11))
        np.testing.assert_array_equal(np.asarray(s1.w), np.asarray(s2.w))
        assert not np.array_equal(np.asarray(s1.w), np.asarray(s3.w))

    def test_weights_stay_on_the_clipped_int8_grid(self):
        """A huge constant-term theta saturates w_q at min(floor(clip/s),127)."""
        state, x = _qlayer(jax.random.PRNGKey(12), 2, 8, 8)
        hot = dataclasses.replace(
            state, theta=state.theta.at[3].set(100.0))   # DELTA plane
        params = _qparams()
        ns, _ = engine.layer_step(hot, x, params=params, impl="xla")
        assert ns.w.dtype == jnp.int8
        assert int(np.asarray(ns.w).max()) == 127        # floor(4*32)=128->127


class TestQuantEngineGuards:
    def test_mismatched_trace_decay_raises(self):
        state, x = _qlayer(jax.random.PRNGKey(0), 2, 8, 8)
        bad = engine.EngineParams(quant=QC)              # float decay 0.8
        with pytest.raises(ValueError, match="trace_decay"):
            engine.layer_step(state, x, params=bad, impl="xla")

    def test_mismatched_tau_raises(self):
        state, x = _qlayer(jax.random.PRNGKey(0), 2, 8, 8)
        bad = engine.EngineParams(tau_m=3.0, trace_decay=QC.decay, quant=QC)
        with pytest.raises(ValueError, match="tau_m"):
            engine.layer_step(state, x, params=bad, impl="xla")

    def test_float_teach_rejected_loudly(self):
        """A float teach would be truncated to zeros by the int cast —
        demand the fixed-point event bus format instead."""
        state, x = _qlayer(jax.random.PRNGKey(2), 2, 8, 8)
        with pytest.raises(ValueError, match="quant mode needs teach"):
            engine.layer_step(state, x, params=_qparams(), impl="xla",
                              teach=0.5 * jnp.ones((2, 8)))

    def test_float_state_rejected_loudly(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        state = engine.LayerState(
            w=0.1 * jax.random.normal(ks[0], (8, 8)),
            v=jnp.zeros((2, 8)), trace_pre=jnp.zeros((2, 8)),
            trace_post=jnp.zeros((2, 8)),
            theta=0.01 * jax.random.normal(ks[1], (4, 8, 8)))
        with pytest.raises(ValueError, match="quant mode needs w"):
            engine.layer_step(state, jnp.zeros((2, 8), jnp.int32),
                              params=_qparams(), impl="xla")


class TestQuantSNN:
    def _cfg(self, impl="xla"):
        return snn.quant_config(snn.SNNConfig(layer_sizes=(6, 16, 4),
                                              timesteps=3, impl=impl))

    def test_init_state_representation(self):
        cfg = self._cfg()
        st = snn.init_state(cfg)
        assert st.w[0].dtype == jnp.int8
        assert st.v[0].dtype == jnp.int32 and st.trace[0].dtype == jnp.int32
        assert len(st.w_scale) == cfg.num_layers
        assert float(st.w_scale[0]) == QC.w_scale
        fl = snn.init_state(cfg, batch=5, fleet=True)
        assert fl.w[0].shape == (5, 6, 16) and fl.w[0].dtype == jnp.int8
        assert fl.w_scale[0].shape == (5,)

    def test_controller_bitwise_across_backends(self):
        theta = snn.init_theta(self._cfg(), jax.random.PRNGKey(0), scale=0.5)
        obs = jnp.linspace(-1, 1, 6)
        results = {}
        for impl in IMPLS:
            cfg = self._cfg(impl)
            st = snn.init_state(cfg)
            for _ in range(3):
                st, act = snn.controller_step(cfg, st, theta, obs)
            results[impl] = (act, st.w)
        a, b = results["xla"], results["pallas-interpret"]
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        for x, y in zip(a[1], b[1]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_quantize_state_migrates_float_sessions(self):
        cfg = self._cfg()
        fcfg = dataclasses.replace(cfg, quant=None)
        fst = snn.init_state(fcfg)
        fst = dataclasses.replace(
            fst, w=tuple(0.5 * jax.random.normal(jax.random.PRNGKey(i),
                                                 w.shape)
                         for i, w in enumerate(fst.w)))
        qst = snn.quantize_state(cfg, fst)
        assert qst.w[0].dtype == jnp.int8
        for wq, s, wf in zip(qst.w, qst.w_scale, fst.w):
            err = np.abs(np.asarray(wq, np.float32) * float(s)
                         - np.asarray(wf))
            assert err.max() <= float(s) * 0.5 + 1e-6   # one rounding
        # and the result actually steps
        theta = snn.init_theta(cfg, jax.random.PRNGKey(0), scale=0.3)
        st, out = snn.timestep(cfg, qst, theta, jnp.ones((6,)))
        assert out.dtype == jnp.float32

    def test_quantize_state_requires_quant_cfg(self):
        fcfg = snn.SNNConfig(layer_sizes=(6, 16, 4))
        with pytest.raises(ValueError, match="cfg.quant"):
            snn.quantize_state(fcfg, snn.init_state(fcfg))

    def test_float_vs_quant_actions_close_early(self):
        """The quant datapath tracks the float reference on matched
        (power-of-two) dynamics over an early window.  Spiking plasticity
        is chaotic — threshold flips amplify — so long-horizon trajectories
        legitimately diverge; the per-step/task-level error is measured and
        documented by benchmarks/quant_parity.py, not bounded here."""
        cfg = self._cfg()
        fcfg = dataclasses.replace(cfg, quant=None)
        theta = snn.init_theta(cfg, jax.random.PRNGKey(0), scale=0.3)
        qst, fst = snn.init_state(cfg), snn.init_state(fcfg)
        obs = 0.5 * jnp.sin(jnp.arange(6, dtype=jnp.float32))
        errs = []
        for _ in range(3):
            qst, qa = snn.controller_step(cfg, qst, theta, obs)
            fst, fa = snn.controller_step(fcfg, fst, theta, obs)
            errs.append(float(jnp.abs(qa - fa).max()))
        assert max(errs) < 0.5, errs


class TestQuantServing:
    def _cfg(self, impl="xla"):
        return snn.quant_config(snn.SNNConfig(layer_sizes=(6, 12, 4),
                                              timesteps=2, impl=impl))

    def _drive(self, uid, t, n=6):
        phase = (hash(uid) % 97) / 97.0
        return np.sin(0.3 * t + phase + np.arange(n)).astype(np.float32)

    @pytest.mark.parametrize("impl", IMPLS)
    def test_evict_restore_different_slot_bit_identical(self, impl,
                                                        tmp_path):
        """THE acceptance pin, quantized: interrupted == uninterrupted."""
        cfg = self._cfg(impl)
        theta = snn.init_theta(cfg, jax.random.PRNGKey(0))
        steps = 8 if impl == "xla" else 6
        cut = steps // 2

        def trajectory(interrupt):
            sub = "int" if interrupt else "unint"
            sched = FleetScheduler(
                cfg, theta, slots=2,
                store=SessionStore(root=str(tmp_path / f"{impl}-{sub}")))
            assert sched.admit("probe") == 0
            outs = []
            for t in range(steps):
                if interrupt and t == cut:
                    sched.evict("probe")           # int8 payload -> disk
                    sched.store._warm.clear()      # force the disk path
                    sched.admit("rival")           # rival takes slot 0 and
                    sched.step({"rival": self._drive("rival", 99)})  # ticks
                    assert sched.admit("probe") == 1   # DIFFERENT slot
                outs.append(np.asarray(sched.step(
                    {u: self._drive(u, t) for u in sched.active_users}
                )["probe"]))
            sched.evict("probe")
            final, step = sched.store.checkout(
                "probe", lambda: snn.init_state(cfg))
            return outs, final, step

        o1, f1, s1 = trajectory(False)
        o2, f2, s2 = trajectory(True)
        assert s1 == s2 == steps
        for a, b in zip(o1, o2):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(f2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_quant_pool_is_int8_and_smaller(self):
        cfg = self._cfg()
        theta = snn.init_theta(cfg, jax.random.PRNGKey(0))
        q = FleetScheduler(cfg, theta, slots=8, store=SessionStore())
        f = FleetScheduler(dataclasses.replace(cfg, quant=None,
                                               trace_decay=0.8),
                           theta, slots=8, store=SessionStore())
        assert q.fleet.w[0].dtype == jnp.int8
        assert q.pool_nbytes() < f.pool_nbytes() / 2   # weights dominate

    def test_churn_never_recompiles_after_warmup(self):
        cfg = self._cfg()
        theta = snn.init_theta(cfg, jax.random.PRNGKey(0))
        s = FleetScheduler(cfg, theta, slots=3, store=SessionStore())
        s.admit("w"); s.step({"w": self._drive("w", 0)})
        s.evict("w"); s.admit("w"); s.step({"w": self._drive("w", 1)})
        s.evict("w")
        c0 = s.compile_count()
        for t in range(12):
            uid = f"u{t % 4}"
            if uid in s.user_slot:
                s.evict(uid)
            else:
                s.admit(uid, evict_lru=True)
            s.step({u: self._drive(u, t) for u in s.active_users})
        assert s.compile_count() == c0

    def test_checkout_rejects_mode_mismatch_ram(self):
        """Satellite bugfix: float payload can't enter an int8 pool."""
        qcfg = self._cfg()
        fcfg = dataclasses.replace(qcfg, quant=None, trace_decay=0.8)
        store = SessionStore(root=None)
        store.checkin("u", snn.init_state(fcfg), 3)
        store._warm.clear()                       # force the archive path
        with pytest.raises(ValueError, match="quantize_state"):
            store.checkout("u", lambda: snn.init_state(qcfg))

    def test_checkout_rejects_mode_mismatch_warm_and_disk(self, tmp_path):
        qcfg = self._cfg()
        fcfg = dataclasses.replace(qcfg, quant=None, trace_decay=0.8)
        store = SessionStore(root=str(tmp_path))
        store.checkin("u", snn.init_state(fcfg), 3)
        with pytest.raises(ValueError):           # warm-cache path
            store.checkout("u", lambda: snn.init_state(qcfg))
        store = SessionStore(root=str(tmp_path))  # fresh store: disk path
        with pytest.raises(ValueError):
            store.checkout("u", lambda: snn.init_state(qcfg))

    def test_checkout_matching_mode_still_works(self, tmp_path):
        qcfg = self._cfg()
        store = SessionStore(root=str(tmp_path))
        st = snn.init_state(qcfg)
        store.checkin("u", st, 5)
        store._warm.clear()
        out, step = store.checkout("u", lambda: snn.init_state(qcfg))
        assert step == 5
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_quantized_session_admits_via_quantize_state(self):
        """The sanctioned float -> int8 migration path works end to end."""
        qcfg = self._cfg()
        fcfg = dataclasses.replace(qcfg, quant=None, trace_decay=0.8)
        store = SessionStore(root=None)
        fstate = snn.init_state(fcfg)
        store.checkin("u", snn.quantize_state(qcfg, fstate), 0)
        store._warm.clear()
        theta = snn.init_theta(qcfg, jax.random.PRNGKey(0))
        sched = FleetScheduler(qcfg, theta, slots=2, store=store)
        sched.admit("u")
        out = sched.step({"u": self._drive("u", 0)})
        assert np.isfinite(np.asarray(out["u"])).all()
