"""Session serving: the active-mask contract, the SessionStore, and the
FleetScheduler's continuous-batching guarantees.

Pins, in order of load-bearing-ness:

  1. `active (B,)` through the engine stack: inactive fleet slots are TRUE
     no-ops on every backend — weights/membranes/traces bit-frozen, events
     zero — and active slots are bit-identical to an unmasked step.
  2. Evict -> persist (disk) -> re-admit into a DIFFERENT slot: the
     session's subsequent trajectory is bit-identical to an uninterrupted
     run, on xla and on pallas-interpret (the validated lowering of the
     pallas TPU path).
  3. The fixed-shape contract: churn (admit/evict/occupancy changes) never
     recompiles anything after the warm-up cycle.
  4. Fleet-mode state-shape validation (the satellite bugfix): an unbatched
     membrane/trace no longer silently broadcasts across streams.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, snn
from repro.serving import FleetScheduler, SessionStore

IMPLS = ["xla", "pallas-interpret"]


def _fleet_layer(key, b, n, m, plastic=True):
    ks = jax.random.split(key, 6)
    x = (jax.random.uniform(ks[0], (b, n)) > 0.5).astype(jnp.float32)
    state = engine.LayerState(
        w=0.1 * jax.random.normal(ks[1], (b, n, m)),
        v=0.1 * jax.random.normal(ks[2], (b, m)),
        trace_pre=jax.random.uniform(ks[3], (b, n)),
        trace_post=jax.random.uniform(ks[4], (b, m)),
        theta=0.01 * jax.random.normal(ks[5], (4, n, m)) if plastic
        else None)
    return state, x


def _drive(uid, t, n):
    phase = (hash(uid) % 97) / 97.0
    return np.sin(0.3 * t + phase + np.arange(n)).astype(np.float32)


class TestActiveMask:
    """engine.layer_step(active=...): vacant slots are true no-ops."""

    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("b,n,m,block_m", [(4, 10, 30, 16),
                                               (3, 17, 40, 128)])
    def test_inactive_frozen_active_untouched(self, impl, b, n, m, block_m):
        state, x = _fleet_layer(jax.random.PRNGKey(b * 7 + m), b, n, m)
        act = jnp.arange(b) % 2 == 0
        params = engine.EngineParams(block_m=block_m)
        ns, out = engine.layer_step(state, x, params=params, impl=impl,
                                    active=act)
        ns0, out0 = engine.layer_step(state, x, params=params, impl=impl)
        for i in range(b):
            if act[i]:
                # active slot: bit-identical to the unmasked step
                np.testing.assert_array_equal(np.asarray(ns.w[i]),
                                              np.asarray(ns0.w[i]))
                np.testing.assert_array_equal(np.asarray(out[i]),
                                              np.asarray(out0[i]))
            else:
                # inactive slot: bit-frozen state, zero events
                for fld in ("w", "v", "trace_post"):
                    np.testing.assert_array_equal(
                        np.asarray(getattr(ns, fld)[i]),
                        np.asarray(getattr(state, fld)[i]), err_msg=fld)
                assert (np.asarray(out[i]) == 0).all()

    def test_backend_parity_with_mask(self):
        state, x = _fleet_layer(jax.random.PRNGKey(3), 5, 12, 40)
        act = jnp.array([1, 0, 1, 1, 0], jnp.int32)
        params = engine.EngineParams(block_m=16)
        rs, ro = engine.layer_step(state, x, params=params, impl="xla",
                                   active=act)
        ps, po = engine.layer_step(state, x, params=params,
                                   impl="pallas-interpret", active=act)
        np.testing.assert_allclose(np.asarray(rs.w), np.asarray(ps.w),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ro), np.asarray(po),
                                   rtol=1e-5, atol=1e-5)
        # the frozen slots agree BITWISE across backends (no compute ran)
        for i in (1, 4):
            np.testing.assert_array_equal(np.asarray(rs.w[i]),
                                          np.asarray(ps.w[i]))

    @pytest.mark.parametrize("impl", IMPLS)
    def test_readout_layer_zeroes_inactive_output(self, impl):
        """spiking=False: `out` is the membrane, and the state gate freezes
        v to its OLD (nonzero) value — the OUTPUT must still be zero for
        inactive slots, never a stale membrane."""
        state, x = _fleet_layer(jax.random.PRNGKey(21), 4, 10, 12)
        act = jnp.array([True, False, True, False])
        params = engine.EngineParams(spiking=False)
        ns, out = engine.layer_step(state, x, params=params, impl=impl,
                                    active=act)
        for i in (1, 3):
            assert (np.asarray(out[i]) == 0).all()
            # while the membrane STATE stays frozen (nonzero)
            np.testing.assert_array_equal(np.asarray(ns.v[i]),
                                          np.asarray(state.v[i]))
        ns0, out0 = engine.layer_step(state, x, params=params, impl=impl)
        for i in (0, 2):
            np.testing.assert_array_equal(np.asarray(out[i]),
                                          np.asarray(out0[i]))

    @pytest.mark.parametrize("impl", IMPLS)
    def test_all_inactive_is_identity(self, impl):
        state, x = _fleet_layer(jax.random.PRNGKey(5), 3, 8, 24)
        ns, out = engine.layer_step(
            state, x, params=engine.EngineParams(), impl=impl,
            active=jnp.zeros(3, bool))
        for fld in ("w", "v", "trace_post"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ns, fld)), np.asarray(getattr(state, fld)))
        assert (np.asarray(out) == 0).all()

    def test_shared_weights_reject_mask(self):
        b, n, m = 3, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(7), 5)
        state = engine.LayerState(
            w=0.1 * jax.random.normal(ks[0], (n, m)),
            v=jnp.zeros((b, m)), trace_pre=jnp.zeros((b, n)),
            trace_post=jnp.zeros((b, m)),
            theta=0.01 * jax.random.normal(ks[1], (4, n, m)))
        with pytest.raises(ValueError, match="fleet-mode"):
            engine.layer_step(state, jnp.zeros((b, n)),
                              active=jnp.ones(b, bool))

    def test_bad_mask_shape_rejected(self):
        state, x = _fleet_layer(jax.random.PRNGKey(9), 4, 8, 16)
        with pytest.raises(ValueError, match="active slot mask"):
            engine.layer_step(state, x, active=jnp.ones(3, bool))

    def test_timestep_freezes_input_trace(self):
        cfg = snn.SNNConfig(layer_sizes=(6, 12, 4))
        st = snn.init_state(cfg, batch=3, fleet=True)
        st = dataclasses.replace(
            st, trace=tuple(jax.random.uniform(jax.random.PRNGKey(i), t.shape)
                            for i, t in enumerate(st.trace)))
        theta = snn.init_theta(cfg, jax.random.PRNGKey(1))
        drive = jax.random.normal(jax.random.PRNGKey(2), (3, 6))
        act = jnp.array([True, False, True])
        st1, _ = snn.timestep(cfg, st, theta, drive, active=act)
        np.testing.assert_array_equal(np.asarray(st1.trace[0][1]),
                                      np.asarray(st.trace[0][1]))
        assert not np.array_equal(np.asarray(st1.trace[0][0]),
                                  np.asarray(st.trace[0][0]))


class TestFleetShapeValidation:
    """Satellite bugfix: v/trace_pre/trace_post get the same treatment x got."""

    def _state(self, b=4, n=10, m=30):
        return _fleet_layer(jax.random.PRNGKey(0), b, n, m)

    @pytest.mark.parametrize("field,shape", [
        ("v", (30,)),                 # unbatched membrane
        ("trace_pre", (10,)),         # unbatched pre trace
        ("trace_post", (30,)),        # unbatched post trace
        ("v", (30, 4)),               # transposed
        ("trace_post", (5, 30)),      # wrong B
    ])
    def test_unbatched_or_wrong_state_raises(self, field, shape):
        state, x = self._state()
        bad = dataclasses.replace(state, **{field: jnp.zeros(shape)})
        with pytest.raises(ValueError, match=f"fleet mode needs {field}"):
            engine.layer_step(bad, x, params=engine.EngineParams())

    def test_m_equals_b_trap(self):
        # the silent-broadcast trap: with M == B an unbatched (M,) membrane
        # broadcast used to be shape-compatible with (B, M)
        state, x = _fleet_layer(jax.random.PRNGKey(1), 4, 10, 4)
        bad = dataclasses.replace(state, v=jnp.zeros((4,)))
        with pytest.raises(ValueError, match="fleet mode needs v"):
            engine.layer_step(bad, x, params=engine.EngineParams())

    def test_valid_fleet_state_still_accepted(self):
        state, x = self._state()
        engine.layer_step(state, x, params=engine.EngineParams())


class TestSessionStore:
    def _cfg(self):
        return snn.SNNConfig(layer_sizes=(6, 12, 4), timesteps=2)

    def _rand_state(self, cfg, seed):
        z = snn.init_state(cfg)
        ks = jax.random.split(jax.random.PRNGKey(seed), len(z.w))
        return dataclasses.replace(
            z, w=tuple(0.3 * jax.random.normal(k, w.shape)
                       for k, w in zip(ks, z.w)))

    def test_checkout_is_exclusive(self, tmp_path):
        store = SessionStore(root=str(tmp_path))
        cfg = self._cfg()
        store.checkin("a", self._rand_state(cfg, 1), 5)
        assert "a" in store
        state, step = store.checkout("a", lambda: snn.init_state(cfg))
        assert step == 5 and "a" not in store     # no stale second copy

    def test_disk_roundtrip_bit_identical(self, tmp_path):
        cfg = self._cfg()
        store = SessionStore(root=str(tmp_path))
        st = self._rand_state(cfg, 2)
        store.checkin("u", st, 17)
        store._warm.clear()                        # force the disk path
        out, step = store.checkout("u", lambda: snn.init_state(cfg))
        assert step == 17 and store.restores == 1
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_lru_capacity_drops_without_losing_durability(self, tmp_path):
        cfg = self._cfg()
        store = SessionStore(root=str(tmp_path), capacity=2)
        for i, uid in enumerate(("a", "b", "c")):
            store.checkin(uid, self._rand_state(cfg, i), i)
        assert store.cached == ["b", "c"]           # a LRU-dropped...
        _, step = store.checkout("a", lambda: snn.init_state(cfg))
        assert step == 0 and store.restores == 1    # ...but still durable

    def test_ram_archive_without_root(self):
        cfg = self._cfg()
        store = SessionStore(root=None)
        st = self._rand_state(cfg, 3)
        store.checkin("u", st, 4)
        store._warm.clear()
        out, step = store.checkout("u", lambda: snn.init_state(cfg))
        assert step == 4
        np.testing.assert_array_equal(np.asarray(st.w[0]),
                                      np.asarray(out.w[0]))

    def test_fresh_user_gets_factory_state(self, tmp_path):
        cfg = self._cfg()
        store = SessionStore(root=str(tmp_path))
        out, step = store.checkout("new", lambda: snn.init_state(cfg))
        assert step == 0 and store.creates == 1
        assert all((np.asarray(w) == 0).all() for w in out.w)


class TestFleetScheduler:
    def _cfg(self, impl="xla"):
        return snn.SNNConfig(layer_sizes=(6, 12, 4), timesteps=2, impl=impl)

    def _sched(self, impl="xla", slots=3, root=None):
        cfg = self._cfg(impl)
        theta = snn.init_theta(cfg, jax.random.PRNGKey(0))
        return FleetScheduler(cfg, theta, slots=slots,
                              store=SessionStore(root=root))

    def test_admit_evict_bookkeeping(self):
        s = self._sched()
        assert s.admit("a") == 0 and s.admit("b") == 1
        with pytest.raises(ValueError, match="already in slot"):
            s.admit("a")
        s.evict("a")
        assert s.slot_user[0] is None and s.free_slots == 2
        with pytest.raises(KeyError):
            s.evict("a")
        assert s.admit("c") == 0                    # slot reuse

    def test_full_pool_raises_or_evicts_lru(self):
        s = self._sched(slots=2)
        s.admit("a"); s.admit("b")
        with pytest.raises(RuntimeError, match="pool is full"):
            s.admit("c")
        slot = s.admit("c", evict_lru=True)         # a is LRU
        assert slot == 0 and "a" not in s.user_slot
        assert s.store.known("a")                   # evicted durably

    def test_step_validates_drive_cover(self):
        s = self._sched()
        s.admit("a")
        with pytest.raises(ValueError, match="missing"):
            s.step({})
        with pytest.raises(ValueError, match="not admitted"):
            s.step({"a": np.zeros(6, np.float32),
                    "ghost": np.zeros(6, np.float32)})
        with pytest.raises(ValueError, match="teach signals"):
            s.step({"a": np.zeros(6, np.float32)},
                   teach={"ghost": np.zeros(4, np.float32)})

    @pytest.mark.parametrize("impl", IMPLS)
    def test_evict_restore_different_slot_bit_identical(self, impl,
                                                        tmp_path):
        """THE acceptance pin: interrupted == uninterrupted, per backend."""
        cfg = self._cfg(impl)
        theta = snn.init_theta(cfg, jax.random.PRNGKey(0))
        steps = 10 if impl == "xla" else 6
        cut = steps // 2

        def trajectory(interrupt):
            sub = "int" if interrupt else "unint"
            sched = FleetScheduler(
                cfg, theta, slots=2,
                store=SessionStore(root=str(tmp_path / f"{impl}-{sub}")))
            assert sched.admit("probe") == 0
            outs, states = [], []
            for t in range(steps):
                if interrupt and t == cut:
                    sched.evict("probe")           # -> disk
                    sched.store._warm.clear()      # force the disk path
                    sched.admit("rival")           # rival takes slot 0
                    sched.step({"rival": _drive("rival", 99, 6)})
                    assert sched.admit("probe") == 1   # DIFFERENT slot
                outs.append(np.asarray(sched.step(
                    {u: _drive(u, t, 6) for u in sched.active_users}
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

    def test_churn_never_recompiles_after_warmup(self):
        s = self._sched(slots=3)
        # warm-up cycle: touches step, put, take once each
        s.admit("w"); s.step({"w": _drive("w", 0, 6)})
        s.evict("w"); s.admit("w"); s.step({"w": _drive("w", 1, 6)})
        s.evict("w")
        c0 = s.compile_count()
        users = [f"u{i}" for i in range(5)]
        for t in range(20):
            uid = users[t % len(users)]
            if uid in s.user_slot:
                s.evict(uid)
            else:
                s.admit(uid, evict_lru=True)
            s.step({u: _drive(u, t, 6) for u in s.active_users})
        assert s.compile_count() == c0

    def test_idle_slots_frozen_bitwise(self):
        s = self._sched(slots=3)
        s.admit("a"); s.admit("b")
        for t in range(4):
            s.step({u: _drive(u, t, 6) for u in s.active_users})
        s.evict("b")
        vacant = s.slot_user.index(None)
        before = [np.asarray(w[vacant]).copy() for w in s.fleet.w]
        for t in range(6):
            s.step({"a": _drive("a", 10 + t, 6)})
        for w, b in zip(s.fleet.w, before):
            np.testing.assert_array_equal(np.asarray(w[vacant]), b)

    def test_teach_routes_to_output_layer(self):
        s = self._sched()
        s.admit("a"); s.admit("b")
        d = {u: _drive(u, 0, 6) for u in ("a", "b")}
        out_plain = s.step(d)
        s2 = self._sched()
        s2.admit("a"); s2.admit("b")
        out_teach = s2.step(d, teach={"a": 5.0 * np.ones(4, np.float32),
                                      "b": np.zeros(4, np.float32)})
        assert not np.array_equal(np.asarray(out_plain["a"]),
                                  np.asarray(out_teach["a"]))
        np.testing.assert_array_equal(np.asarray(out_plain["b"]),
                                      np.asarray(out_teach["b"]))

    def test_control_step_matches_controller_step_solo(self):
        """Pool control_step == snn.controller_step for a lone fleet-of-1.

        Ties the scheduler's windowed API to the reference controller
        semantics (same engine path, fleet B=1 vs fleet B=1)."""
        cfg = self._cfg()
        theta = snn.init_theta(cfg, jax.random.PRNGKey(0))
        s = FleetScheduler(cfg, theta, slots=1, store=SessionStore())
        s.admit("solo")
        obs = _drive("solo", 0, 6)
        a_pool = np.asarray(s.control_step({"solo": obs})["solo"])
        ref_state = snn.init_state(cfg, batch=1, fleet=True)
        _, a_ref = snn.controller_step(cfg, ref_state, theta, obs[None])
        np.testing.assert_allclose(a_pool, np.asarray(a_ref[0]),
                                   rtol=1e-6, atol=1e-6)


# the pool programs whose output array `FleetScheduler._unpack` splits
_POOL_PROGRAMS = ("_step", "_rollout", "_step_tel", "_rollout_tel",
                  "_step_rec", "_rollout_rec")


def _spy_outputs(sched):
    """Wrap every pool program of `sched` so that each call's output array
    (the one the scheduler splits per session) is appended to the list
    returned; the compile audit still reads the real programs."""
    seen = []
    for name in _POOL_PROGRAMS:
        def spy(*args, _real=getattr(sched, name)):
            res = _real(*args)
            seen.append(res[1])
            return res
        setattr(sched, name, spy)
    return seen


class TestOneLaunchUnpack:
    """`_unpack` splits a call's output array into per-slot arrays in one
    compiled program (``pool_unpack``): pure data movement, so every
    session's output is bitwise the program's array at its slot."""

    def _sched(self, datapath="float32", slots=6, health=None,
               spiking_readout=False):
        cfg = snn.SNNConfig(layer_sizes=(6, 12, 4), timesteps=3,
                            spiking_readout=spiking_readout)
        if datapath == "int8":
            cfg = snn.quant_config(cfg)
        # a rule strong enough that readouts reach O(1) within a few
        # windows, so a wrong slot or a missing tanh shows
        theta = snn.init_theta(cfg, jax.random.PRNGKey(0), scale=0.2)
        return FleetScheduler(cfg, theta, slots=slots, store=SessionStore(),
                              health=health)

    @staticmethod
    def _drives(sched, t):
        """A distinct drive per session (the uid's last character sets its
        phase)."""
        return {u: 2.0 * np.sin(0.7 * t + 1.3 * ord(u[-1])
                                + np.arange(6)).astype(np.float32)
                for u in sched.active_users}

    @pytest.mark.parametrize("datapath", ["float32", "int8"])
    @pytest.mark.parametrize("variant", ["plain", "telemetry", "record"])
    @pytest.mark.parametrize("method,slot_axis",
                             [("step", 0), ("pool_step", 1)])
    def test_outputs_are_the_program_array_at_each_slot(
            self, method, slot_axis, variant, datapath):
        """Partial occupancy: a vacant, a lost and a quarantined slot
        beside healthy ones; every admitted uid (stranded and quarantined
        ones too) gets bitwise its slot of the program's output."""
        from repro.obs.health import HealthConfig
        s = self._sched(datapath, health=HealthConfig())
        seen = _spy_outputs(s)
        for u in ("a", "b", "c", "d", "e", "f"):
            s.admit(u)
        for t in range(3):                       # readouts grow to O(1)
            s.pool_step(self._drives(s, t))
        s.evict("b")                                   # slot 1 vacant
        assert s.fail_slots([s.user_slot["c"]]) == ["c"]
        s.quarantine("d")
        kw = {"plain": {}, "telemetry": {"telemetry": True},
              "record": {"record": True}}[variant]
        for t in range(3, 5):
            got = getattr(s, method)(self._drives(s, t), **kw)
            if variant == "telemetry":
                got = got[0]
            arr = np.asarray(seen[-1])
            assert sorted(got) == ["a", "c", "d", "e", "f"]
            for u, slot in s.user_slot.items():
                want = np.take(arr, slot, axis=slot_axis)
                assert got[u].dtype == want.dtype
                np.testing.assert_array_equal(np.asarray(got[u]), want)
            # the healthy sessions' rows differ from each other and from
            # the frozen slots', so a row handed to the wrong uid shows
            healthy = [np.asarray(got[u]).tobytes() for u in ("a", "e", "f")]
            assert len(set(healthy)) == 3
            assert np.abs(np.asarray(got["e"])).max() > 0.1
        # one program per output shape: the warm-up windows' and, for
        # `step`, its own
        assert s.compiled_programs()["pool_unpack"] == 1 + (method == "step")

    def test_one_program_per_output_shape_and_none_under_churn(self):
        """Occupancy changes every call; after the first call of each
        entry point nothing compiles, and ``pool_unpack`` holds one
        program per kind of output: the step's (B, act), the window's
        (K, B, act) and the control step's actions."""
        from repro.obs.watchdog import watchdog as watch
        s = self._sched(slots=4)
        s.admit("w")
        s.evict("w")
        s.admit("w")

        def drives(t):
            return self._drives(s, t)

        s.step(drives(0))
        s.pool_step(drives(0))
        s.control_step(drives(0))
        progs = s.compiled_programs()
        assert progs["pool_unpack"] == 3
        users = [f"u{i}" for i in range(6)]
        watch.install()
        watch.reset()
        with watch.armed():
            for t in range(12):
                uid = users[t % len(users)]
                if uid in s.user_slot:
                    s.evict(uid)
                else:
                    s.admit(uid, evict_lru=True)
                outs = s.step(drives(t))
                assert sorted(outs) == sorted(s.user_slot)
                outs = s.pool_step(drives(t))
                assert sorted(outs) == sorted(s.user_slot)
                s.control_step(drives(t))
        assert watch.violations == 0, watch.violation_signatures
        assert s.compiled_programs() == progs

    @pytest.mark.parametrize("datapath", ["float32", "int8"])
    @pytest.mark.parametrize("spiking_readout", [False, True])
    def test_control_step_is_the_per_uid_window_mean(self, datapath,
                                                     spiking_readout):
        """One launch takes every session's window mean (tanh unless the
        readout spikes) and splits it: equal, within float32 rounding, to
        reducing each uid's window on its own, on a twin pool."""
        a, b = (self._sched(datapath, spiking_readout=spiking_readout)
                for _ in range(2))
        for s in (a, b):
            for u in ("x", "y", "z"):
                s.admit(u)
            s.evict("y")
        for t in range(4):
            d = self._drives(a, t)
            got = a.control_step(d)
            windows = b.pool_step(d)
            assert sorted(got) == sorted(windows) == ["x", "z"]
            for u, w in windows.items():
                want = w.mean(axis=0)
                if not spiking_readout:
                    want = jnp.tanh(want)
                assert got[u].shape == (4,)
                np.testing.assert_allclose(np.asarray(got[u]),
                                           np.asarray(want),
                                           rtol=1e-6, atol=1e-7)
        assert np.abs(np.asarray(windows["x"]).mean(axis=0)).max() > 0.3
        for x, y in zip(jax.tree.leaves(a.fleet), jax.tree.leaves(b.fleet)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class _Refused:
    """A module as `repro.serving.scheduler` sees it, with the names in
    `refused` (all of them where it is None) raising when used."""

    def __init__(self, module, refused=None):
        self._module, self._refused = module, refused

    def __getattr__(self, name):
        if self._refused is None or name in self._refused:
            def refuse(*args, **kwargs):
                raise AssertionError(
                    f"{self._module.__name__}.{name} inside a warm pool "
                    "call: a device op outside the pool program")
            return refuse
        return getattr(self._module, name)


def _pool_for_dispatch(datapath="float32", meshed=False, slots=4,
                       health=None):
    from repro.distributed import sharding as dsh
    cfg = snn.SNNConfig(layer_sizes=(6, 12, 4), timesteps=3)
    if datapath == "int8":
        cfg = snn.quant_config(cfg)
    theta = snn.init_theta(cfg, jax.random.PRNGKey(0), scale=0.2)
    # the CPU test devices: a mesh over four where the lane forces them
    mesh = (dsh.fleet_mesh(4 if len(jax.devices()) >= 4 else 1)
            if meshed else None)
    return FleetScheduler(cfg, theta, slots=slots, store=SessionStore(),
                          mesh=mesh, health=health)


def _dispatch_drives(sched, t, n=6):
    return {u: 2.0 * np.sin(0.7 * t + 1.3 * ord(u[-1])
                            + np.arange(n)).astype(np.float32)
            for u in sched.active_users}


class TestOneDispatch:
    """A fleet call's per-call inputs (the drives or the held window, the
    active mask, teach, the per-session step counters) reach the device
    only as numpy arguments of the pool program: once warm, a call makes
    two launches, the pool program and ``pool_unpack``, and no transfer
    or eager op of its own."""

    @pytest.mark.parametrize("meshed", [False, True])
    @pytest.mark.parametrize("method", ["step", "pool_step", "control_step"])
    def test_warm_call_makes_two_launches_and_no_transfer(
            self, method, meshed, monkeypatch):
        from repro.serving import scheduler as sched_mod
        s = _pool_for_dispatch(meshed=meshed)
        for u in ("a", "b", "c"):
            s.admit(u)
        teach = ({u: np.ones(4, np.float32) for u in ("a", "c")}
                 if method != "control_step" else None)

        def call(t):
            kw = {} if teach is None else {"teach": teach}
            return getattr(s, method)(_dispatch_drives(s, t), **kw)

        call(0)                                            # warm-up
        progs = s.compiled_programs()
        prog = "_step" if method == "step" else "_rollout"
        launches = []

        def spy(fleet, x, active, tarr, seeds, _real=getattr(s, prog)):
            launches.append(prog)
            k = () if method == "step" else (3,)
            for name, a, shape in (("x", x, k + (4, 6)),
                                   ("active", active, (4,)),
                                   ("seeds", seeds, (4,))):
                assert type(a) is np.ndarray, (name, type(a))
                assert a.shape == shape, (name, a.shape)
            assert active.dtype == np.bool_ and seeds.dtype == np.int32
            assert active.tolist() == [True, True, True, False]
            if teach is None:
                assert tarr is None
            else:
                assert type(tarr) is np.ndarray and tarr.shape == (4, 4)
            return _real(fleet, x, active, tarr, seeds)

        def split(*args, _real=s._split, **kwargs):
            launches.append("pool_unpack")
            return _real(*args, **kwargs)

        monkeypatch.setattr(s, prog, spy)
        monkeypatch.setattr(s, "_split", split)
        monkeypatch.setattr(sched_mod, "jnp", _Refused(jnp))
        monkeypatch.setattr(sched_mod, "jax", _Refused(
            jax, ("device_put", "device_get", "block_until_ready")))
        got = call(1)
        monkeypatch.undo()
        assert launches == [prog, "pool_unpack"]
        assert sorted(got) == ["a", "b", "c"]
        assert s.compiled_programs() == progs


def _reference_call(sched, drives, teach, k):
    """One call of `sched` dispatched as the pool once did: every input
    made a device array by its own transfer or eager op before the pool
    program ran.  Returns uid -> output."""
    n_in = sched.cfg.layer_sizes[0]
    drive = np.zeros((sched.slots, n_in), np.float32)
    for uid, row in drives.items():
        drive[sched.user_slot[uid]] = row
    tarr = None
    if teach is not None:
        tarr = np.zeros((sched.slots, sched.cfg.layer_sizes[-1]),
                        np.float32)
        for uid, row in teach.items():
            tarr[sched.user_slot[uid]] = row
        tarr = jnp.asarray(tarr)
    x = jnp.asarray(drive)
    if k is not None:
        x = jnp.broadcast_to(x[None], (k, sched.slots, n_in))
    fn = sched._step if k is None else sched._rollout
    res = fn(sched.fleet, x, sched._active_mask(), tarr,
             jnp.asarray(sched._steps.astype(np.int32)))
    sched.fleet = res[0]
    sched.advance_steps(1 if k is None else k)
    axis = 0 if k is None else 1
    return {u: np.take(np.asarray(res[1]), slot, axis=axis)
            for u, slot in sched.user_slot.items()}


class TestHostOperandsBitwise:
    """Handing the pool program host operands changes no number: pool
    state and every session's outputs are bitwise those of a reference
    run that made each operand a device array first."""

    @pytest.mark.parametrize("meshed", [False, True])
    @pytest.mark.parametrize("method", ["step", "pool_step"])
    @pytest.mark.parametrize("with_teach", [False, True])
    @pytest.mark.parametrize("datapath", ["float32", "int8"])
    def test_matches_device_operand_reference(self, datapath, with_teach,
                                              method, meshed):
        from repro.obs.health import HealthConfig
        prog, ref = (_pool_for_dispatch(datapath, meshed, slots=4,
                                        health=HealthConfig())
                     for _ in range(2))
        for s in (prog, ref):
            for u in ("a", "b", "c", "d"):
                s.admit(u)
        k = None if method == "step" else 3
        for t in range(6):
            if t == 2:                    # one vacant, one quarantined slot
                for s in (prog, ref):
                    s.evict("b")
                    s.quarantine("c")
            d = _dispatch_drives(prog, t)
            teach = ({u: np.full(4, 0.5 * (t % 3), np.float32)
                      for u in ("a", "d")} if with_teach else None)
            kw = {} if k is None else {"timesteps": k}
            got = getattr(prog, method)(d, teach=teach, **kw)
            want = _reference_call(ref, d, teach, k)
            assert sorted(got) == sorted(want) == sorted(prog.user_slot)
            for u in want:
                np.testing.assert_array_equal(np.asarray(got[u]), want[u])
            for x, y in zip(jax.tree.leaves(prog.fleet),
                            jax.tree.leaves(ref.fleet)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert np.abs(want["a"]).max() > 0.1
        np.testing.assert_array_equal(prog._steps, ref._steps)
