"""Multi-device session pools: sharding, fault tolerance, elastic re-mesh.

Pins, converting the `distributed/` seed modules' contracts into gates:

  1. MESH TRANSPARENCY — a pool on a single-device mesh is bitwise
     identical to the unmeshed pool (the `sharding.py` docstring contract:
     without/with a trivial mesh the identical code runs), per backend and
     datapath, with `shard_constraint` a no-op when no mesh is active.
  2. DEVICE PARITY — on D=2/4 forced host devices the sharded pool's
     trajectories are bit-identical to D=1 (slot rows are mutually
     independent; `engine.fleet_spmd` runs the same program per shard),
     and churn after warmup stays at ZERO recompiles.
  3. DEVICE-LOSS RECOVERY — `fail_device`/`fail_slots` poison a shard,
     `drain_failed` re-homes its sessions onto surviving devices from
     `SessionStore` checkpoints, and every drained session's subsequent
     trajectory is bit-identical to an uninterrupted control pool (the
     evict -> re-admit invariant extended across devices).  Poisoned rows
     never leak into survivors' math.
  4. ELASTIC RE-MESH — `save_pool` at D devices + `load_pool` at D'
     (including unmeshed) resumes occupancy, step counters, and bits.
  5. SESSION HEALTH UNDER MESH — the ``record=`` trace variants, the
     flight-recorder state, and quarantine -> rollback remediation are
     bit-identical between meshed and unmeshed pools, and churn through
     the record variants (elastic re-mesh restores included) stays silent
     under the armed recompile watchdog.

The D>1 cells need forced host devices and run under the `multidevice-
smoke` CI lane (``XLA_FLAGS=--xla_force_host_platform_device_count=4``);
in a single-device session they skip.  One subprocess test forces 4
devices from inside tier-1 so the sharded path never goes ungated.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import snn
from repro.distributed import sharding as dsh
from repro.obs.health import HealthConfig
from repro.serving import SessionStore
from repro.serving.scheduler import SHARED, FleetScheduler

IMPLS = ["xla", "pallas-interpret"]
DATAPATHS = ["float32", "int8"]
CELLS = [(i, d) for i in IMPLS for d in DATAPATHS]

N_DEV = len(jax.devices())
multidevice = pytest.mark.skipif(
    N_DEV < 4, reason="needs XLA_FLAGS=--xla_force_host_platform_"
                      "device_count=4 (the multidevice-smoke CI lane)")


def _cfg(impl, datapath):
    cfg = snn.SNNConfig(layer_sizes=(8, 16, 4), impl=impl, block_m=16)
    if datapath == "int8":
        cfg = snn.quant_config(cfg, impl=impl, block_m=16)
    return cfg


def _drive(uid, t, n=8):
    phase = (hash(uid) % 97) / 97
    return np.sin(0.3 * t + phase + np.arange(n)).astype(np.float32)


def _sched(impl, datapath, slots=4, mesh=None, store=None, health=None):
    cfg = _cfg(impl, datapath)
    theta = snn.init_theta(cfg, jax.random.PRNGKey(0))
    return FleetScheduler(cfg, theta, slots=slots, mesh=mesh, store=store,
                          health=health)


# recording enabled, every detector disabled: the mesh-parity tests want
# the flight recorder running without any verdict-driven divergence
HEALTH_OFF = HealthConfig(z_threshold=1e9, bounds=((-1e9, 1e9),) * 4,
                          dead_floor=-1.0, hysteresis=(9999,) * 4)


def _assert_outputs_equal(a, b):
    assert a.keys() == b.keys()
    for u in a:
        np.testing.assert_array_equal(np.asarray(a[u]), np.asarray(b[u]))


def _assert_pools_equal(a, b):
    for x, y in zip(jax.tree.leaves(a.pool), jax.tree.leaves(b.pool)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _lm_model():
    """A dense smoke LM with a float32 plastic adapter (the
    tests/test_serving_lm.py idiom; mesh parity needs just one cell — the
    sharded-jit wrapper is datapath-blind)."""
    from repro.models import factory
    cfg = factory.build("qwen3-4b", smoke=True).cfg.with_(
        plastic_adapter=True, adapter_neurons=8, adapter_impl="xla",
        adapter_quant=False)
    model = factory.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params["adapter"]["scale"] = jnp.float32(0.5)
    return model, params


class TestShardingHelpers:
    def test_fleet_mesh_shape_and_axis(self):
        mesh = dsh.fleet_mesh(1)
        assert mesh.axis_names == ("data",)
        assert mesh.shape["data"] == 1
        assert dsh.fleet_mesh().shape["data"] == N_DEV

    def test_fleet_mesh_rejects_bad_counts(self):
        with pytest.raises(ValueError):
            dsh.fleet_mesh(0)
        with pytest.raises(ValueError):
            dsh.fleet_mesh(N_DEV + 1)

    def test_slot_pspec(self):
        assert dsh.slot_pspec(0) == P("data")
        assert dsh.slot_pspec(2) == P(None, None, "data")
        assert dsh.slot_pspec(SHARED) == P()
        assert dsh.slot_pspec(None) == P()
        # bool is an int subclass but never a slot axis
        assert dsh.slot_pspec(True) == P()

    def test_pool_shardings_follow_axes_pytree(self):
        mesh = dsh.fleet_mesh(1)
        axes = {"w": (0, 0), "cache": 2, "clock": SHARED}
        sh = dsh.pool_shardings(mesh, axes)
        assert sh["w"][0].spec == P("data")
        assert sh["cache"].spec == P(None, None, "data")
        assert sh["clock"].spec == P()
        assert all(s.mesh.shape["data"] == 1
                   for s in jax.tree.leaves(sh))

    def test_shard_constraint_noop_without_mesh(self):
        """The sharding.py docstring contract, previously unpinned: with no
        active mesh every constraint is an identity pass-through, so unit
        tests run the identical code on one device."""
        assert dsh.get_mesh() is None
        x = jnp.arange(8.0)
        assert dsh.shard_constraint(x, ("data",)) is x

    def test_pool_mesh_validation(self):
        from jax.sharding import Mesh
        with pytest.raises(ValueError, match="data"):
            _sched("xla", "float32",
                   mesh=Mesh(np.array(jax.devices()[:1]), ("model",)))
        if N_DEV >= 4:
            with pytest.raises(ValueError, match="divide"):
                _sched("xla", "float32", slots=6, mesh=dsh.fleet_mesh(4))


class TestSingleDeviceMesh:
    """A trivial (D=1) mesh must not change a single bit anywhere."""

    @pytest.mark.parametrize("impl,datapath", CELLS)
    def test_bitwise_vs_unmeshed(self, impl, datapath):
        ref = _sched(impl, datapath)
        m = _sched(impl, datapath, mesh=dsh.fleet_mesh(1))
        for s in (ref, m):
            for u in ("a", "b", "c"):
                s.admit(u)
        for t in range(3):
            d = {u: _drive(u, t) for u in ("a", "b", "c")}
            _assert_outputs_equal(ref.step(dict(d)), m.step(dict(d)))
        d = {u: _drive(u, 9) for u in ("a", "b", "c")}
        _assert_outputs_equal(ref.pool_step(dict(d), timesteps=3),
                              m.pool_step(dict(d), timesteps=3))
        # churn parity: evict -> re-admit into the meshed pool round-trips
        for s in (ref, m):
            s.evict("b")
            s.admit("b")
        d = {u: _drive(u, 20) for u in ("a", "b", "c")}
        _assert_outputs_equal(ref.step(dict(d)), m.step(dict(d)))
        _assert_pools_equal(ref, m)

    def test_telemetry_variant_parity(self):
        ref = _sched("xla", "float32")
        m = _sched("xla", "float32", mesh=dsh.fleet_mesh(1))
        for s in (ref, m):
            s.admit("a")
            s.admit("b")
        d = {u: _drive(u, 0) for u in ("a", "b")}
        o1, t1 = ref.step(dict(d), telemetry=True)
        o2, t2 = m.step(dict(d), telemetry=True)
        _assert_outputs_equal(o1, o2)
        for x, y in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_record_variant_parity(self):
        """The record= trace variant on a single-device mesh: outputs,
        pool state, the whole flight-recorder pytree, and the latched
        verdict are bitwise identical to the unmeshed recording pool."""
        ref = _sched("xla", "float32", health=HEALTH_OFF)
        m = _sched("xla", "float32", mesh=dsh.fleet_mesh(1),
                   health=HEALTH_OFF)
        users = ("a", "b", "c")
        for s in (ref, m):
            for u in users:
                s.admit(u)
        for t in range(3):
            d = {u: _drive(u, t) for u in users}
            _assert_outputs_equal(ref.step(dict(d), record=True),
                                  m.step(dict(d), record=True))
        d = {u: _drive(u, 9) for u in users}
        _assert_outputs_equal(
            ref.pool_step(dict(d), timesteps=3, record=True),
            m.pool_step(dict(d), timesteps=3, record=True))
        _assert_pools_equal(ref, m)
        for x, y in zip(jax.tree.leaves(ref._rec), jax.tree.leaves(m._rec)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(ref.last_verdict),
                                      np.asarray(m.last_verdict))


class TestFailureDrain:
    """Slot-level failure injection + drain (mesh-free machinery: the same
    path the device-level tests drive at D=4)."""

    @pytest.mark.parametrize("impl,datapath",
                             [("xla", "float32"), ("xla", "int8"),
                              ("pallas-interpret", "int8")])
    def test_drain_bit_identity_vs_uninterrupted(self, impl, datapath):
        ctrl = _sched(impl, datapath)
        vict = _sched(impl, datapath)
        for s in (ctrl, vict):
            s.admit("a")
            s.admit("b")
        for t in range(3):
            d = {u: _drive(u, t) for u in ("a", "b")}
            _assert_outputs_equal(ctrl.step(dict(d)), vict.step(dict(d)))
        vict.persist_resident()
        stranded = vict.fail_slots([0], poison=True)
        assert stranded == ["a"]
        assert vict.stranded_sessions() == ["a"]
        rep = vict.drain_failed()
        assert [r["uid"] for r in rep] == ["a"]
        assert rep[0]["from_slot"] == 0 and rep[0]["to_slot"] != 0
        assert rep[0]["steps_lost"] == 0
        for t in range(3, 6):
            d = {u: _drive(u, t) for u in ("a", "b")}
            _assert_outputs_equal(ctrl.step(dict(d)), vict.step(dict(d)))

    def test_poison_isolated_from_survivors(self):
        """While a failed slot is stranded (before drain), the survivors'
        math must not see its NaN rows: the active mask freezes and
        isolates it exactly like a vacant slot."""
        ctrl = _sched("xla", "float32")
        vict = _sched("xla", "float32")
        for s in (ctrl, vict):
            s.admit("a")
            s.admit("b")
        vict.fail_slots([vict.user_slot["a"]], poison=True)
        d = {u: _drive(u, 0) for u in ("a", "b")}
        ov = vict.step(dict(d))
        oc = ctrl.step({"b": d["b"], "a": d["a"]})
        np.testing.assert_array_equal(np.asarray(ov["b"]),
                                      np.asarray(oc["b"]))
        # the stranded session's output is masked to zeros, not NaN
        assert np.all(np.asarray(ov["a"]) == 0)

    def test_lost_slot_never_admits_and_refuses_evict(self):
        s = _sched("xla", "float32", slots=2)
        s.admit("a")
        s.fail_slots([s.user_slot["a"]], poison=True)
        with pytest.raises(RuntimeError, match="drain_failed"):
            s.evict("a")
        s.admit("b")                       # lands in the surviving slot
        assert s.user_slot["b"] != s.user_slot["a"]
        assert s.free_slots == 0           # lost slot is not free
        with pytest.raises(RuntimeError, match="full"):
            s.admit("c")
        # LRU eviction must never pick the lost slot either
        s2 = _sched("xla", "float32", slots=2)
        s2.admit("x")
        s2.admit("y")
        s2.fail_slots([s2.user_slot["x"]], poison=True)
        s2.admit("z", evict_lru=True)      # evicts y, never lost x
        assert "x" in s2.user_slot and "y" not in s2.user_slot

    def test_steps_lost_reporting(self):
        """Steps taken after the last durable snapshot are the blast
        radius of a failure, and the drain report says exactly how many."""
        s = _sched("xla", "float32")
        s.admit("a")
        for t in range(3):
            s.step({"a": _drive("a", t)})
        s.persist_resident()
        for t in range(3, 7):              # 4 steps past the snapshot
            s.step({"a": _drive("a", t)})
        s.fail_slots([s.user_slot["a"]])
        rep = s.drain_failed()
        assert rep[0]["steps_lost"] == 4
        assert int(s._steps[s.user_slot["a"]]) == 3   # resumed at snapshot

    def test_fresh_session_drains_to_zero_state(self):
        """A never-persisted session has no checkpoint: drain restarts it
        from the factory state and reports every step lost."""
        s = _sched("xla", "float32")
        s.admit("a")
        for t in range(2):
            s.step({"a": _drive("a", t)})
        s.fail_slots([s.user_slot["a"]])
        rep = s.drain_failed()
        assert rep[0]["steps_lost"] == 2
        fresh = _sched("xla", "float32")
        fresh.admit("a")
        o1 = s.step({"a": _drive("a", 0)})
        o2 = fresh.step({"a": _drive("a", 0)})
        _assert_outputs_equal(o1, o2)


class TestPoolCheckpoint:
    def test_save_load_round_trip(self, tmp_path):
        s = _sched("xla", "int8")
        s.admit("a")
        s.admit("b")
        for t in range(3):
            s.step({u: _drive(u, t) for u in ("a", "b")})
        s.evict("b")
        s.save_pool(str(tmp_path))
        fresh = _sched("xla", "int8")
        fresh.load_pool(str(tmp_path))
        assert fresh.slot_user == s.slot_user
        assert fresh.user_slot == s.user_slot
        np.testing.assert_array_equal(fresh._steps, s._steps)
        _assert_pools_equal(fresh, s)
        o1 = s.step({"a": _drive("a", 9)})
        o2 = fresh.step({"a": _drive("a", 9)})
        _assert_outputs_equal(o1, o2)

    def test_save_refuses_stranded_sessions(self, tmp_path):
        s = _sched("xla", "float32")
        s.admit("a")
        s.fail_slots([s.user_slot["a"]])
        with pytest.raises(RuntimeError, match="drain"):
            s.save_pool(str(tmp_path))
        s.drain_failed()
        s.save_pool(str(tmp_path))         # drained pool checkpoints fine

    def test_load_rejects_slot_count_mismatch(self, tmp_path):
        s = _sched("xla", "float32", slots=4)
        s.save_pool(str(tmp_path))
        other = _sched("xla", "float32", slots=2)
        # the manager's leaf-shape validation fires first (slot rows are
        # leading dims); the pool's own slots gate backstops sharded loads
        with pytest.raises(ValueError, match="slots|shape mismatch"):
            other.load_pool(str(tmp_path))


class TestLMSingleDeviceMesh:
    def test_token_parity(self):
        from repro.serving import LMScheduler
        model, params = _lm_model()
        rng = np.random.RandomState(7)
        prompts = {u: rng.randint(0, model.cfg.vocab,
                                  size=5).astype(np.int32)
                   for u in ("u", "v")}
        ref = LMScheduler(model, params, slots=2, max_len=16)
        m = LMScheduler(model, params, slots=2, max_len=16,
                        mesh=dsh.fleet_mesh(1))
        for s in (ref, m):
            for u, p in prompts.items():
                s.admit_prompt(u, p)
        assert {u: ref.pending(u) for u in prompts} == \
               {u: m.pending(u) for u in prompts}
        for _ in range(5):
            assert ref.step() == m.step()
        w = {u: np.asarray([ref.pending(u), 3, 5], np.int32)
             for u in prompts}
        la, lb = ref.decode_window(dict(w)), m.decode_window(dict(w))
        for u in la:
            np.testing.assert_array_equal(
                np.argmax(np.asarray(la[u]), -1),
                np.argmax(np.asarray(lb[u]), -1))


@multidevice
class TestMultiDevice:
    """The D=2/4 cells (the multidevice-smoke CI lane)."""

    @pytest.mark.parametrize("impl,datapath",
                             [("xla", "float32"), ("xla", "int8"),
                              ("pallas-interpret", "float32")])
    @pytest.mark.parametrize("d", [2, 4])
    def test_pool_parity_vs_single_device(self, impl, datapath, d):
        users = [f"u{i}" for i in range(6)]
        ref = _sched(impl, datapath, slots=8)
        m = _sched(impl, datapath, slots=8, mesh=dsh.fleet_mesh(d))
        for s in (ref, m):
            for u in users:
                s.admit(u)
        for t in range(2):
            dd = {u: _drive(u, t) for u in users}
            _assert_outputs_equal(ref.step(dict(dd)), m.step(dict(dd)))
        dd = {u: _drive(u, 5) for u in users}
        _assert_outputs_equal(ref.pool_step(dict(dd), timesteps=3),
                              m.pool_step(dict(dd), timesteps=3))
        _assert_pools_equal(ref, m)

    @pytest.mark.parametrize("datapath", DATAPATHS)
    @pytest.mark.parametrize("d", [2, 4])
    def test_unpack_is_the_program_array_at_each_slot(self, d, datapath):
        """The one-launch split of a sharded call's output: every uid's
        array is bitwise the meshed program's output at its slot, and the
        unmeshed pool's."""
        users = [f"u{i}" for i in range(6)]
        ref = _sched("xla", datapath, slots=8)
        m = _sched("xla", datapath, slots=8, mesh=dsh.fleet_mesh(d))
        seen = []
        for name in ("_step", "_rollout"):
            def spy(*args, _real=getattr(m, name)):
                res = _real(*args)
                seen.append(res[1])
                return res
            setattr(m, name, spy)
        for s in (ref, m):
            for u in users:
                s.admit(u)
            s.evict("u2")
        for call, slot_axis, kw in (("step", 0, {}),
                                    ("pool_step", 1, {"timesteps": 3})):
            dd = {u: _drive(u, 7) for u in ref.active_users}
            want, got = (getattr(s, call)(dict(dd), **kw) for s in (ref, m))
            _assert_outputs_equal(want, got)
            arr = np.asarray(seen[-1])
            for u, slot in m.user_slot.items():
                np.testing.assert_array_equal(
                    np.asarray(got[u]), np.take(arr, slot, axis=slot_axis))
        assert m.compiled_programs()["pool_unpack"] == 2

    def test_zero_recompiles_under_churn(self):
        m = _sched("xla", "float32", slots=8, mesh=dsh.fleet_mesh(4))
        users = [f"u{i}" for i in range(6)]
        for u in users:
            m.admit(u)
        m.step({u: _drive(u, 0) for u in users})
        m.pool_step({u: _drive(u, 1) for u in users}, timesteps=3)
        m.evict("u0")
        m.admit("u0")
        warm = m.compile_count()
        for t in range(5):
            m.evict("u0")
            m.admit("u0")
            m.evict("u3")
            m.admit(f"g{t}")
            m.step({u: _drive(u, t) for u in m.active_users})
            m.pool_step({u: _drive(u, 50 + t) for u in m.active_users},
                        timesteps=3)
            m.evict(f"g{t}")
            m.admit("u3")
        assert m.compile_count() == warm, m.compiled_programs()

    @pytest.mark.parametrize("impl,datapath", CELLS)
    def test_device_drain_bit_identity(self, impl, datapath):
        """Kill device 0's shard; its sessions drain onto surviving
        devices and every subsequent trajectory is bit-identical to an
        uninterrupted single-device control — both backends, float32 and
        int8 (the PR's acceptance gate)."""
        users = [f"u{i}" for i in range(6)]
        ctrl = _sched(impl, datapath, slots=8)
        m = _sched(impl, datapath, slots=8, mesh=dsh.fleet_mesh(4))
        for s in (ctrl, m):
            for u in users:
                s.admit(u)
        for t in range(2):
            d = {u: _drive(u, t) for u in users}
            _assert_outputs_equal(ctrl.step(dict(d)), m.step(dict(d)))
        warm = m.compile_count()
        m.persist_resident()
        stranded = m.fail_device(0, poison=True)
        assert stranded                     # device 0 held slots 0-1
        rep = m.drain_failed()
        assert {r["uid"] for r in rep} == set(stranded)
        assert all(r["from_device"] == 0 and r["to_device"] != 0
                   for r in rep)
        assert all(r["steps_lost"] == 0 for r in rep)
        for t in range(2, 5):
            d = {u: _drive(u, t) for u in users}
            _assert_outputs_equal(ctrl.step(dict(d)), m.step(dict(d)))
        assert m.compile_count() == warm    # drain reuses warm programs

    def test_elastic_restore_across_device_counts(self, tmp_path):
        """A pool checkpointed at D=4 resumes at D'=2 and unmeshed with
        identical occupancy and bits (`ft.elastic_restore` under
        `load_pool`: leaves are stored unsharded, restore is a pure
        device_put onto the new NamedShardings)."""
        users = [f"u{i}" for i in range(6)]
        src = _sched("xla", "int8", slots=8, mesh=dsh.fleet_mesh(4))
        for u in users:
            src.admit(u)
        for t in range(3):
            src.step({u: _drive(u, t) for u in users})
        src.save_pool(str(tmp_path))
        for mesh in (dsh.fleet_mesh(2), None):
            tgt = _sched("xla", "int8", slots=8, mesh=mesh)
            tgt.load_pool(str(tmp_path))
            assert tgt.slot_user == src.slot_user
            np.testing.assert_array_equal(tgt._steps, src._steps)
            d = {u: _drive(u, 9) for u in users}
            _assert_outputs_equal(src.pool_step(dict(d), timesteps=2),
                                  tgt.pool_step(dict(d), timesteps=2))
            src.load_pool(str(tmp_path))   # rewind the source for the
            #                                next target's comparison

    def test_lm_pool_parity_d2(self):
        from repro.serving import LMScheduler
        model, params = _lm_model()
        rng = np.random.RandomState(11)
        prompts = {u: rng.randint(0, model.cfg.vocab,
                                  size=5).astype(np.int32)
                   for u in ("u", "v", "w")}
        ref = LMScheduler(model, params, slots=4, max_len=16)
        m = LMScheduler(model, params, slots=4, max_len=16,
                        mesh=dsh.fleet_mesh(2))
        for s in (ref, m):
            for u, p in prompts.items():
                s.admit_prompt(u, p)
        for _ in range(5):
            assert ref.step() == m.step()

    @pytest.mark.parametrize("impl,datapath",
                             [("xla", "float32"), ("xla", "int8")])
    def test_meshed_record_parity_and_rollback(self, impl, datapath):
        """Recording, quarantine, and rollback on a D=4 pool are bitwise
        identical to the unmeshed pool: the recorder state shards over the
        slot axis, the quarantine freeze is the same runtime mask, and the
        rolled-back session resumes the same checkpoint bits."""
        users = [f"u{i}" for i in range(6)]
        ref = _sched(impl, datapath, slots=8, health=HEALTH_OFF)
        m = _sched(impl, datapath, slots=8, mesh=dsh.fleet_mesh(4),
                   health=HEALTH_OFF)
        for s in (ref, m):
            for u in users:
                s.admit(u)
        for t in range(3):
            d = {u: _drive(u, t) for u in users}
            _assert_outputs_equal(ref.step(dict(d), record=True),
                                  m.step(dict(d), record=True))
        for x, y in zip(jax.tree.leaves(ref._rec), jax.tree.leaves(m._rec)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        for s in (ref, m):
            assert s.health_checkpoint() == len(users)
            s.quarantine("u2")
        for t in range(3, 5):       # u2 frozen on both pools
            d = {u: _drive(u, t) for u in users}
            _assert_outputs_equal(ref.step(dict(d), record=True),
                                  m.step(dict(d), record=True))
        ra, rb = ref.rollback("u2"), m.rollback("u2")
        assert ra["steps_lost"] == rb["steps_lost"] == 2
        for t in range(5, 8):
            d = {u: _drive(u, t) for u in users}
            _assert_outputs_equal(ref.step(dict(d), record=True),
                                  m.step(dict(d), record=True))
        _assert_pools_equal(ref, m)

    def test_record_churn_and_remesh_watchdog_silent(self, tmp_path):
        """Armed-watchdog gate over the meshed health path: session churn
        through the record variants AND an elastic re-mesh restore into an
        already-warmed pool compile nothing."""
        from repro.obs.watchdog import watchdog as watch
        users = [f"u{i}" for i in range(6)]
        m = _sched("xla", "float32", slots=8, mesh=dsh.fleet_mesh(4),
                   health=HEALTH_OFF)
        for u in users:
            m.admit(u)
        m.step({u: _drive(u, 0) for u in users}, record=True)
        m.pool_step({u: _drive(u, 1) for u in users}, timesteps=3,
                    record=True)
        m.evict("u0")               # warms recorder_reset under the mesh
        m.admit("u0")
        m.save_pool(str(tmp_path))
        tgt = _sched("xla", "float32", slots=8, mesh=dsh.fleet_mesh(2),
                     health=HEALTH_OFF)
        tgt.load_pool(str(tmp_path))
        tgt.step({u: _drive(u, 2) for u in tgt.active_users}, record=True)
        tgt.evict("u0")
        tgt.admit("u0")
        warm_m, warm_t = m.compile_count(), tgt.compile_count()
        watch.install()
        watch.reset()
        with watch.armed():
            for t in range(3):
                m.evict("u1")
                m.admit(f"g{t}")
                m.step({u: _drive(u, t) for u in m.active_users},
                       record=True)
                m.pool_step({u: _drive(u, 50 + t) for u in m.active_users},
                            timesteps=3, record=True)
                m.evict(f"g{t}")
                m.admit("u1")
            # elastic re-mesh restore into the warmed D=2 pool (load_pool
            # rebuilds the recorder lazily; same shapes, same shardings)
            tgt.load_pool(str(tmp_path))
            tgt.step({u: _drive(u, 9) for u in tgt.active_users},
                     record=True)
        assert watch.violations == 0, watch.violation_signatures
        assert m.compile_count() == warm_m, m.compiled_programs()
        assert tgt.compile_count() == warm_t, tgt.compiled_programs()

    def test_drained_session_survives_durable_store(self, tmp_path):
        """Drain from an on-disk SessionStore (not just the RAM archive):
        the recovery path CI exercises is the deployment path."""
        store_a = SessionStore(root=str(tmp_path / "a"))
        store_b = SessionStore(root=str(tmp_path / "b"))
        ctrl = _sched("xla", "float32", slots=8, store=store_a)
        m = _sched("xla", "float32", slots=8, mesh=dsh.fleet_mesh(4),
                   store=store_b)
        for s in (ctrl, m):
            for u in ("a", "b", "c"):
                s.admit(u)
        for t in range(2):
            d = {u: _drive(u, t) for u in ("a", "b", "c")}
            _assert_outputs_equal(ctrl.step(dict(d)), m.step(dict(d)))
        m.persist_resident()
        m.fail_device(0, poison=True)
        m.drain_failed()
        for t in range(2, 4):
            d = {u: _drive(u, t) for u in ("a", "b", "c")}
            _assert_outputs_equal(ctrl.step(dict(d)), m.step(dict(d)))


class TestForcedMultiDeviceSubprocess:
    """Tier-1's view of the multi-device path: force 4 host devices in a
    subprocess (the flag must be set before jax initializes, so it cannot
    run in-process) and assert the core sharding contracts end to end."""

    def test_sharded_pool_parity_drain_and_elastic(self):
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=4")
            import tempfile
            import jax
            import numpy as np
            assert len(jax.devices()) == 4, jax.devices()
            from repro.core import snn
            from repro.distributed import sharding as dsh
            from repro.serving.scheduler import FleetScheduler

            cfg = snn.SNNConfig(layer_sizes=(8, 16, 4), impl="xla")
            theta = snn.init_theta(cfg, jax.random.PRNGKey(0))

            def drive(uid, t, n=8):
                ph = (hash(uid) % 97) / 97
                return np.sin(0.3 * t + ph + np.arange(n)).astype(
                    np.float32)

            users = ["u%d" % i for i in range(6)]
            ref = FleetScheduler(cfg, theta, slots=8)
            m = FleetScheduler(cfg, theta, slots=8,
                               mesh=dsh.fleet_mesh(4))
            for s in (ref, m):
                for u in users:
                    s.admit(u)
            for t in range(2):
                d = {u: drive(u, t) for u in users}
                o1, o2 = ref.step(dict(d)), m.step(dict(d))
                for u in users:
                    np.testing.assert_array_equal(
                        np.asarray(o1[u]), np.asarray(o2[u]))
            warm = m.compile_count()
            m.persist_resident()
            stranded = m.fail_device(0, poison=True)
            rep = m.drain_failed()
            assert {r["uid"] for r in rep} == set(stranded)
            assert all(r["to_device"] != 0 for r in rep), rep
            for t in range(2, 5):
                d = {u: drive(u, t) for u in users}
                o1, o2 = ref.step(dict(d)), m.step(dict(d))
                for u in users:
                    np.testing.assert_array_equal(
                        np.asarray(o1[u]), np.asarray(o2[u]))
            assert m.compile_count() == warm
            with tempfile.TemporaryDirectory() as td:
                m.save_pool(td)
                tgt = FleetScheduler(cfg, theta, slots=8,
                                     mesh=dsh.fleet_mesh(2))
                tgt.load_pool(td)
                d = {u: drive(u, 9) for u in users}
                o1, o2 = m.pool_step(dict(d), timesteps=2), \\
                    tgt.pool_step(dict(d), timesteps=2)
                for u in users:
                    np.testing.assert_array_equal(
                        np.asarray(o1[u]), np.asarray(o2[u]))
            print("multidevice-ok")
        """)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)         # the child sets its own
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=600,
                              env=env)
        assert proc.returncode == 0, proc.stderr
        assert "multidevice-ok" in proc.stdout

    def test_meshed_unpack_on_four_host_devices(self):
        """The multi-device cells of the one-launch output split, run from
        tier-1 in a process with 4 forced host devices."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             "-p", "no:xdist", os.path.abspath(__file__),
             "-k", "test_unpack_is_the_program_array_at_each_slot"],
            capture_output=True, text=True, timeout=600, env=env)
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr
        assert "4 passed" in proc.stdout, proc.stdout[-3000:]
