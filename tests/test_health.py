"""Session-health pins: flight recorder, streaming detectors, quarantine ->
rollback remediation (src/repro/obs/health.py, obs/recorder.py, and the
schedulers' ``record=`` trace variants).

The contracts this file locks down (DESIGN.md §Health):

  1. DETECTOR ORACLES — each of the four streaming detectors (ewma_z,
     bound, stuck, dead) fires exactly at its hysteresis count, LATCHES
     once flagged, respects warmup gating (bound alone fires cold), and
     holds inactive slots' state bit-exactly with streaks reset.  The EWMA
     baseline is WINSORIZED-robust: a z-firing sample teaches it only a
     clipped ±z_threshold·sigma deviation, so a sustained fault cannot
     drag the mean under itself within a hysteresis streak, while a
     recurring clean burst re-teaches the variance and stops firing.
  2. RECORDER MECHANICS — the (B, W, C) ring wraps and unrolls
     oldest->newest, wnorm0 latches at a slot's FIRST ACTIVE step (drift
     channel starts at exactly 0), `reset_slot` zeroes one slot's rows
     only, and inactive slots record exact zeros.
  3. RECORD IS FREE WHEN OFF — ``record=True`` pool stepping leaves the
     fleet state and outputs BITWISE identical to ``record=False`` on xla
     AND pallas-interpret, float32 AND int8; without ``health=`` it raises.
  4. THE INCIDENT DRILL (the headline): clean warmup -> health_checkpoint
     -> injected drive blowout -> flagged within the hysteresis budget ->
     remediate (quarantine + incident dump + rollback) -> the session's
     continuation is BITWISE identical to a manual evict-before-incident /
     re-admit control run — with ZERO recompiles under the armed watchdog
     and the compile-audit dict pinned exactly.
  5. QUARANTINE SEMANTICS — a quarantined slot is bit-frozen like a vacant
     one; evict/save_pool/LRU-admission refuse quarantined sessions;
     rollback demands a prior quarantine; lost slots are drain_failed's
     business, not quarantine's.
  6. LM POOL PARITY — quarantine/rollback on the decode pool: frozen
     decode steps leave the session row bit-unchanged and the rolled-back
     stream's tokens match the manual-control run exactly.
  7. PLUMBING — `serve_metrics` serves real HTTP (prom text + JSON + 404),
     anomaly presets are deterministic and validated, and the
     fault-tolerant runner's registry counters reconcile with its events.
"""
import dataclasses
import json
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import snn
from repro.distributed.ft import FaultTolerantRunner
from repro.kernels.plasticity import quant as Q
from repro.models import factory
from repro.obs import MetricsRegistry, serve_metrics
from repro.obs.health import (CHANNELS, DETECTORS, HealthConfig, HealthState,
                              health_update, init_health)
from repro.obs.recorder import (init_recorder, recorder_update, reset_slot,
                                unroll_ring)
from repro.obs.watchdog import watchdog as watch
from repro.scenarios import ANOMALIES, AnomalyPreset, inject_anomaly
from repro.serving import FleetScheduler
from repro.serving.lm import LMScheduler

IMPLS = ["xla", "pallas-interpret"]
DATAPATHS = ["float32", "int8"]

_OFF = 1e9      # an "effectively disabled" threshold / corridor edge
_NEVER = 9999   # an "effectively disabled" hysteresis count


def _np(x):
    return np.asarray(jax.device_get(x))


def _trees_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(_np(x), _np(y)),
                 a, b)


def _hcfg(**kw):
    """HealthConfig with every detector disabled; kwargs turn them on."""
    base = dict(window=8, warmup=0, z_threshold=_OFF,
                bounds=((-_OFF, _OFF),) * 4, dead_floor=-1.0,
                hysteresis=(_NEVER,) * 4)
    base.update(kw)
    return HealthConfig(**base)


# ---------------------------------------------------------------------------
# 1. detector oracles (pure health_update)
# ---------------------------------------------------------------------------

class TestHealthConfigValidation:
    def test_rejects_bad_shapes(self):
        with pytest.raises(ValueError):
            HealthConfig(window=0)
        with pytest.raises(ValueError):
            HealthConfig(bounds=((0.0, 1.0),) * 3)
        with pytest.raises(ValueError):
            HealthConfig(hysteresis=(1, 1, 1))
        with pytest.raises(ValueError):
            HealthConfig(hysteresis=(1, 1, 1, 0))


def _x(rows):
    return jnp.asarray(rows, jnp.float32)


class TestHealthUpdate:
    def test_hysteresis_counts_consecutive_fires_only(self):
        """bound must fire hysteresis=3 CONSECUTIVE steps: two fires, a
        clean step (streak resets), two more fires -> still unflagged;
        the third consecutive fire flags."""
        cfg = _hcfg(bounds=((0.0, 1.0),) + ((-_OFF, _OFF),) * 3,
                    hysteresis=(_NEVER, 3, _NEVER, _NEVER))
        h = init_health(cfg, 2)
        act = jnp.ones(2)
        bad = _x([[2.0, 0, 0, 0], [0.5, 0, 0, 0]])
        ok = _x([[0.5, 0, 0, 0], [0.5, 0, 0, 0]])
        for xs in (bad, bad, ok, bad, bad):
            h, verdict = health_update(cfg, h, xs, act)
            assert not _np(verdict).any()
        h, verdict = health_update(cfg, h, bad, act)
        assert _np(verdict).tolist() == [True, False]
        assert _np(h.flagged)[0, DETECTORS.index("bound")]

    def test_flags_latch_after_signal_normalizes(self):
        cfg = _hcfg(bounds=((0.0, 1.0),) + ((-_OFF, _OFF),) * 3,
                    hysteresis=(_NEVER, 1, _NEVER, _NEVER))
        h = init_health(cfg, 1)
        h, verdict = health_update(cfg, h, _x([[2.0, 0, 0, 0]]),
                                   jnp.ones(1))
        assert _np(verdict).all()
        for _ in range(5):
            h, verdict = health_update(cfg, h, _x([[0.5, 0, 0, 0]]),
                                       jnp.ones(1))
            assert _np(verdict).all()
            assert _np(h.streaks)[0, DETECTORS.index("bound")] == 0

    def test_warmup_gates_z_stuck_dead_but_not_bound(self):
        """Before ``warmup`` recorded steps only the absolute corridor may
        fire; once warm, the same frozen/dead/anomalous sample trips
        stuck, dead, and ewma_z too."""
        cfg = _hcfg(warmup=3,
                    bounds=((-_OFF, _OFF), (0.0, 1.0)) + ((-_OFF, _OFF),) * 2,
                    z_threshold=6.0, dead_floor=1e-5,
                    hysteresis=(1, 1, 1, 1))
        h = init_health(cfg, 1)
        xs = _x([[0.0, 2.0, 0, 0]])  # 0 spike rate, dw out of corridor, frozen
        for step in range(6):
            h, _ = health_update(cfg, h, xs, jnp.ones(1))
            flags = {d for i, d in enumerate(DETECTORS)
                     if _np(h.flagged)[0, i]}
            if step < 2:            # stuck needs one prior sample anyway
                assert flags == {"bound"}, (step, flags)
        assert flags == set(DETECTORS), flags

    def test_inactive_slots_hold_state_bit_exactly(self):
        cfg = _hcfg(warmup=0, hysteresis=(2, 2, 2, 2))
        h = init_health(cfg, 2)
        rng = np.random.RandomState(0)
        for _ in range(4):
            h, _ = health_update(cfg, h, _x(rng.rand(2, 4)), jnp.ones(2))
        before = jax.device_get(h)
        # slot 1 goes inactive; its sample arrives as exact zeros (the
        # recorder's gating) and must teach/fire nothing
        h, verdict = health_update(
            cfg, h, _x(np.stack([rng.rand(4), np.zeros(4)])),
            jnp.asarray([1.0, 0.0]))
        after = jax.device_get(h)
        for field in ("ewma_mean", "ewma_var", "last", "flagged", "steps"):
            np.testing.assert_array_equal(
                getattr(before, field)[1], getattr(after, field)[1])
        assert after.streaks[1].tolist() == [0, 0, 0, 0]
        assert not _np(verdict)[1]

    def test_winsorized_baseline_bounds_anomaly_chase(self):
        """A z-firing sample still teaches the EWMA, but only a clipped
        ±z_threshold·sigma deviation: each step's mean move is EXACTLY
        alpha·z_threshold·sigma (never the naive alpha·d chase), so the
        z-score stays above threshold for the whole hysteresis streak and
        the flag latches before the baseline reaches the anomaly."""
        cfg = _hcfg(z_threshold=3.0, warmup=2,
                    hysteresis=(4, _NEVER, _NEVER, _NEVER))
        h = init_health(cfg, 1)
        clean = _x([[1.0, 1.0, 1.0, 1.0]])
        for _ in range(10):
            h, _ = health_update(cfg, h, clean, jnp.ones(1))
        anom = _x([[5.0, 5.0, 5.0, 5.0]])
        a, k = cfg.ewma_alpha, cfg.z_threshold
        for step in range(4):
            mean_pre = _np(h.ewma_mean).copy()
            sigma_pre = np.sqrt(_np(h.ewma_var) + cfg.z_floor ** 2)
            # the sample fires on every step of the streak...
            assert (5.0 - mean_pre > k * sigma_pre).all()
            h, verdict = health_update(cfg, h, anom, jnp.ones(1))
            # ...so the update is the exact winsorized step, not naive EWMA
            np.testing.assert_allclose(
                _np(h.ewma_mean), mean_pre + a * k * sigma_pre, rtol=1e-5)
            assert bool(_np(verdict)[0]) == (step == 3)
        assert _np(h.flagged)[0, DETECTORS.index("ewma_z")]
        # naive chasing would have the mean at ~3.3 by now
        assert (_np(h.ewma_mean) < 2.5).all()

    def test_winsorized_baseline_absorbs_recurring_bursts(self):
        """The flip side of winsorization: a legitimately bimodal channel
        (quiet baseline with recurring bursts — e.g. a tiny adapter's
        quantized spike rate jumping 0 <-> 0.25) fires ewma_z at most a
        couple of consecutive steps before the grown variance absorbs the
        burst; with hysteresis 3 it never flags.  A hard robust gate
        (firing samples never teach) latches here forever."""
        cfg = _hcfg(z_threshold=6.0, warmup=4,
                    hysteresis=(3, _NEVER, _NEVER, _NEVER))
        h = init_health(cfg, 1)
        quiet = _x([[0.0, 0.0, 0.0, 0.0]])
        burst = _x([[0.25, 0.1, 0.875, 0.5]])
        for _ in range(8):
            h, _ = health_update(cfg, h, quiet, jnp.ones(1))
        for cyc in range(6):
            for xs in (burst, burst, burst, quiet, quiet):
                h, verdict = health_update(cfg, h, xs, jnp.ones(1))
                assert not _np(verdict)[0], cyc
        assert not _np(h.flagged).any()


# ---------------------------------------------------------------------------
# 2. recorder mechanics
# ---------------------------------------------------------------------------

class TestRecorder:
    def test_ring_wraps_and_unrolls_oldest_to_newest(self):
        cfg = _hcfg(window=4)
        rec = init_recorder(cfg, 1)
        for t in range(6):
            # last column is the raw weight norm; keep it constant so the
            # drift channel stays 0 and channel 0 carries the step stamp
            ch = _x([[float(t + 1), 0.0, 0.0, 5.0]])
            rec, _ = recorder_update(cfg, rec, ch, jnp.int32(t), jnp.ones(1))
        hist = unroll_ring(_np(rec.ring[0]), pos=6, window=4)
        assert hist.shape == (4, len(CHANNELS))
        np.testing.assert_array_equal(hist[:, 0], [3.0, 4.0, 5.0, 6.0])
        # partial fill: only pos rows exist; empty before any write
        short = unroll_ring(_np(rec.ring[0]), pos=2, window=4)
        assert short.shape == (2, len(CHANNELS))
        assert unroll_ring(_np(rec.ring[0]), pos=0, window=4).shape[0] == 0

    def test_wnorm0_latches_at_first_active_step(self):
        cfg = _hcfg()
        rec = init_recorder(cfg, 2)
        # slot 1 inactive on the first step: no latch, row records zeros
        rec, _ = recorder_update(cfg, rec, _x([[0.1, 0, 0, 3.0],
                                               [0.9, 0, 0, 9.0]]),
                                 jnp.int32(0), jnp.asarray([1.0, 0.0]))
        assert _np(rec.wnorm0).tolist() == [3.0, 0.0]
        np.testing.assert_array_equal(_np(rec.ring)[1, 0], np.zeros(4))
        # drift channel is |wnorm - wnorm0| -> exactly 0 at the latch step
        assert _np(rec.ring)[0, 0, CHANNELS.index("wnorm_drift")] == 0.0
        # slot 1's first ACTIVE step latches ITS norm; slot 0 drifts
        rec, _ = recorder_update(cfg, rec, _x([[0.1, 0, 0, 3.5],
                                               [0.9, 0, 0, 7.0]]),
                                 jnp.int32(1), jnp.ones(2))
        assert _np(rec.wnorm0).tolist() == [3.0, 7.0]
        drift = _np(rec.ring)[:, 1, CHANNELS.index("wnorm_drift")]
        np.testing.assert_allclose(drift, [0.5, 0.0], atol=1e-7)

    def test_reset_slot_zeroes_one_row_only(self):
        cfg = _hcfg()
        rec = init_recorder(cfg, 2)
        for t in range(3):
            rec, _ = recorder_update(cfg, rec,
                                     _x(np.full((2, 4), t + 1.0)),
                                     jnp.int32(t), jnp.ones(2))
        keep = jax.tree.map(lambda a: _np(a)[1].copy(), rec)
        rec2 = reset_slot(rec, jnp.int32(0))
        for leaf in jax.tree.leaves(jax.tree.map(lambda a: _np(a)[0], rec2)):
            assert not np.any(leaf)
        _trees_equal(keep, jax.tree.map(lambda a: _np(a)[1], rec2))


# ---------------------------------------------------------------------------
# fleet fixtures
# ---------------------------------------------------------------------------

def _sched(impl="xla", datapath="float32", slots=4, health=None):
    quant = datapath == "int8"
    cfg = snn.SNNConfig(layer_sizes=(8, 12, 4), timesteps=3, plastic=True,
                        encoding="current", impl=impl,
                        trace_decay=0.75 if quant else 0.8,
                        quant=Q.QuantConfig() if quant else None)
    theta = snn.init_theta(cfg, jax.random.PRNGKey(0), scale=0.05)
    return FleetScheduler(cfg, theta, slots=slots, health=health)


def _clean_drive(uid: str, t: int = 0) -> np.ndarray:
    """Per-user clean drive, CONSTANT across steps (like the obs_health
    benchmark's): on this tiny discrete-spiking net a per-step-varying
    drive makes the telemetry channels jump between quantized levels,
    which is exactly the kind of shift ewma_z exists to flag — a held
    drive keeps the clean baseline stationary."""
    seed = (sum(ord(c) for c in uid) * 131) & 0x7FFFFFFF
    rng = np.random.RandomState(seed)
    return (0.5 * rng.standard_normal(8)).astype(np.float32)


def _own_step_drives(sched, anomalous=None, preset=None):
    """Clean drives keyed on each session's OWN step counter (so a rolled-
    back session replays the same stream its control twin sees)."""
    drives = {}
    for uid, slot in sched.user_slot.items():
        t = int(sched._steps[slot])
        d = _clean_drive(uid, t)
        if uid == anomalous:
            d = inject_anomaly(preset, d, t)
        drives[uid] = d
    return drives


# ---------------------------------------------------------------------------
# 3. record= is a free static variant
# ---------------------------------------------------------------------------

class TestRecordVariant:
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("datapath", DATAPATHS)
    def test_record_off_bitwise_identical(self, impl, datapath):
        """record=True must not perturb the computation: per-step outputs
        and the final fleet state are BITWISE equal to record=False."""
        a = _sched(impl, datapath, health=HealthConfig())
        b = _sched(impl, datapath, health=HealthConfig())
        for s in (a, b):
            s.admit("u0")
            s.admit("u1")
        for t in range(4):
            drives = {u: _clean_drive(u, t) for u in ("u0", "u1")}
            off = a.step(drives)
            on = b.step(drives, record=True)
            for u in off:
                np.testing.assert_array_equal(_np(off[u]), _np(on[u]))
        # the windowed path too (one fused rollout launch per pool_step)
        drives = {u: _clean_drive(u, 99) for u in ("u0", "u1")}
        off = a.pool_step(drives)
        on = b.pool_step(drives, record=True)
        for u in off:
            np.testing.assert_array_equal(_np(off[u]), _np(on[u]))
        _trees_equal(a.fleet, b.fleet)
        assert b.last_verdict is not None and a.last_verdict is None
        assert a.compiled_programs()["pool_step_record"] == 0
        assert b.compiled_programs()["pool_step_record"] == 1
        assert b.compiled_programs()["pool_rollout_record"] == 1

    def test_record_without_health_raises(self):
        sched = _sched()
        sched.admit("u0")
        with pytest.raises(ValueError, match="health=HealthConfig"):
            sched.step({"u0": _clean_drive("u0", 0)}, record=True)


# ---------------------------------------------------------------------------
# 4. the incident drill
# ---------------------------------------------------------------------------

# dead_floor sits two decades under the clean spike rates (~0.3-0.6) but
# above the int8 pool's stochastic-rounding noise floor (~1.5e-3 — rare
# quantization-dither spikes keep the rate from reaching exactly 0)
DRILL_HCFG = HealthConfig(warmup=8, z_threshold=_OFF,
                          bounds=((0.0, _OFF),) * 4, dead_floor=1e-2,
                          hysteresis=(_NEVER, _NEVER, _NEVER, 2))
WARM, CONT = 12, 6


class TestIncidentDrill:
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("datapath", DATAPATHS)
    def test_flag_quarantine_rollback_bit_identity(self, impl, datapath,
                                                   tmp_path):
        """The end-to-end incident drill: clean recorded warmup ->
        health_checkpoint -> an injected dead input collapses the
        session's spike rate and flags it within its hysteresis budget -> remediate (quarantine + flight
        dump + rollback) -> the session's continuation is bitwise
        identical to a manual evict-at-checkpoint control run, with zero
        recompiles under the armed watchdog and the compile audit pinned.
        """
        users = ["u0", "sick", "u2"]
        a = _sched(impl, datapath, health=DRILL_HCFG)
        for u in users:
            a.admit(u)
        for _ in range(WARM):
            a.pool_step(_own_step_drives(a), record=True)
        # pre-warm the recorder-reset program (a steady-state pool has
        # churned at least once since recording began)
        a.admit("tmp")
        a.evict("tmp")
        assert a.flagged_sessions() == []          # clean warmup: no flags
        assert a.health_checkpoint() == len(users)

        preset = AnomalyPreset("dead_input")
        watch.install()
        watch.reset()
        with watch.armed():
            n_anom = 0
            for _ in range(12):
                a.pool_step(_own_step_drives(a, "sick", preset),
                            record=True)
                n_anom += 1
                if "sick" in a.flagged_sessions():
                    break
            assert a.flagged_sessions() == ["sick"]
            # residual membrane/trace activity takes a few windows to decay
            # before the rate crosses dead_floor; then the 2-window streak
            # completes — well inside the 12-window budget either way
            assert n_anom <= 10, n_anom
            flags = _np(a._rec.health.flagged)[a.user_slot["sick"]]
            assert flags[DETECTORS.index("dead")]

            reports = a.remediate(flight_dir=str(tmp_path))
            assert len(reports) == 1
            assert reports[0]["uid"] == "sick"
            assert reports[0]["steps_lost"] == a.cfg.timesteps * n_anom
            assert a.flagged_sessions() == []
            assert a.quarantined_slots == frozenset()

            a_outs = []
            for _ in range(CONT):
                a_outs.append(a.pool_step(_own_step_drives(a),
                                          record=True)["sick"])
        assert watch.violations == 0, watch.violation_signatures
        assert a.compiled_programs() == {
            "slot_put": 1, "slot_take": 1, "recorder_reset": 1,
            "pool_step": 0, "pool_rollout": 0,
            "pool_step_telemetry": 0, "pool_rollout_telemetry": 0,
            "pool_step_record": 0, "pool_rollout_record": 1,
            "pool_unpack": 1}

        # incident bundle: JSON + NPZ post-mortem
        doc = json.load(open(reports[0]["incident"]))
        assert doc["uid"] == "sick" and doc["verdict"]
        assert doc["flagged"]["dead"]
        assert doc["channels"] == list(CHANNELS)
        npz = np.load(os.path.join(str(tmp_path), doc["npz"]))
        assert npz["ring"].shape == (min(WARM + n_anom, DRILL_HCFG.window),
                                     len(CHANNELS))

        # control: same pool, but 'sick' is manually evicted and re-admitted
        # at the checkpoint instead of blowing up — no anomalous steps ever
        b = _sched(impl, datapath, health=DRILL_HCFG)
        for u in users:
            b.admit(u)
        for _ in range(WARM):
            b.pool_step(_own_step_drives(b))
        b.evict("sick")
        b.admit("sick")
        b_outs = [b.pool_step(_own_step_drives(b))["sick"]
                  for _ in range(CONT)]

        for x, y in zip(a_outs, b_outs):
            np.testing.assert_array_equal(_np(x), _np(y))
        _trees_equal(a._take(a.pool, jnp.int32(a.user_slot["sick"])),
                     b._take(b.pool, jnp.int32(b.user_slot["sick"])))


# ---------------------------------------------------------------------------
# 5. quarantine semantics + error paths
# ---------------------------------------------------------------------------

class TestQuarantine:
    def test_quarantine_freezes_slot_bit_exactly(self):
        sched = _sched(health=HealthConfig())
        sched.admit("a")
        sched.admit("b")
        for t in range(3):
            sched.step({u: _clean_drive(u, t) for u in ("a", "b")})
        slot = sched.quarantine("a")
        frozen = jax.tree.map(lambda x: _np(x).copy(),
                              sched._take(sched.pool, jnp.int32(slot)))
        for t in range(3, 6):
            sched.step({u: _clean_drive(u, t) for u in ("a", "b")})
        _trees_equal(frozen, sched._take(sched.pool, jnp.int32(slot)))
        assert sched.quarantined_slots == frozenset({slot})

    def test_error_paths(self, tmp_path):
        sched = _sched(slots=2)
        sched.admit("a")
        sched.admit("b")
        with pytest.raises(KeyError):
            sched.quarantine("ghost")
        with pytest.raises(RuntimeError, match="not quarantined"):
            sched.rollback("a")
        sched.quarantine("a")
        with pytest.raises(RuntimeError, match="quarantined"):
            sched.evict("a")
        with pytest.raises(RuntimeError, match="quarantined"):
            sched.save_pool(str(tmp_path))
        # LRU admission never evicts a quarantined resident
        sched.quarantine("b")
        with pytest.raises(RuntimeError, match="pool is full"):
            sched.admit("c", evict_lru=True)
        # lost slots are drain_failed's business, not quarantine's
        sched2 = _sched(slots=2)
        sched2.admit("a")
        sched2.fail_slots([sched2.user_slot["a"]])
        with pytest.raises(RuntimeError, match="LOST"):
            sched2.quarantine("a")

    def test_remediate_is_noop_on_clean_pool(self):
        sched = _sched(health=HealthConfig())
        sched.admit("a")
        sched.step({"a": _clean_drive("a", 0)}, record=True)
        assert sched.remediate() == []
        # and on a pool that never recorded at all
        assert _sched().remediate() == []

    def test_flagged_sessions_excludes_quarantined_and_lost(self):
        """dead_floor=_OFF turns the dead detector into a 'flag every warm
        active slot' generator: all three users flag, then quarantining /
        losing a slot removes it from the actionable list."""
        cfg = _hcfg(warmup=1, dead_floor=_OFF,
                    hysteresis=(_NEVER, _NEVER, _NEVER, 2))
        sched = _sched(health=cfg)
        for u in ("a", "b", "c"):
            sched.admit(u)
        for t in range(4):
            sched.step({u: _clean_drive(u, t) for u in ("a", "b", "c")},
                       record=True)
        assert sched.flagged_sessions() == ["a", "b", "c"]
        sched.quarantine("b")
        assert sched.flagged_sessions() == ["a", "c"]
        sched.fail_slots([sched.user_slot["c"]], poison=False)
        assert sched.flagged_sessions() == ["a"]


# ---------------------------------------------------------------------------
# 6. LM decode pool parity
# ---------------------------------------------------------------------------

def _model(impl, datapath):
    cfg = factory.build("qwen3-4b", smoke=True).cfg
    cfg = cfg.with_(plastic_adapter=True, adapter_neurons=8,
                    adapter_impl=impl, adapter_quant=(datapath == "int8"))
    model = factory.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params["adapter"]["scale"] = jnp.float32(0.5)
    return model, params


def _prompt(uid, n, vocab):
    rng = np.random.RandomState(sum(ord(c) for c in uid) * 7919 % (2 ** 31))
    return rng.randint(0, vocab, size=n).astype(np.int32)


class TestLMHealth:
    @pytest.mark.parametrize("impl,datapath",
                             [("xla", "float32"), ("pallas-interpret", "int8")])
    def test_quarantine_rollback_bit_identity(self, impl, datapath):
        """Decode-pool drill: recorded steps -> checkpoint -> quarantine
        freezes the stream's whole session row bit-exactly while its
        neighbour keeps decoding -> rollback re-admits the checkpoint and
        the continuation tokens match the manual-control run bitwise."""
        model, params = _model(impl, datapath)
        vocab = model.cfg.vocab
        a = LMScheduler(model, params, slots=3, max_len=32,
                        health=HealthConfig())
        for u in ("keep", "other"):
            a.admit_prompt(u, _prompt(u, 6, vocab))
        for _ in range(3):
            a.step(record=True)
        assert a.health_checkpoint() == 2
        a.quarantine("keep")
        frozen = jax.tree.map(lambda x: _np(x).copy(), a.session_view("keep"))
        for _ in range(2):
            a.step(record=True)    # 'other' decodes on; 'keep' is frozen
        _trees_equal(frozen, a.session_view("keep"))
        report = a.rollback("keep")
        # the 2 frozen decode steps still ticked the host clock: they are
        # the wall-clock steps the session "lost" to the incident
        assert report["uid"] == "keep" and report["steps_lost"] == 2
        a_toks = [a.step(record=True)["keep"] for _ in range(5)]

        b = LMScheduler(model, params, slots=3, max_len=32)
        for u in ("keep", "other"):
            b.admit_prompt(u, _prompt(u, 6, vocab))
        for _ in range(3):
            b.step()
        b.evict("keep")
        b.admit_prompt("keep", _prompt("keep", 6, vocab))   # restore path
        b_toks = [b.step()["keep"] for _ in range(5)]

        assert a_toks == b_toks
        _trees_equal(a.session_view("keep"), b.session_view("keep"))


# ---------------------------------------------------------------------------
# 7. plumbing: HTTP metrics, anomaly presets, FT-runner registry
# ---------------------------------------------------------------------------

class TestServeMetricsHTTP:
    def test_endpoints(self):
        reg = MetricsRegistry()
        reg.counter("pool_admissions_total", "h").inc(3)
        srv = serve_metrics(reg, port=0)
        try:
            port = srv.server_address[1]
            base = f"http://127.0.0.1:{port}"
            with urllib.request.urlopen(f"{base}/metrics") as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/plain")
                assert b"pool_admissions_total 3" in r.read()
            with urllib.request.urlopen(f"{base}/metrics.json") as r:
                snap = json.loads(r.read())
            assert snap["pool_admissions_total"]["value"] == 3.0
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(f"{base}/bogus")
            assert e.value.code == 404
        finally:
            srv.shutdown()


class TestAnomalyPresets:
    def test_validation(self):
        with pytest.raises(ValueError, match="unknown anomaly"):
            AnomalyPreset("meteor_strike")
        assert ANOMALIES == {"drive_blowout", "dead_input", "stuck_input"}

    def test_deterministic_and_shaped(self):
        drive = np.linspace(-1, 1, 8).astype(np.float32)
        blow = AnomalyPreset("drive_blowout", gain=200.0)
        np.testing.assert_array_equal(inject_anomaly(blow, drive, 3),
                                      drive * np.float32(200.0))
        np.testing.assert_array_equal(
            inject_anomaly(AnomalyPreset("dead_input"), drive, 0),
            np.zeros(8, np.float32))
        stuck = AnomalyPreset("stuck_input")
        np.testing.assert_array_equal(inject_anomaly(stuck, drive, 0),
                                      inject_anomaly(stuck, drive, 17))
        noisy = AnomalyPreset("drive_blowout", gain=1.0, noise_std=0.1)
        a, b = (inject_anomaly(noisy, drive, t) for t in (4, 4))
        np.testing.assert_array_equal(a, b)
        assert np.any(inject_anomaly(noisy, drive, 5) != a)


class TestFTRunnerRegistry:
    def test_counters_reconcile_with_events(self, tmp_path):
        reg = MetricsRegistry()

        def step(state, batch):
            x = state["x"] + batch
            loss = jnp.where(jnp.asarray(int(batch) == 3), jnp.nan, x.sum())
            return {"x": x}, {"loss": loss}

        ckpt = CheckpointManager(str(tmp_path), keep=3)
        runner = FaultTolerantRunner(step, ckpt, save_every=2,
                                     max_rollbacks=3, registry=reg)
        state, hist = runner.run({"x": jnp.zeros(())},
                                 lambda s: jnp.asarray(float(s)), 6)
        snap = reg.snapshot()
        rollback_events = [e for e in runner.events
                           if e["kind"] == "rollback"]
        assert snap["ft_rollbacks_total"]["value"] == len(rollback_events) \
            == runner.rollbacks == 1
        assert snap["ft_step_seconds"]["count"] == len(hist)
        assert snap["ft_stragglers_total"]["value"] == len(
            [e for e in runner.events if e["kind"] == "straggler"])
        # a resume from the checkpoint counts once
        runner2 = FaultTolerantRunner(step, ckpt, registry=reg)
        _, start = runner2.restore_or_init({"x": jnp.zeros(())})
        assert start == 6
        assert reg.snapshot()["ft_resumes_total"]["value"] == 1.0
