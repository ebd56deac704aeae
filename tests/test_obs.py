"""Observability pins: metrics registry, fleet telemetry, recompile watchdog.

The contracts this file locks down (see src/repro/obs/ and DESIGN.md):

  1. TELEMETRY IS FREE WHEN OFF — `telemetry=False` engine/rollout results
     are bitwise identical to `telemetry=True`'s state/outputs on xla AND
     pallas-interpret, float32 AND int8: the flag is a static trace
     variant, never a runtime branch inside the program.
  2. TELEMETRY IS HONEST WHEN ON — the per-slot health vector matches an
     independent numpy oracle computed from the step's own inputs/outputs
     (spike rate, net |dw|, membrane saturation), and VACANT slots report
     exact zeros in every field (no stale-state leakage).
  3. The metrics registry exports a stable JSON snapshot schema and valid
     Prometheus text exposition; typed get-or-create never aliases kinds.
  4. The schedulers' `compiled_programs()` audit names every jitted entry
     point, telemetry variants included, with untraced variants at 0.
  5. The recompile watchdog counts every backend compile, flags compiles
     as violations ONLY while armed, and captures the offending program's
     name.
  6. SessionStore's legacy counter attributes (warm_hits/restores/creates/
     persists) are live views of the obs counters — one source of truth.
  7. The scheduler's host spans, read back from a recorded profiler trace:
     each `FleetScheduler` call opens ``pool.pack``, its dispatch span and
     ``pool.unpack`` once, in that order, whatever the number of sessions;
     ``pool.swap_out``, ``pool.persist`` and ``pool.clear`` lie inside
     ``pool.evict``; and tracing compiles no program.
"""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, snn
from repro.kernels.plasticity import quant as Q
from repro.obs import (FleetTelemetry, HealthConfig, MetricsRegistry,
                       SAT_FRACTION, adapter_telemetry, record_fleet_telemetry,
                       watchdog as watch)
from repro.serving import FleetScheduler, SessionStore

IMPLS = ["xla", "pallas-interpret"]
DATAPATHS = ["float32", "int8"]

B, SIZES, K = 4, (6, 10, 3), 5
VACANT = 2                       # slot held inactive in the fleet fixtures
TEL_FIELDS = ("spike_rate", "mean_abs_dw", "sat_frac", "occupancy")


def _np(x):
    return np.asarray(jax.device_get(x))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("admissions_total", "h")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        assert reg.counter("admissions_total") is c  # get-or-create

    def test_gauge_set_add(self):
        g = MetricsRegistry().gauge("occupancy")
        g.set(0.5)
        g.add(0.25)
        assert g.value == 0.75

    def test_histogram_buckets_and_percentiles(self):
        h = MetricsRegistry().histogram("lat", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.05, 0.5, 5.0):
            h.observe(v)
        assert h.count == 5 and h.sum == pytest.approx(5.605)
        assert h.mean == pytest.approx(5.605 / 5)
        assert h.percentile(50) == 0.05
        snap = h.snapshot()
        # cumulative le-buckets: 0.005 | +2x0.05 | +0.5 (the 5.0 overflows)
        assert snap["buckets"] == {"0.01": 1, "0.1": 3, "1": 4}
        assert snap["p50"] == 0.05

    def test_histogram_time_context(self):
        reg = MetricsRegistry()
        with reg.timer("block_seconds"):
            pass
        h = reg.histogram("block_seconds")
        assert h.count == 1 and h.sum >= 0.0

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x_total")
        with pytest.raises(TypeError):
            reg.gauge("x_total")

    def test_snapshot_schema_and_to_json(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("a_total").inc()
        reg.gauge("b").set(2)
        reg.histogram("c").observe(0.5)
        snap = reg.snapshot()
        assert snap["a_total"] == {"type": "counter", "value": 1.0}
        assert snap["b"] == {"type": "gauge", "value": 2.0}
        assert snap["c"]["type"] == "histogram" and snap["c"]["count"] == 1
        path = tmp_path / "m.json"
        reg.to_json(str(path))
        assert json.loads(path.read_text()) == json.loads(json.dumps(snap))

    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("req_total", "requests").inc(3)
        reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.05)
        text = reg.prometheus_text()
        assert "# TYPE req_total counter" in text
        assert "req_total 3" in text
        assert '# TYPE lat histogram' in text
        assert 'lat_bucket{le="0.1"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_count 1" in text


class TestRecordFleetTelemetry:
    def test_active_weighted_means(self):
        # 4 slots, slot 2 vacant (mandated zeros): gauges must average
        # over ACTIVE slots only, occupancy over ALL slots
        reg = MetricsRegistry()
        tel = FleetTelemetry(
            spike_rate=jnp.array([0.2, 0.4, 0.0, 0.6], jnp.float32),
            mean_abs_dw=jnp.array([1e-3, 2e-3, 0.0, 3e-3], jnp.float32),
            sat_frac=jnp.array([0.1, 0.2, 0.0, 0.3], jnp.float32),
            occupancy=jnp.array([1.0, 1.0, 0.0, 1.0], jnp.float32))
        vals = record_fleet_telemetry(reg, tel)
        assert vals["fleet_spike_rate"] == pytest.approx(0.4)
        assert vals["fleet_mean_abs_dw"] == pytest.approx(2e-3)
        assert vals["fleet_sat_frac"] == pytest.approx(0.2, abs=1e-7)
        assert vals["fleet_occupancy"] == pytest.approx(0.75)
        assert reg.gauge("fleet_spike_rate").value == pytest.approx(0.4)

    def test_empty_fleet_is_zero(self):
        reg = MetricsRegistry()
        vals = record_fleet_telemetry(reg, FleetTelemetry.zeros(3),
                                      prefix="adapter")
        assert vals == {"adapter_spike_rate": 0.0,
                        "adapter_mean_abs_dw": 0.0,
                        "adapter_sat_frac": 0.0,
                        "adapter_occupancy": 0.0}


# ---------------------------------------------------------------------------
# engine telemetry: static-variant identity + numpy oracle + vacant zeros
# ---------------------------------------------------------------------------

def _fleet_fixture(datapath: str):
    quant = datapath == "int8"
    cfg = snn.SNNConfig(layer_sizes=SIZES, timesteps=K, plastic=True,
                        encoding="current",
                        trace_decay=0.75 if quant else 0.8,
                        quant=Q.QuantConfig() if quant else None)
    state = snn.init_state(cfg, batch=B, fleet=True)
    theta = snn.init_theta(cfg, jax.random.PRNGKey(1), scale=0.05)
    drives = jax.random.normal(jax.random.PRNGKey(2), (K, B, SIZES[0])) * 2.5
    active = jnp.array([1.0, 1.0, 0.0, 1.0])
    assert float(active[VACANT]) == 0.0
    return cfg, state, theta, drives, active


def _run_rollout(datapath, impl, telemetry):
    cfg, state, theta, drives, active = _fleet_fixture(datapath)
    qc = cfg.quant
    d = Q.to_fixed(drives, qc) if qc is not None else drives
    params = [cfg.engine_params(i) for i in range(cfg.num_layers)]
    return state, engine.rollout(state, list(theta), d, params=params,
                                 impl=impl, active=active,
                                 telemetry=telemetry)


class TestTelemetryStaticVariant:
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("datapath", DATAPATHS)
    def test_off_path_bitwise_identical(self, impl, datapath):
        """telemetry=True must not perturb the computation: state and
        outputs are BITWISE equal to the telemetry=False run."""
        _, off = _run_rollout(datapath, impl, telemetry=False)
        _, on = _run_rollout(datapath, impl, telemetry=True)
        assert len(off) == 2 and len(on) == 3
        for a, b in zip(jax.tree.leaves((off[0], off[1])),
                        jax.tree.leaves((on[0], on[1]))):
            np.testing.assert_array_equal(_np(a), _np(b))

    @pytest.mark.parametrize("datapath", DATAPATHS)
    def test_backend_parity_and_vacant_zeros(self, datapath):
        """xla and pallas-interpret agree on every telemetry field, and the
        vacant slot reports exact zeros on both."""
        _, tx = _run_rollout(datapath, "xla", telemetry=True)
        _, tp = _run_rollout(datapath, "pallas-interpret", telemetry=True)
        for f in TEL_FIELDS:
            ax, ap = _np(getattr(tx[2], f)), _np(getattr(tp[2], f))
            assert ax.shape == (B,) and ax.dtype == np.float32
            np.testing.assert_allclose(ax, ap, atol=2e-4, err_msg=f)
            assert ax[VACANT] == 0.0 and ap[VACANT] == 0.0
        # the fixture drives hard enough that active slots actually spike —
        # an all-zero parity pass would prove nothing
        assert _np(tx[2].spike_rate)[0] > 0.0
        np.testing.assert_array_equal(_np(tx[2].occupancy),
                                      [1.0, 1.0, 0.0, 1.0])

    def test_layer_step_matches_numpy_oracle(self):
        """One float fleet layer step on the oracle backend: telemetry
        re-derived in numpy from the step's own inputs/outputs."""
        cfg, state, theta, drives, active = _fleet_fixture("float32")
        layer = engine.LayerState(
            w=state.w[0], v=state.v[0], trace_pre=state.trace[0],
            trace_post=state.trace[1], theta=theta[0], w_scale=None)
        p = cfg.engine_params(0)
        new, out, tel = engine.layer_step(layer, drives[0], params=p,
                                          impl="xla", active=active,
                                          telemetry=True)
        spikes, v, w0, w1 = _np(out), _np(new.v), _np(layer.w), _np(new.w)
        act, m = _np(active), SIZES[1]
        np.testing.assert_allclose(
            _np(tel.spike_rate),
            np.abs(spikes).sum(1) / m * act, atol=1e-6)
        np.testing.assert_allclose(
            _np(tel.mean_abs_dw),
            np.abs(w1 - w0).sum((1, 2)) / (SIZES[0] * m) * act, atol=1e-6)
        np.testing.assert_allclose(
            _np(tel.sat_frac),
            (np.abs(v) >= SAT_FRACTION * p.v_th).sum(1) / m * act,
            atol=1e-6)
        np.testing.assert_array_equal(_np(tel.occupancy), act)

    @pytest.mark.parametrize("datapath", DATAPATHS)
    def test_rollout_dw_is_net_window_motion(self, datapath):
        """Windowed mean_abs_dw is the NET weight motion over the window,
        sum_i |w_end - w_start| / (N_i*M_i), / (K * n_plastic) — checked
        in numpy against the rollout's own weight endpoints."""
        state, (new_state, _, tel) = _run_rollout(datapath, "xla",
                                                  telemetry=True)
        qc = Q.QuantConfig() if datapath == "int8" else None
        plast = [0, 1]               # both layers plastic in the fixture
        dw = np.zeros(B)
        for i in plast:
            a, b = _np(state.w[i]), _np(new_state.w[i])
            d = np.abs(b.astype(np.int64) - a.astype(np.int64)) \
                if qc is not None else np.abs(b - a)
            per_slot = d.sum((1, 2)).astype(np.float64)
            if qc is not None:
                per_slot = per_slot * _np(state.w_scale[i]).reshape(-1)
            dw += per_slot / (a.shape[-2] * a.shape[-1])
        dw /= K * len(plast)
        dw[VACANT] = 0.0
        np.testing.assert_allclose(_np(tel.mean_abs_dw), dw, atol=2e-6)


class TestAdapterTelemetry:
    def _caches(self, b=3, n=4, decay=0.8):
        rng = np.random.default_rng(0)
        tr2 = rng.uniform(0.1, 0.9, (b, n)).astype(np.float32)
        s2 = (rng.random((b, n)) < 0.5).astype(np.float32)  # this step's events
        w0 = rng.standard_normal((b, n, n)).astype(np.float32)
        dw = rng.standard_normal((b, n, n)).astype(np.float32) * 1e-3
        before = {"tr2": jnp.asarray(tr2), "w_fast": jnp.asarray(w0),
                  "v2": jnp.zeros((b, n), jnp.float32)}
        after = {"tr2": jnp.asarray(decay * tr2 + s2),
                 "w_fast": jnp.asarray(w0 + dw),
                 "v2": jnp.asarray(
                     np.array([[0.95, 0.1, -0.92, 0.0]] * b, np.float32))}
        return before, after, s2, dw

    def test_exact_event_recovery(self):
        """tr2' = decay*tr2 + s2  =>  the recovered event vector equals s2
        exactly, |dw| comes off the w_fast delta, sat off v2."""
        before, after, s2, dw = self._caches()
        tel = adapter_telemetry(before, after, jnp.ones(3))
        np.testing.assert_allclose(_np(tel.spike_rate),
                                   np.abs(s2).mean(1), atol=1e-6)
        np.testing.assert_allclose(_np(tel.mean_abs_dw),
                                   np.abs(dw).sum((1, 2)) / 16, atol=1e-7)
        # v2 rows are [0.95, 0.1, -0.92, 0.0]: two of four >= 0.9*v_th
        np.testing.assert_allclose(_np(tel.sat_frac), [0.5] * 3)

    def test_inactive_slots_report_zeros(self):
        """Gating by `active` kills the phantom (1-decay)*tr2 event a
        frozen slot's unchanged trace would otherwise 'recover'."""
        before, _, _, _ = self._caches()
        frozen = {k: v for k, v in before.items()}   # no step happened
        tel = adapter_telemetry(before, frozen, jnp.array([1.0, 0.0, 0.0]))
        for f in TEL_FIELDS:
            arr = _np(getattr(tel, f))
            assert arr[1] == 0.0 and arr[2] == 0.0, f
        # ...and the active slot DOES see the phantom — proof the gate, not
        # the math, is what protects vacant slots
        assert _np(tel.spike_rate)[0] > 0.0


# ---------------------------------------------------------------------------
# scheduler integration: compile audit + recorded gauges
# ---------------------------------------------------------------------------

def _sched(impl="xla", slots=3, health=None):
    cfg = snn.SNNConfig(layer_sizes=(8, 12, 4), timesteps=3, plastic=True,
                        encoding="current", impl=impl)
    theta = snn.init_theta(cfg, jax.random.PRNGKey(0), scale=0.05)
    return FleetScheduler(cfg, theta, slots=slots, health=health)


class TestSchedulerObs:
    def test_compiled_programs_audit(self):
        """Every jitted entry point is named; telemetry variants register
        up-front at 0 executables and grow to exactly 1 when used."""
        sched = _sched()
        progs = sched.compiled_programs()
        assert set(progs) == {"slot_put", "slot_take", "recorder_reset",
                              "pool_step", "pool_rollout",
                              "pool_step_telemetry",
                              "pool_rollout_telemetry",
                              "pool_step_record", "pool_rollout_record"}
        assert progs["pool_step_telemetry"] == 0
        sched.admit("u0")
        drives = {"u0": np.ones(8, np.float32)}
        sched.step(drives)
        sched.step(drives, telemetry=True)
        sched.step(drives, telemetry=True)      # cached, must not grow
        progs = sched.compiled_programs()
        assert progs["pool_step"] == 1
        assert progs["pool_step_telemetry"] == 1
        assert sched.compile_count() == sum(progs.values())

    def test_step_telemetry_records_gauges(self):
        sched = _sched(slots=4)
        for u in ("u0", "u1"):
            sched.admit(u)
        drives = {u: np.ones(8, np.float32) * 2.0
                  for u in sched.active_users}
        outs, tel = sched.step(drives, telemetry=True)
        assert set(outs) == {"u0", "u1"}
        assert _np(tel.occupancy).tolist() == [1.0, 1.0, 0.0, 0.0]
        snap = sched.metrics.snapshot()
        assert snap["fleet_occupancy"]["value"] == pytest.approx(0.5)
        for name in ("fleet_spike_rate", "fleet_mean_abs_dw",
                     "fleet_sat_frac"):
            assert name in snap
        # off-path step returns the plain dict (no tuple)
        assert set(sched.step(drives)) == {"u0", "u1"}

    def test_pool_lifecycle_counters(self):
        sched = _sched()
        sched.admit("a")
        sched.admit("b")
        sched.evict("a")
        snap = sched.metrics.snapshot()
        assert snap["pool_admissions_total"]["value"] == 2
        assert snap["pool_evictions_total"]["value"] == 1
        assert snap["pool_occupancy"]["value"] == pytest.approx(1 / 3)
        assert snap["pool_admit_seconds"]["count"] == 2


def _traced_spans(trace_dir, fn):
    """Run `fn` under the profiler; return its ``pool.*`` host spans as
    (start_ns, end_ns, name), by start."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name == "/host:CPU"
                  for line in plane.lines for e in line.events
                  if e.name.startswith("pool."))


class TestSchedulerSpans:
    @pytest.mark.parametrize("method,dispatch",
                             [("pool_step", "pool.rollout"),
                              ("step", "pool.step")])
    # telemetry off, telemetry on, and the flight-recorder variant
    @pytest.mark.parametrize("telemetry", [False, True, "record"])
    def test_pack_dispatch_unpack_once_per_call_in_order(
            self, tmp_path, method, dispatch, telemetry):
        """Traced calls open three spans each, whatever the pool's size,
        and give bitwise the outputs of untraced calls on a twin pool,
        with the same programs compiled."""
        record = telemetry == "record"
        health = HealthConfig(z_threshold=1e9, bounds=((-1e9, 1e9),) * 4,
                              dead_floor=-1.0, hysteresis=(9999,) * 4)
        traced, plain = (_sched(slots=4, health=health if record else None)
                         for _ in range(2))
        for sched in (traced, plain):
            for u in ("u0", "u1", "u2"):
                sched.admit(u)
        drives = {u: np.full(8, 1.5, np.float32) for u in ("u0", "u1", "u2")}
        kw = {"record": True} if record else {"telemetry": telemetry}
        calls = 3

        def run(sched):
            call = getattr(sched, method)
            outs = [call(drives, **kw) for _ in range(calls)]
            return [o[0] if telemetry is True else o for o in outs]

        want = run(plain)
        got = []
        spans = _traced_spans(tmp_path, lambda: got.extend(run(traced)))
        assert [n for _, _, n in spans] == \
            ["pool.pack", dispatch, "pool.unpack"] * calls
        for (_, end, _), (start, _, _) in zip(spans, spans[1:]):
            assert end <= start
        for g, w in zip(got, want):
            assert sorted(g) == sorted(w) == ["u0", "u1", "u2"]
            for u in g:
                np.testing.assert_array_equal(_np(g[u]), _np(w[u]))
        assert traced.compiled_programs() == plain.compiled_programs()

    def test_evict_children_lie_inside_evict(self, tmp_path):
        sched = _sched(slots=3)
        for u in ("a", "b", "c"):
            sched.admit(u)
        sched.evict("c")                                # compiles
        progs = sched.compiled_programs()
        spans = _traced_spans(tmp_path, lambda: sched.evict("a"))
        assert [n for _, _, n in spans] == \
            ["pool.evict", "pool.swap_out", "pool.persist", "pool.clear"]
        (lo, hi, _), *children = spans
        for (start, end, _), nxt in zip(children, children[1:] + [None]):
            assert lo <= start <= end <= hi
            if nxt is not None:
                assert end <= nxt[0]
        assert sched.compiled_programs() == progs
        assert sched.store.known("a")


class TestSessionStoreMetrics:
    def test_counters_are_the_source_of_truth(self, tmp_path):
        """warm_hits/restores/creates/persists read through to the obs
        counters, and reconcile with the admission/eviction event log."""
        store = SessionStore(root=str(tmp_path), capacity=1)
        sched = _sched()
        sched2 = FleetScheduler(sched.cfg, sched.theta, slots=3, store=store)
        sched2.admit("u0")          # create
        sched2.admit("u1")          # create
        sched2.evict("u0")          # persist (capacity-1 cache keeps u0)
        sched2.evict("u1")          # persist (evicts u0 from warm cache)
        sched2.admit("u0")          # fell out of warm cache -> disk restore
        sched2.admit("u1")          # warm hit
        assert (store.creates, store.persists) == (2, 2)
        assert (store.restores, store.warm_hits) == (1, 1)
        snap = store.metrics.snapshot()
        assert snap["session_store_creates_total"]["value"] == 2
        assert snap["session_store_persists_total"]["value"] == 2
        assert snap["session_store_restores_total"]["value"] == 1
        assert snap["session_store_warm_hits_total"]["value"] == 1
        checkouts = sum(snap[f"session_store_{k}_total"]["value"]
                        for k in ("warm_hits", "restores", "creates"))
        assert checkouts == 4       # == admissions
        assert snap["session_store_checkout_seconds"]["count"] == 4
        assert snap["session_store_persist_seconds"]["count"] == 2


# ---------------------------------------------------------------------------
# recompile watchdog
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_armed_compile_is_a_violation_with_name(self):
        reg = MetricsRegistry()
        w = watch.install(reg)
        assert watch.install(reg) is w      # idempotent singleton
        x = jnp.ones(7)                      # constants compiled UNARMED
        w.reset()
        jax.jit(lambda a: a * 2.0 + 1.0)(x)  # unarmed: counted, no flag
        assert w.compiles >= 1 and w.violations == 0
        base = w.compiles
        with w.armed():
            assert w.is_armed
            jax.jit(lambda a: a * 3.0 - 2.0)(x)
        assert not w.is_armed
        assert w.compiles > base
        assert w.violations >= 1
        assert any("lambda" in s for s in w.violation_signatures)
        snap = reg.snapshot()
        assert snap["recompiles_after_warmup_total"]["value"] \
            == w.violations
        w.reset()
        assert (w.compiles, w.violations, w.violation_signatures) \
            == (0, 0, [])

    def test_cached_executions_never_fire(self):
        w = watch.install()
        f = jax.jit(lambda a: a + 1)
        x = jnp.ones(5)
        f(x)                                 # compile unarmed
        w.reset()
        with w.armed():
            for _ in range(3):
                f(x)                         # cache hits
        assert w.violations == 0


# ---------------------------------------------------------------------------
# LM adapter telemetry (the cache-delta route)
# ---------------------------------------------------------------------------

class TestLMAdapterTelemetry:
    @pytest.mark.parametrize("datapath", DATAPATHS)
    def test_step_and_window_telemetry(self, datapath):
        from repro.models import factory
        from repro.serving import LMScheduler

        cfg = factory.build("qwen3-4b", smoke=True).cfg.with_(
            plastic_adapter=True, adapter_neurons=8, adapter_impl="xla",
            adapter_quant=(datapath == "int8"))
        model = factory.build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        params["adapter"]["scale"] = jnp.float32(0.5)
        sched = LMScheduler(model, params, slots=3, max_len=16)
        rng = np.random.RandomState(0)
        sched.admit_prompt("u", rng.randint(0, cfg.vocab, 5).astype(np.int32))

        toks, tel = sched.step(telemetry=True)
        assert set(toks) == {"u"}
        for f in TEL_FIELDS:
            arr = _np(getattr(tel, f))
            assert arr.shape == (3,) and arr.dtype == np.float32
            assert arr[1] == 0.0 and arr[2] == 0.0, f"{f}: vacant leaked"
        np.testing.assert_array_equal(_np(tel.occupancy), [1.0, 0.0, 0.0])
        snap = sched.metrics.snapshot()
        assert snap["adapter_occupancy"]["value"] == pytest.approx(1 / 3)
        assert "adapter_spike_rate" in snap

        win = np.full((2,), sched.pending("u"), np.int32)
        out, wtel = sched.decode_window({"u": win}, telemetry=True)
        assert out["u"].shape == (2, cfg.vocab)
        assert _np(wtel.occupancy)[0] == 1.0
        # telemetry audit entries exist even for the unused variants
        progs = sched.compiled_programs()
        assert progs["decode_step_telemetry"] == 1
        assert progs["decode_window_telemetry"] == 1

    def test_telemetry_requires_plastic_adapter(self):
        from repro.models import factory
        from repro.serving import LMScheduler

        cfg = factory.build("qwen3-4b", smoke=True).cfg
        assert not cfg.plastic_adapter
        model = factory.build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sched = LMScheduler(model, params, slots=2, max_len=16)
        sched.admit_prompt("u", np.arange(4, dtype=np.int32))
        with pytest.raises(ValueError, match="plastic_adapter"):
            sched.step(telemetry=True)
