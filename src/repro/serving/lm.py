"""LM decode pool: continuous batching of plastic language-model streams.

The LM counterpart of `scheduler.FleetScheduler`: a fixed pool of B decode
slots whose session pytree is the WHOLE per-stream decode state —

  * the backbone cache (KV planes / Mamba2 SSM + conv states / zsuper's
    stacked hybrid caches, any `models.factory` layout),
  * a per-slot sequence index (streams admitted at different times sit at
    different lengths),
  * the FireFly-P plastic adapter state: ``W_fast (N, N)`` float32 or int8
    (``cfg.adapter_quant``) with its per-session scale and step counter,
  * the pending next token.

Everything rides the generic `SessionPool` machinery: admission is ONE
traced-slot scatter of a freshly-prefilled (or store-restored) session,
eviction is one gather + write-through `SessionStore` persist, and the pool
decodes as ONE jitted program over all B slots per token (`step`) or per
K-token window (`decode_window` — the windowed path routes the adapter
through `plastic.decode_rollout`, so K plasticity steps for every resident
stream are a single time-fused engine launch).  Occupancy is a runtime
``active (B,)`` operand: churn never retraces, vacant slots are bit-exact
no-ops (the MoE dispatch sentinels their garbage tokens out of expert
capacity, the adapter freezes its synapses, the cache index holds).

`benchmarks/serving_lm.py` pins the contracts: zero recompiles under
churn, and evict -> persist -> re-admit bit-identity mid-generation, per
layout x backend x datapath cell.
"""
from __future__ import annotations

from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import factory, plastic
from repro.models.config import ModelConfig
from repro.models.layers import init_from_plan
from repro.obs import MetricsRegistry, phase
from repro.obs import recorder as _recorder
from repro.obs.health import HealthConfig
from repro.obs.telemetry import (FleetTelemetry, adapter_telemetry,
                                 record_fleet_telemetry)
from repro.serving.scheduler import SessionPool, uniform_axes
from repro.serving.sessions import SessionStore


class LMScheduler(SessionPool):
    """Admit/evict LM user streams into a fixed pool of decode slots.

    Args:
      model:   a `factory.Model` (or anything `factory.build` accepts — a
               ModelConfig or an arch id).  ``cfg.adapter_impl`` picks the
               plastic engine backend for the whole pool;
               ``cfg.adapter_quant`` makes the adapter rows an int8 pool.
      params:  model parameters (shared by every stream — the model is the
               deployment, the session is the user).
      slots:   pool size B; fixes every pool tensor shape forever.
      max_len: cache length ceiling shared by all slots.
      store:   `SessionStore` backing eviction/restore.
      mesh:    optional device mesh (see `SessionPool`): the decode pool —
               KV/SSM planes, adapter rows, sequence indices — shards over
               its slot axes and the decode launches run as sharding-
               constrained jit (GSPMD), NOT shard_map: the MoE capacity/
               cumsum stages reduce ACROSS slots, and GSPMD partitions them
               without changing their semantics, where a manual per-shard
               lowering would.  Only the plastic adapter's engine launch
               runs per shard (`engine.fleet_spmd`): its slot rows are
               independent, and GSPMD cannot partition a Pallas kernel.
               The partitioned program is not the unmeshed one, so bf16
               logits may differ by roundings and a greedy token flips
               where the top two logits are that close; at smoke widths
               the token streams agree (tests/test_distributed.py).
    """

    def __init__(self, model, params, slots: int, max_len: int,
                 store: Optional[SessionStore] = None,
                 registry: Optional[MetricsRegistry] = None,
                 mesh=None, health: Optional[HealthConfig] = None):
        if not isinstance(model, factory.Model):
            model = factory.build(model)
        if model.cfg.input_mode != "tokens":
            raise ValueError(
                f"{model.cfg.name}: LMScheduler pools token streams; "
                f"input_mode {model.cfg.input_mode!r} is not poolable")
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.max_len = int(max_len)
        pool = {"cache": model.pool_cache(slots, max_len),
                "tok": jnp.zeros((slots,), jnp.int32)}
        axes = {"cache": model.cache_axes(max_len), "tok": 0}
        super().__init__(pool, axes, slots, store, registry, mesh=mesh,
                         health=health)

        # pin the decode outputs' pool layout (GSPMD would otherwise be
        # free to re-layout the updated cache away from the slot sharding)
        shardings = self._shardings

        def _constrain(new_pool):
            if shardings is None:
                return new_pool
            return jax.tree.map(jax.lax.with_sharding_constraint,
                                new_pool, shardings)

        def _prefill_session(params, prompt):
            # B=1 prompt -> one session row + its first greedy token
            logits, cache = model.prefill(params, prompt[None, :], max_len)
            return {"cache": model.session_from_prefill(cache),
                    "tok": jnp.argmax(logits[0], -1).astype(jnp.int32)}

        def _pool_step(params, pool, active):
            # one greedy decode token for the WHOLE pool; vacant slots are
            # no-ops end to end (cache index held, adapter frozen, pending
            # token carried through)
            logits, cache = model.decode_step(
                params, pool["cache"], pool["tok"][:, None], active=active,
                slot_mesh=mesh)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (_constrain({"cache": cache,
                                "tok": jnp.where(active, nxt, pool["tok"])}),
                    nxt)

        def _pool_window(params, pool, tokens, active):
            # K teacher-forced tokens for the whole pool in ONE launch: the
            # backbone scans token-by-token, the adapter runs K plasticity
            # steps as a single time-fused plastic.decode_rollout
            logits, cache = model.decode_rollout(
                params, pool["cache"], tokens, active=active, slot_mesh=mesh)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            return (_constrain({"cache": cache,
                                "tok": jnp.where(active, nxt, pool["tok"])}),
                    logits)

        qcfg = plastic.QUANT if self.cfg.adapter_quant else None

        def _pool_step_tel(params, pool, active):
            # telemetry trace VARIANT: the adapter's decode step is buried
            # inside the backbone's jitted program, so the per-slot health
            # vector is recovered as a pure function of the adapter cache
            # before/after — traced into the SAME launch, no extra pass
            before = pool["cache"]["adapter"]
            new_pool, nxt = _pool_step(params, pool, active)
            tel = adapter_telemetry(before, new_pool["cache"]["adapter"],
                                    active, qcfg=qcfg)
            return new_pool, nxt, tel

        def _pool_window_tel(params, pool, tokens, active):
            before = pool["cache"]["adapter"]
            new_pool, logits = _pool_window(params, pool, tokens, active)
            # window-mean telemetry: the K-step cache delta normalized by
            # the window length (net weight motion, recovered event mass)
            tel = adapter_telemetry(before, new_pool["cache"]["adapter"],
                                    active, qcfg=qcfg)
            k = tokens.shape[1]
            tel = FleetTelemetry(
                spike_rate=tel.spike_rate / k,
                mean_abs_dw=tel.mean_abs_dw / k,
                sat_frac=tel.sat_frac, occupancy=tel.occupancy)
            return new_pool, logits, tel

        hcfg = health
        adapter_quant = bool(self.cfg.adapter_quant)

        def _record(tel, adapter, rec, pos, active):
            # record trace VARIANTS: telemetry channels + adapter weight
            # norm -> flight-recorder ring + streaming detectors, fused
            # into the decode launch (no host sync; the verdict latches
            # on device until flagged_sessions/remediate reads it)
            wnorm = _recorder.adapter_weight_norm(adapter, adapter_quant)
            ch = jnp.stack([tel.spike_rate, tel.mean_abs_dw, tel.sat_frac,
                            wnorm], axis=-1)
            return _recorder.recorder_update(hcfg, rec, ch, pos, active)

        def _pool_step_rec(params, pool, active, rec, pos):
            new_pool, nxt, tel = _pool_step_tel(params, pool, active)
            rec2, verdict = _record(tel, new_pool["cache"]["adapter"],
                                    rec, pos, active)
            return new_pool, nxt, tel, rec2, verdict

        def _pool_window_rec(params, pool, tokens, active, rec, pos):
            new_pool, logits, tel = _pool_window_tel(params, pool, tokens,
                                                     active)
            rec2, verdict = _record(tel, new_pool["cache"]["adapter"],
                                    rec, pos, active)
            return new_pool, logits, tel, rec2, verdict

        # Fixed shapes => one executable per op (per window length for the
        # windowed path); `compiled_programs()` names the per-entry-point
        # totals the churn benchmark and compile audit pin.  Telemetry
        # variants register up-front (untraced => 0 executables) so a
        # telemetry-off run audits them without compiling anything.
        self._prefill = jax.jit(_prefill_session)
        self._step_fn = jax.jit(_pool_step)
        self._window_fn = jax.jit(_pool_window)
        self._step_tel_fn = jax.jit(_pool_step_tel)
        self._window_tel_fn = jax.jit(_pool_window_tel)
        self._step_rec_fn = jax.jit(_pool_step_rec)
        self._window_rec_fn = jax.jit(_pool_window_rec)
        self._jitted.update({
            "prefill": self._prefill,
            "decode_step": self._step_fn,
            "decode_window": self._window_fn,
            "decode_step_telemetry": self._step_tel_fn,
            "decode_window_telemetry": self._window_tel_fn,
            "decode_step_record": self._step_rec_fn,
            "decode_window_record": self._window_rec_fn,
        })

    # ---- session construction --------------------------------------------

    def _session_factory(self):
        # slot 0 of the INITIAL pool, not zeros_like of it: quantized
        # adapter rows carry a non-zero fresh ``w_scale``
        return self._zero_session

    def admit_prompt(self, uid: str, prompt, evict_lru: bool = False) -> int:
        """Prefill `prompt` ((S,) int32) into a fresh session and admit it.

        For a uid the `SessionStore` already knows, the persisted session
        (its cache, adapter memory, and pending token) is restored instead
        and the prompt is ignored — resumption, not re-prefill.  Returns
        the slot index; the stream's first greedy token is `pending(uid)`.
        """
        prompt = jnp.asarray(prompt, jnp.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be (S,), got {prompt.shape}")
        return self.admit(
            uid, evict_lru=evict_lru,
            factory=lambda: self._prefill(self.params, prompt))

    # ---- inspection -------------------------------------------------------

    def pending(self, uid: str) -> int:
        """The stream's next token (greedy argmax of its last logits)."""
        return int(self.pool["tok"][self.user_slot[uid]])

    def session_view(self, uid: str):
        """Gather `uid`'s session pytree WITHOUT evicting (probe/tests)."""
        return self._take(self.pool, jnp.int32(self.user_slot[uid]))

    # ---- stepping ---------------------------------------------------------

    def _require_adapter(self) -> None:
        if not self.cfg.plastic_adapter:
            raise ValueError(
                f"{self.cfg.name}: telemetry reads the plastic adapter "
                "cache; this model has cfg.plastic_adapter=False")

    def step(self, telemetry: bool = False, record: bool = False):
        """One greedy decode token for every admitted stream (one launch).

        Each stream consumes its pending token and produces the next;
        returns uid -> newly generated token (which is also the new
        pending token).

        ``telemetry=True`` (plastic-adapter models only) dispatches the
        telemetry trace variant — the adapter's per-slot health vector is
        recovered from its cache delta inside the same launch — and
        returns ``(tokens, FleetTelemetry)``, recording summary gauges
        into ``self.metrics`` under the ``adapter_`` prefix.

        ``record=True`` (plastic-adapter models with
        ``health=HealthConfig(...)``) dispatches the record trace variant:
        the same channels plus the adapter weight norm feed the flight
        recorder and the streaming detectors inside the decode launch — no
        host sync; combine with ``telemetry=True`` for the tuple return.
        """
        if record:
            self._require_adapter()
            rec = self._ensure_recorder()
            with phase("lm.decode_step"):
                self.pool, nxt, tel, self._rec, self.last_verdict = \
                    self._step_rec_fn(self.params, self.pool,
                                      self._active_mask(), rec,
                                      jnp.int32(self._rec_pos))
            self._rec_pos += 1
        elif telemetry:
            self._require_adapter()
            with phase("lm.decode_step"):
                self.pool, nxt, tel = self._step_tel_fn(
                    self.params, self.pool, self._active_mask())
        else:
            with phase("lm.decode_step"):
                self.pool, nxt = self._step_fn(self.params, self.pool,
                                               self._active_mask())
        self.advance_steps(1)
        nxt = np.asarray(nxt)
        toks = {uid: int(nxt[slot]) for uid, slot in self.user_slot.items()}
        if not telemetry:
            return toks
        record_fleet_telemetry(self.metrics, tel, prefix="adapter")
        return toks, tel

    def decode_window(self, windows: Mapping[str, jax.Array],
                      telemetry: bool = False, record: bool = False):
        """K teacher-forced tokens per stream, ONE fused launch per window.

        `windows` maps uid -> ``(K,)`` int32 (same K for every stream —
        one executable per window length), covering exactly the admitted
        sessions; ``windows[uid][0]`` is typically the stream's pending
        token (then draft/forced continuations).  Equivalent to K `step`
        calls on those tokens — same cache writes, same K adapter
        plasticity steps (run as one `plastic.decode_rollout` launch), same
        stochastic-round stream in quant mode — and bit-identical to them
        (`tests/test_serving_lm.py` pins it).  Returns uid -> ``(K, V)``
        logits; the new pending token is the last position's argmax.

        ``telemetry=True`` (plastic-adapter models only) returns
        ``(logits, FleetTelemetry)`` with window-normalized adapter health
        (net weight motion / recovered event mass over the K steps),
        recording ``adapter_*`` gauges into ``self.metrics``.

        ``record=True`` (with ``health=HealthConfig(...)``) records the
        window's normalized channels as ONE flight-recorder observation
        and one detector update inside the same launch.
        """
        missing = [u for u in self.user_slot if u not in windows]
        extra = [u for u in windows if u not in self.user_slot]
        if missing or extra:
            raise ValueError(
                f"windows must cover exactly the admitted sessions; "
                f"missing {missing}, not admitted {extra}")
        ks = {int(np.asarray(w).shape[0]) for w in windows.values()}
        if len(ks) > 1:
            raise ValueError(f"all windows must share one length, got {ks}")
        k = ks.pop() if ks else 1
        tokens = np.zeros((self.slots, k), np.int32)
        for uid, w in windows.items():
            tokens[self.user_slot[uid]] = np.asarray(w, np.int32)
        if record:
            self._require_adapter()
            rec = self._ensure_recorder()
            with phase("lm.decode_window"):
                self.pool, logits, tel, self._rec, self.last_verdict = \
                    self._window_rec_fn(self.params, self.pool,
                                        jnp.asarray(tokens),
                                        self._active_mask(), rec,
                                        jnp.int32(self._rec_pos))
            self._rec_pos += 1
        elif telemetry:
            self._require_adapter()
            with phase("lm.decode_window"):
                self.pool, logits, tel = self._window_tel_fn(
                    self.params, self.pool, jnp.asarray(tokens),
                    self._active_mask())
        else:
            with phase("lm.decode_window"):
                self.pool, logits = self._window_fn(
                    self.params, self.pool, jnp.asarray(tokens),
                    self._active_mask())
        self.advance_steps(k)
        out = {uid: logits[slot] for uid, slot in self.user_slot.items()}
        if not telemetry:
            return out
        record_fleet_telemetry(self.metrics, tel, prefix="adapter")
        return out, tel


class AdapterPool(SessionPool):
    """Adapter-state-only pool: the batch rows of `launch/serve.py`.

    The classic batched-serving driver decodes a fixed batch in lockstep
    (one shared scalar cache index), so only the plastic adapter rows —
    each user's learned ``W_fast`` + membranes/traces/step counter (+ scale
    when ``cfg.adapter_quant``) — are session state.  This pool IS the
    ``cache["adapter"]`` pytree: admit users before `generate`, install
    `pool.pool` as the cache's adapter entry, and evict afterwards to
    persist what each stream learned.
    """

    def __init__(self, cfg: ModelConfig, slots: int,
                 store: Optional[SessionStore] = None,
                 registry: Optional[MetricsRegistry] = None,
                 mesh=None, health: Optional[HealthConfig] = None):
        if not cfg.plastic_adapter:
            raise ValueError(f"{cfg.name}: AdapterPool needs "
                             "cfg.plastic_adapter=True")
        self.cfg = cfg
        pool = init_from_plan(plastic.plan_cache(cfg, slots),
                              jax.random.PRNGKey(0))
        super().__init__(pool, uniform_axes(pool), slots, store, registry,
                         mesh=mesh, health=health)

    def _session_factory(self):
        # fresh sessions keep plan inits (quant rows: non-zero w_scale)
        return self._zero_session
