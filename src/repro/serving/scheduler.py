"""Session-pytree slot pools: continuous batching into fixed-shape tensors.

The fleet tensor (PR 2) gives B per-request weight sets one fused launch per
layer; this module decides WHICH users occupy those B slots over time.  A
pool is ANY pytree of fixed-shape arrays in which each leaf either carries a
slot axis (one row per resident session) or is shared pool state (a clock).
Slots are never added or removed, so every jitted program (the pool step and
the gather/scatter swaps) compiles exactly once per shape and the compile
count is pinned (`compile_count()`; asserted by benchmarks/serving_churn.py
and benchmarks/serving_lm.py).

Two pools ride the same machinery:

  * `FleetScheduler` — the SNN controller fleet: a `NetworkState` of shape
    ``(B, N, M)`` stepped through the `engine.layer_step`/`engine.rollout`
    fleet path.
  * `serving.lm.LMScheduler` — the LM decode pool: KV/SSM caches
    ``(L, B, S, ...)``, per-slot sequence indices ``(B,)``, and the plastic
    adapter's ``W_fast (B, N, N)`` (float32 or int8), all one session
    pytree.

Mechanics per scheduling event (`SessionPool`):

  * ``admit(uid)``  — `SessionStore.checkout` (warm hit / durable restore /
    fresh state), then swap-in: one jitted per-leaf scatter along each
    leaf's slot axis, with the slot index TRACED so any slot reuses the
    same executable.
  * ``evict(uid)``  — swap-out (jitted per-leaf gather), a subclass
    finalize hook (e.g. stamping the session's step counter), and
    `SessionStore.checkin` (write-through persist); the vacated slot is
    scatter-cleared to zeros for hygiene.
  * stepping        — subclass-owned: ONE fused program over all B slots
    with the ``active (B,)`` mask gating vacant slots into true no-ops
    (state frozen bit-exactly, outputs zero/ignored).  Occupancy changes
    never retrace: the mask is a runtime operand, not a shape.

Because slot rows are mutually independent and the active mask freezes
state bit-exactly, a session's trajectory is invariant to WHICH slot it
occupies, to its neighbours, and to evict -> persist -> re-admit
round-trips — the bit-identity contract `tests/test_serving.py` and
`tests/test_serving_lm.py` pin on the xla and pallas-interpret backends.

SESSION HEALTH (opt-in via ``health=HealthConfig(...)``): pools carry a
device-side flight recorder + streaming detectors (`obs.recorder` /
`obs.health`) as a third static trace variant (``record=``, exactly like
``telemetry=``), and the base class turns the latched verdict into action:
`flagged_sessions` → `quarantine` (the slot joins the same runtime-mask
freeze vacant and lost slots use) → `rollback` (re-admit from the last
healthy `SessionStore` checkpoint — `health_checkpoint` rides the
`persist_resident` path) → bit-identical continuation.  `remediate()` runs
the whole loop, optionally dumping a flight-recorder incident bundle per
casualty first.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, snn
from repro.core.engine import NetworkState
from repro.obs import MetricsRegistry, phase
from repro.obs import recorder as _recorder
from repro.obs.health import HealthConfig
from repro.obs.watchdog import watchdog as _compile_watchdog
from repro.obs.telemetry import FleetTelemetry, record_fleet_telemetry
from repro.serving.sessions import SessionStore

# Axis sentinel: a pool leaf marked SHARED has no slot rows — it is pool-
# global state (e.g. the fleet clock `NetworkState.t`).  Swap-in carries it
# through untouched; swap-out returns zeros (the scheduler stamps the
# session's true host-side value in `_finalize_session`).
SHARED = "shared"


# ---- generic slot gather/scatter (any pytree of leading-slot-rank leaves) --

@functools.partial(jax.jit, donate_argnums=(0,))
def slot_put(pool, slot, user):
    """Scatter `user` (pytree of unbatched leaves) into `pool[slot]`."""
    return jax.tree.map(
        lambda p, u: p.at[slot].set(u.astype(p.dtype)), pool, user)


@jax.jit
def slot_take(pool, slot):
    """Gather slot `slot` of every pool leaf as an unbatched pytree."""
    return jax.tree.map(lambda p: p[slot], pool)


def _put_leaf(p, u, ax, slot):
    if ax == SHARED:
        return p
    idx = (slice(None),) * ax + (slot,)
    return p.at[idx].set(u.astype(p.dtype))


def _take_leaf(p, ax, slot):
    if ax == SHARED:
        return jnp.zeros_like(p)
    return jnp.take(p, slot, axis=ax)


def make_slot_ops(axes, shardings=None):
    """Jitted (put, take) for a pool whose per-leaf slot axes are `axes`.

    `axes` is a pytree matching the pool structure whose leaves are either
    an int (the axis carrying slot rows in that leaf) or `SHARED`.  The
    slot index is traced, so every slot reuses one executable per op.

    `shardings` (a NamedSharding pytree matching the pool, from
    `distributed.sharding.pool_shardings`) pins the scatter's OUTPUT layout
    on a meshed pool: without the constraint GSPMD is free to gather the
    donated pool onto one device and the slot -> device placement would
    silently dissolve on the first admission.
    """
    def put(pool, slot, user):
        out = jax.tree.map(
            lambda p, u, ax: _put_leaf(p, u, ax, slot), pool, user, axes)
        if shardings is not None:
            out = jax.tree.map(
                jax.lax.with_sharding_constraint, out, shardings)
        return out

    def take(pool, slot):
        return jax.tree.map(
            lambda p, ax: _take_leaf(p, ax, slot), pool, axes)

    return (jax.jit(put, donate_argnums=(0,)), jax.jit(take))


def uniform_axes(tree, axis=0):
    """Axes pytree assigning one slot `axis` to every leaf of `tree`."""
    return jax.tree.map(lambda _: axis, tree)


# ---- the generic pool ------------------------------------------------------


class SessionPool:
    """Admit/evict user sessions into a fixed-shape slot pool (base class).

    Subclasses provide the pool pytree + its slot-axes pytree and own the
    stepping programs; this base owns occupancy bookkeeping, LRU admission,
    the jitted traced-slot swaps, per-session step counters, and the
    `SessionStore` round-trip.

    Args:
      pool:  the pool pytree (must start ZEROED in its slot rows — the
             vacated-slot hygiene scatter reuses slot 0 of this initial
             pool as the zero template).
      axes:  pytree matching `pool`: per-leaf slot axis (int) or `SHARED`.
      slots: pool size B; fixes every pool tensor shape forever.
      store: `SessionStore` backing eviction/restore; a private in-RAM
             store is created if omitted.
      mesh:  optional `jax.sharding.Mesh` with a ``"data"`` axis (see
             `distributed.sharding.fleet_mesh`).  The pool pytree is placed
             with `NamedSharding` over its slot axes — device d owns the
             contiguous slot block ``[d*B/D, (d+1)*B/D)`` — and every slot
             op pins that layout, so admissions/evictions/steps run on a
             D-device fleet with the SAME executables-per-entry-point
             counts as the single-device pool (zero recompiles under
             churn).  ``slots`` must divide evenly by the device count.
      health: optional `obs.health.HealthConfig` enabling the session-
             health subsystem: subclasses gain ``record=True`` stepping
             (flight recorder + on-device detectors fused into the pool
             step), and this base gains `flagged_sessions` / `quarantine` /
             `rollback` / `remediate`.  Without it, recording raises and
             the pool is byte-for-byte the pre-health pool.
    """

    def __init__(self, pool, axes, slots: int,
                 store: Optional[SessionStore] = None,
                 registry: Optional[MetricsRegistry] = None,
                 mesh=None, health: Optional[HealthConfig] = None):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.slots = slots
        self.mesh = mesh
        self._shardings = None
        self.num_devices = 1
        if mesh is not None:
            if "data" not in mesh.axis_names:
                raise ValueError(
                    f"pool mesh needs a 'data' axis (the slot axis); got "
                    f"axes {mesh.axis_names} — build it with "
                    "distributed.sharding.fleet_mesh()")
            self.num_devices = int(mesh.shape["data"])
            if slots % self.num_devices != 0:
                raise ValueError(
                    f"slots={slots} must divide evenly over the "
                    f"{self.num_devices}-device 'data' axis (every device "
                    "owns the same number of slot rows; pad the pool or "
                    "shrink the mesh)")
            from repro.distributed import sharding as _sharding
            self._shardings = _sharding.pool_shardings(mesh, axes)
            pool = jax.device_put(pool, self._shardings)
        self.metrics = registry if registry is not None else MetricsRegistry()
        self.store = (store if store is not None
                      else SessionStore(registry=self.metrics))
        self.pool = pool
        self._axes = axes
        self._put, self._take = make_slot_ops(axes, self._shardings)
        # round-trip the zero template through host memory so it is an
        # UNCOMMITTED device array, exactly like an admitted payload
        # (store restores are numpy -> jnp.asarray): on a meshed pool a
        # committed gather output would key separate slot_put cache
        # entries for admission vs the vacated-slot hygiene scatter
        self._zero_session = jax.tree.map(
            lambda a: jnp.asarray(np.asarray(jax.device_get(a))),
            self._take(pool, jnp.int32(0)))
        # the pool-mode session template (abstract): what every admitted
        # payload must look like, passed to `SessionStore.checkout` so
        # admission never has to eval_shape the factory (a jitted prefill
        # factory would grow a trace-cache entry per admission otherwise)
        self._template = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self._zero_session)
        self.slot_user: list = [None] * slots        # slot -> uid | None
        self.user_slot: Dict[str, int] = {}          # uid -> slot
        self._steps = np.zeros(slots, np.int64)      # per-session step count
        self._admit_seq = np.zeros(slots, np.int64)  # admission order (LRU)
        self._seq = 0
        self.evictions = 0
        # fault tolerance: slots whose device shard is marked lost.  Lost
        # slots never admit, never count active, and refuse evict (their
        # rows are garbage) — `drain_failed` re-homes their sessions.
        self._lost_slots: set = set()
        self._poison_session = None                  # built on first failure
        # session health: quarantined slots are occupied-but-frozen (same
        # runtime-mask freeze as vacant/lost); the flight recorder state is
        # built lazily on the first record= step so a health-enabled pool
        # that never records allocates nothing
        self.health_cfg = health
        self._quarantined: set = set()
        self._rec = None                             # obs.recorder state
        self._rec_pos = 0                            # global ring cursor
        self._rec_shardings = None
        self.last_verdict = None                     # (B,) bool, last record

        def _rec_reset(rec, slot):
            out = _recorder.reset_slot(rec, slot)
            if self._rec_shardings is not None:
                out = jax.tree.map(
                    jax.lax.with_sharding_constraint, out,
                    self._rec_shardings)
            return out

        # traced slot index -> one executable clears any slot's history
        self._reset_rec = jax.jit(_rec_reset, donate_argnums=(0,))
        # compile_count sources, keyed by entry-point name so the compile
        # audit (`compiled_programs`) can name the program that drifted
        self._jitted: Dict[str, Any] = {
            "slot_put": self._put, "slot_take": self._take,
            "recorder_reset": self._reset_rec}
        self._m_admit = self.metrics.histogram(
            "pool_admit_seconds", "admit latency (checkout + swap-in)")
        self._m_evict = self.metrics.histogram(
            "pool_evict_seconds", "evict latency (swap-out + persist)")
        self._m_occupancy = self.metrics.gauge(
            "pool_occupancy", "admitted sessions / pool slots")
        self._m_admissions = self.metrics.counter(
            "pool_admissions_total", "sessions admitted")
        self._m_evictions = self.metrics.counter(
            "pool_evictions_total", "sessions evicted")
        self._m_failures = self.metrics.counter(
            "pool_device_failures_total", "device shards marked lost")
        self._m_drained = self.metrics.counter(
            "pool_drained_sessions_total",
            "sessions re-homed off a lost shard")
        self._m_drain = self.metrics.histogram(
            "pool_drain_seconds", "drain latency (restore + re-admit, per "
            "drain_failed call)")
        self._m_quarantined = self.metrics.counter(
            "pool_quarantined_total", "sessions quarantined as unhealthy")
        self._m_rollbacks = self.metrics.counter(
            "pool_rollbacks_total",
            "quarantined sessions rolled back to their last healthy "
            "checkpoint")
        self._m_health_ckpts = self.metrics.counter(
            "pool_health_checkpoints_total",
            "health_checkpoint() sweeps (rollback restore points)")

    # ---- occupancy -------------------------------------------------------

    @property
    def active_users(self) -> list:
        return [u for u in self.slot_user if u is not None]

    @property
    def free_slots(self) -> int:
        return sum(1 for s, u in enumerate(self.slot_user)
                   if u is None and s not in self._lost_slots)

    @property
    def lost_slots(self) -> frozenset:
        """Slots whose device shard has been marked lost."""
        return frozenset(self._lost_slots)

    def slot_device(self, slot: int) -> int:
        """Device index owning `slot` under the mesh placement (0 unmeshed).

        NamedSharding over the length-D ``"data"`` axis places contiguous
        blocks: device d owns slots ``[d*B/D, (d+1)*B/D)``."""
        return slot * self.num_devices // self.slots

    def device_slots(self, device: int) -> range:
        """The contiguous slot block owned by `device`."""
        per = self.slots // self.num_devices
        if not 0 <= device < self.num_devices:
            raise ValueError(f"device must be in [0, {self.num_devices}), "
                             f"got {device}")
        return range(device * per, (device + 1) * per)

    def _active_mask(self) -> jax.Array:
        # lost AND quarantined slots are masked out like vacant ones: a
        # stranded session is frozen until drain_failed re-homes it, an
        # unhealthy one until rollback restores it — the mask is a runtime
        # operand, so neither failure nor quarantine ever recompiles
        mask = np.zeros(self.slots, np.bool_)
        for s, u in enumerate(self.slot_user):
            mask[s] = (u is not None and s not in self._lost_slots
                       and s not in self._quarantined)
        return jnp.asarray(mask)

    def compiled_programs(self) -> Dict[str, int]:
        """Per-entry-point executable counts: {name: compiled programs}.

        EVERY jitted entry point the pool owns is audited here (the
        telemetry step variants included) — `tests/test_serving_lm.py`
        pins the exact expected dict per (layout x datapath), so adding a
        jitted program without registering it in ``_jitted`` fails the
        audit rather than silently escaping the no-recompile gates.
        """
        return {name: int(f._cache_size())
                for name, f in self._jitted.items()}

    def compile_count(self) -> int:
        """Total executables compiled by the pool's jitted programs."""
        return sum(self.compiled_programs().values())

    def pool_nbytes(self) -> int:
        """Resident bytes of the pool pytree (all leaves).

        The quantized-pool headline: int8 weight planes instead of float32
        mean the same HBM holds ~4x more resident sessions (weights
        dominate the session footprint).
        """
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.pool))

    # ---- session template hooks -----------------------------------------

    def _session_factory(self):
        """Fresh (zero) session for a brand-new user; subclasses may
        override with richer construction (e.g. an LM prefill)."""
        return jax.tree.map(jnp.zeros_like, self._zero_session)

    def _finalize_session(self, user, step: int):
        """Hook: adjust a just-gathered session before persisting it
        (e.g. stamp the host-side step counter into a SHARED leaf)."""
        return user

    # ---- admission / eviction -------------------------------------------

    def admit(self, uid: str, evict_lru: bool = False, factory=None) -> int:
        """Place `uid` into a free slot (restoring persisted state if any).

        Returns the slot index.  With ``evict_lru=True`` a full pool evicts
        its least-recently-admitted session to make room; otherwise a full
        pool raises RuntimeError.  `factory` overrides the fresh-session
        constructor for THIS admission (it is also the `SessionStore`
        validation template, so it must build the session pytree the pool
        expects).
        """
        if uid in self.user_slot:
            raise ValueError(f"session {uid!r} is already in slot "
                             f"{self.user_slot[uid]}")
        healthy = [s for s in range(self.slots) if s not in self._lost_slots]
        free = [s for s in healthy if self.slot_user[s] is None]
        if not free:
            # quarantined residents are not LRU-evictable: evicting one
            # would persist its diverged state over the healthy checkpoint
            candidates = [s for s in healthy if self.slot_user[s] is not None
                          and s not in self._quarantined]
            if not evict_lru or not candidates:
                lost = (f" ({len(self._lost_slots)} slots lost to device "
                        "failure)" if self._lost_slots else "")
                raise RuntimeError(
                    f"pool is full ({self.slots} slots{lost}); pass "
                    "evict_lru=True or evict a session first")
            lru = min(candidates, key=lambda s: self._admit_seq[s])
            self.evict(self.slot_user[lru])
            free = [lru]
        slot = free[0]
        with self._m_admit.time(), phase("pool.admit"):
            state, step = self.store.checkout(
                uid, self._session_factory if factory is None else factory,
                template=self._template)
            # normalize to device arrays: a store restore hands back HOST
            # (numpy) leaves, and numpy arguments key a SEPARATE jit cache
            # entry — without this, the first restore-admission after warm-up
            # would read as a recompile under the pinned-zero churn gate
            state = jax.tree.map(jnp.asarray, state)
            with phase("pool.swap_in"):
                self.pool = self._put(self.pool, jnp.int32(slot), state)
        self.slot_user[slot] = uid
        self.user_slot[uid] = slot
        self._steps[slot] = step
        self._admit_seq[slot] = self._seq
        self._seq += 1
        # the slot's flight-recorder history belongs to the PREVIOUS tenant;
        # clear it so detectors baseline on this session from step 0
        if self._rec is not None:
            self._rec = self._reset_rec(self._rec, jnp.int32(slot))
        self._m_admissions.inc()
        self._m_occupancy.set(len(self.user_slot) / self.slots)
        return slot

    def evict(self, uid: str) -> None:
        """Swap `uid` out, persist it durably, and clear its slot."""
        slot = self.user_slot.get(uid)
        if slot is None:
            raise KeyError(f"session {uid!r} is not in the pool")
        if slot in self._lost_slots:
            raise RuntimeError(
                f"session {uid!r} sits in lost slot {slot} (device "
                f"{self.slot_device(slot)}); its rows are gone — recover it "
                "with drain_failed(), which restores the last durable "
                "checkpoint, instead of evicting garbage")
        if slot in self._quarantined:
            raise RuntimeError(
                f"session {uid!r} in slot {slot} is quarantined as "
                "unhealthy; evicting would persist its diverged state over "
                "the last healthy checkpoint — recover it with rollback() "
                "or remediate() instead")
        self.user_slot.pop(uid)
        with self._m_evict.time(), phase("pool.evict"):
            with phase("pool.swap_out"):
                user = self._take(self.pool, jnp.int32(slot))
            user = self._finalize_session(user, int(self._steps[slot]))
            with phase("pool.persist"):
                self.store.checkin(uid, user, int(self._steps[slot]))
            self.slot_user[slot] = None
            # hygiene: scatter zeros over the vacated slot so no stale user
            # data lingers in the pool tensor (the mask already freezes it)
            with phase("pool.clear"):
                self.pool = self._put(self.pool, jnp.int32(slot),
                                      self._zero_session)
        self._steps[slot] = 0
        if self._rec is not None:
            self._rec = self._reset_rec(self._rec, jnp.int32(slot))
        self.evictions += 1
        self._m_evictions.inc()
        self._m_occupancy.set(len(self.user_slot) / self.slots)

    def advance_steps(self, k: int) -> None:
        """Advance every admitted session's host-side step counter by k."""
        for slot in self.user_slot.values():
            self._steps[slot] += k

    # ---- device-loss recovery (distributed/ft.py posture) ----------------

    def persist_resident(self) -> int:
        """Durably snapshot every resident session WITHOUT evicting it.

        The periodic drain-safety checkpoint: `drain_failed` recovers a
        lost shard's sessions from their last durable snapshot, so steps
        taken since it are the blast radius of a device loss.  Gathers each
        healthy resident session (lost slots are skipped — their rows are
        gone) and writes it through `SessionStore.persist`; the warm cache
        is untouched (resident uids are checked out, never warm).  Returns
        the number of sessions persisted.
        """
        n = 0
        for uid, slot in list(self.user_slot.items()):
            # quarantined rows are diverged state — persisting one would
            # clobber the very checkpoint rollback needs
            if slot in self._lost_slots or slot in self._quarantined:
                continue
            user = self._take(self.pool, jnp.int32(slot))
            user = self._finalize_session(user, int(self._steps[slot]))
            self.store.persist(uid, user, int(self._steps[slot]))
            n += 1
        return n

    def stranded_sessions(self) -> list:
        """Uids resident in lost slots, awaiting `drain_failed`."""
        return [u for u, s in self.user_slot.items()
                if s in self._lost_slots]

    def _poison(self):
        if self._poison_session is None:
            def leaf(z):
                if jnp.issubdtype(z.dtype, jnp.floating):
                    return jnp.full_like(z, jnp.nan)
                if jnp.issubdtype(z.dtype, jnp.integer):
                    return jnp.full_like(z, jnp.iinfo(z.dtype).max)
                return jnp.ones_like(z)
            self._poison_session = jax.tree.map(leaf, self._zero_session)
        return self._poison_session

    def fail_slots(self, slots, poison: bool = True) -> list:
        """Failure injection: mark `slots` lost; returns the stranded uids.

        With ``poison=True`` (the default) the rows are overwritten with
        sentinel garbage (NaN float planes, saturated integer planes) —
        recovery tests that pass with poison on PROVE the drain path reads
        only `SessionStore` checkpoints, never the dead shard, and that the
        active mask isolates the garbage from surviving slots' math.
        """
        slots = sorted(set(int(s) for s in slots))
        for s in slots:
            if not 0 <= s < self.slots:
                raise ValueError(f"slot {s} out of range [0, {self.slots})")
        self._lost_slots.update(slots)
        if poison:
            for s in slots:
                self.pool = self._put(self.pool, jnp.int32(s),
                                      self._poison())
        return self.stranded_sessions()

    def fail_device(self, device: int, poison: bool = True) -> list:
        """Mark one device's whole slot shard lost (see `fail_slots`).

        The injection hook the multi-device recovery tests and the drain-
        latency benchmark drive: everything device `device` owned — resident
        sessions included — is gone; follow with `drain_failed()` to re-home
        its sessions onto the surviving shards.
        """
        stranded = self.fail_slots(self.device_slots(device), poison=poison)
        self._m_failures.inc()
        return stranded

    def drain_failed(self, evict_lru: bool = False) -> list:
        """Re-home every stranded session onto surviving shards.

        For each uid resident in a lost slot: drop the dead occupancy (the
        shard is gone — nothing is gathered or persisted from it), then
        `admit` the uid normally, which restores its last durable snapshot
        from the `SessionStore`.  Admission only considers healthy slots,
        so the session lands on a SURVIVING device — and because a session's
        trajectory is slot- and neighbour-invariant (the pool contract),
        its continuation is bit-identical to an uninterrupted run from that
        snapshot.  Steps taken after the last `persist_resident`/evict are
        lost; each report row says how many.

        Returns a list of dicts: ``{uid, from_slot, to_slot, from_device,
        to_device, steps_lost}``.  With ``evict_lru=True`` a full pool
        evicts least-recently-admitted survivors to make room.
        """
        report = []
        with self._m_drain.time(), phase("pool.drain"):
            for uid in self.stranded_sessions():
                old_slot = self.user_slot.pop(uid)
                self.slot_user[old_slot] = None
                steps_at_fail = int(self._steps[old_slot])
                self._steps[old_slot] = 0
                # hygiene (simulation-only: a real dead device is not
                # writable, but the injected one is): clear the poison so
                # the checkpointed pool keeps the slots-are-zero-when-
                # vacant invariant
                self.pool = self._put(self.pool, jnp.int32(old_slot),
                                      self._zero_session)
                new_slot = self.admit(uid, evict_lru=evict_lru)
                self._m_drained.inc()
                report.append({
                    "uid": uid,
                    "from_slot": old_slot, "to_slot": new_slot,
                    "from_device": self.slot_device(old_slot),
                    "to_device": self.slot_device(new_slot),
                    "steps_lost": steps_at_fail - int(self._steps[new_slot]),
                })
        self._m_occupancy.set(len(self.user_slot) / self.slots)
        return report

    # ---- session health: detect -> quarantine -> rollback ----------------

    @property
    def quarantined_slots(self) -> frozenset:
        """Slots frozen by `quarantine` (occupied, masked out, awaiting
        rollback)."""
        return frozenset(self._quarantined)

    def _ensure_recorder(self):
        """Build the flight-recorder state on first use (meshed pools place
        it with the same contiguous slot-block `NamedSharding` as the pool
        itself, so the record-variant step needs no resharding)."""
        if self.health_cfg is None:
            raise ValueError(
                "this pool was built without health=HealthConfig(...); "
                "recording and remediation are unavailable")
        if self._rec is None:
            rec = _recorder.init_recorder(self.health_cfg, self.slots)
            if self.mesh is not None:
                from repro.distributed import sharding as _sharding
                self._rec_shardings = _sharding.pool_shardings(
                    self.mesh, jax.tree.map(lambda _: 0, rec))
                rec = jax.device_put(rec, self._rec_shardings)
            self._rec = rec
        return self._rec

    def health_checkpoint(self) -> int:
        """Durably snapshot every HEALTHY resident session — the restore
        point `rollback` recovers to.  Rides `persist_resident` (lost and
        quarantined slots are skipped), so the cadence/cost profile is the
        drain-safety checkpoint's; steps since the last call are the blast
        radius of an incident.  Returns the number persisted."""
        n = self.persist_resident()
        self._m_health_ckpts.inc()
        return n

    def flagged_sessions(self) -> list:
        """Uids whose latched device-side verdict is unhealthy (slot order).

        The one host read of the health loop: a single ``(B, D)`` bool
        gather, on demand — never per step.  Lost and already-quarantined
        slots are excluded (they are some OTHER remediation's business).
        """
        if self._rec is None:
            return []
        flags = np.asarray(
            jax.device_get(self._rec.health.flagged)).any(axis=-1)
        return [u for s, u in enumerate(self.slot_user)
                if u is not None and flags[s]
                and s not in self._lost_slots
                and s not in self._quarantined]

    def quarantine(self, uid: str) -> int:
        """Freeze `uid`'s slot via the runtime active mask (no recompiles,
        no data movement): its state stops evolving bit-exactly, exactly
        like a vacant slot's, until `rollback` re-homes it.  Returns the
        quarantined slot index."""
        slot = self.user_slot.get(uid)
        if slot is None:
            raise KeyError(f"session {uid!r} is not in the pool")
        if slot in self._lost_slots:
            raise RuntimeError(
                f"session {uid!r} sits in LOST slot {slot}; use "
                "drain_failed(), not quarantine")
        self._quarantined.add(slot)
        self._m_quarantined.inc()
        return slot

    def rollback(self, uid: str, evict_lru: bool = False) -> dict:
        """Re-admit a quarantined session from its last healthy checkpoint.

        Mirrors the device-loss drain, and deliberately shares its
        machinery: drop the diverged occupancy (nothing is gathered or
        persisted from it), zero the slot, clear its flight-recorder rows,
        then `admit(uid)` — which restores the last durable snapshot from
        the `SessionStore`, so the continuation is bit-identical to a
        manual evict-before-incident -> re-admit of the same checkpoint
        (the incident drill `tests/test_health.py` pins).  Steps since the
        last `health_checkpoint`/evict are lost; the report says how many.

        Returns ``{uid, from_slot, to_slot, steps_lost}``.
        """
        slot = self.user_slot.get(uid)
        if slot is None:
            raise KeyError(f"session {uid!r} is not in the pool")
        if slot not in self._quarantined:
            raise RuntimeError(
                f"session {uid!r} (slot {slot}) is not quarantined; "
                "rollback only recovers quarantined sessions — call "
                "quarantine(uid) first (or remediate(), which does both)")
        steps_at_flag = int(self._steps[slot])
        self.user_slot.pop(uid)
        self.slot_user[slot] = None
        self._steps[slot] = 0
        self.pool = self._put(self.pool, jnp.int32(slot),
                              self._zero_session)
        self._quarantined.discard(slot)
        if self._rec is not None:
            self._rec = self._reset_rec(self._rec, jnp.int32(slot))
        new_slot = self.admit(uid, evict_lru=evict_lru)
        self._m_rollbacks.inc()
        return {"uid": uid, "from_slot": slot, "to_slot": new_slot,
                "steps_lost": steps_at_flag - int(self._steps[new_slot])}

    def remediate(self, evict_lru: bool = False,
                  flight_dir: Optional[str] = None) -> list:
        """The automated health loop: quarantine every flagged session,
        optionally dump its flight-recorder incident bundle, and roll it
        back to the last healthy checkpoint.  Returns one `rollback`
        report per casualty (with an ``"incident"`` path when dumping).
        Safe to call at any cadence — flags latch on device, and a clean
        pool is a no-op."""
        reports = []
        for uid in self.flagged_sessions():
            slot = self.quarantine(uid)
            incident = None
            if flight_dir is not None:
                incident = _recorder.dump_incident(
                    flight_dir, uid=uid, slot=slot, rec=self._rec,
                    cfg=self.health_cfg, pos=self._rec_pos,
                    registry=self.metrics, watchdog=_compile_watchdog)
            report = self.rollback(uid, evict_lru=evict_lru)
            if incident is not None:
                report["incident"] = incident
            reports.append(report)
        return reports

    # ---- whole-pool checkpointing (elastic re-mesh) ----------------------

    def save_pool(self, directory: str) -> str:
        """Checkpoint the WHOLE pool — resident sessions in place — plus the
        occupancy bookkeeping, in the standard `checkpoint.manager` layout.

        Leaves are stored unsharded, so the checkpoint is topology-free: a
        pool saved at D devices restores at any D' via `load_pool` (the
        `distributed.ft.elastic_restore` path).  Stranded sessions must be
        drained first — their rows are garbage and checkpointing garbage as
        state would be silent corruption.
        """
        stranded = self.stranded_sessions()
        if stranded:
            raise RuntimeError(
                f"cannot checkpoint a pool with stranded sessions "
                f"{stranded}; run drain_failed() first")
        sick = [u for u, s in self.user_slot.items()
                if s in self._quarantined]
        if sick:
            # load_pool restarts with an empty quarantine set, which would
            # silently unfreeze diverged state as healthy
            raise RuntimeError(
                f"cannot checkpoint a pool with quarantined sessions "
                f"{sick}; run remediate() first")
        from repro.checkpoint.manager import save_checkpoint
        extra = {
            "slots": self.slots,
            "slot_user": list(self.slot_user),
            "steps": [int(s) for s in self._steps],
            "admit_seq": [int(s) for s in self._admit_seq],
            "seq": int(self._seq),
        }
        return save_checkpoint(directory, int(self._seq), self.pool,
                               extra=extra)

    def load_pool(self, directory: str, step: Optional[int] = None) -> None:
        """Resume a `save_pool` checkpoint INTO this pool, re-laid-out on
        this pool's mesh.

        The elastic re-mesh path: construct the scheduler at the NEW
        topology (any device count whose shard evenly divides ``slots``,
        including unmeshed) and load a checkpoint taken at the old one —
        leaves are stored unsharded, so restore is a pure device_put onto
        the new `NamedSharding`s (`distributed.ft.elastic_restore`).
        Occupancy, per-session step counters, and LRU order resume exactly;
        all slots come back healthy.
        """
        if self.mesh is not None:
            from repro.distributed import ft as _ft
            from repro.distributed import sharding as _sharding
            tree, _, extra = _ft.elastic_restore(
                directory, self.pool, self.mesh,
                lambda mesh: _sharding.pool_shardings(mesh, self._axes),
                step=step)
        else:
            from repro.checkpoint.manager import load_checkpoint
            tree, _, extra = load_checkpoint(directory, self.pool, step=step)
        if int(extra["slots"]) != self.slots:
            raise ValueError(
                f"checkpointed pool has {extra['slots']} slots; this pool "
                f"has {self.slots} (elastic restore re-meshes devices, not "
                "the slot count)")
        self.pool = tree
        self.slot_user = list(extra["slot_user"])
        self.user_slot = {u: s for s, u in enumerate(self.slot_user)
                          if u is not None}
        self._steps = np.asarray(extra["steps"], np.int64).copy()
        self._admit_seq = np.asarray(extra["admit_seq"], np.int64).copy()
        self._seq = int(extra["seq"])
        self._lost_slots = set()
        self._poison_session = None
        # recorder state is not checkpointed (detector baselines are cheap
        # to rebuild and meaningless across a re-mesh): restart clean
        self._quarantined = set()
        self._rec = None
        self._rec_pos = 0
        self.last_verdict = None
        self._m_occupancy.set(len(self.user_slot) / self.slots)


# ---- the SNN controller fleet ---------------------------------------------


def _network_axes(fleet: NetworkState) -> NetworkState:
    """Slot axes of a fleet NetworkState: every leaf carries slot rows on
    axis 0 except the shared pool clock `t`.  In a quantized pool the
    per-layer ``w_scale`` rows are slot state like everything else — a
    restored session brings its own scale into whatever slot it lands in
    (the int8 payload is meaningless without it)."""
    return NetworkState(
        w=tuple(0 for _ in fleet.w),
        v=tuple(0 for _ in fleet.v),
        trace=tuple(0 for _ in fleet.trace),
        t=SHARED,
        w_scale=tuple(0 for _ in fleet.w_scale))


class FleetScheduler(SessionPool):
    """Admit/evict user sessions into a fixed-shape controller slot pool.

    Args:
      cfg:    `snn.SNNConfig` of the controller (``cfg.impl`` picks the
              engine backend for the whole pool; ``cfg.quant`` — see
              `snn.quant_config` — makes it a QUANTIZED pool: int8 weight
              slots with per-slot scales, int32 membrane/trace slots,
              ~4x more resident sessions per byte, and per-session step
              counters driving the deterministic stochastic round so
              evict -> re-admit stays bit-identical).
      theta:  per-layer packed rule coefficients (shared by every session —
              the rule is the deployment, the weights are the user).
      slots:  pool size B; fixes the fleet tensor shape forever.
      store:  `SessionStore` backing eviction/restore; a private in-RAM
              store is created if omitted.
      mesh:   optional device mesh (see `SessionPool`): the fleet tensors
              shard over their slot axis and every step/rollout launch
              lowers under `engine.fleet_spmd` (shard_map) — each device
              runs the identical engine program on its B/D local slots, so
              the meshed pool is bit-identical to the unmeshed one on every
              backend and datapath (tests/test_distributed.py pins it).
    """

    def __init__(self, cfg: snn.SNNConfig, theta, slots: int,
                 store: Optional[SessionStore] = None,
                 registry: Optional[MetricsRegistry] = None,
                 mesh=None, health: Optional[HealthConfig] = None):
        self.cfg = cfg
        self.theta = theta
        fleet = snn.init_state(cfg, batch=slots, fleet=True)
        super().__init__(fleet, _network_axes(fleet), slots, store, registry,
                         mesh=mesh, health=health)

        def _pool_step(fleet, drive, active, teach, seeds):
            # `seeds` are the PER-SESSION step counters (host bookkeeping
            # scattered to device each step): in a quantized pool they
            # drive the deterministic stochastic round, so a session's
            # update stream follows the session across evictions and slot
            # changes — never the shared pool clock.  Float pools ignore
            # them (same jitted signature either way).
            return snn.timestep(cfg, fleet, theta, drive, teach=teach,
                                active=active, seed=seeds)

        def _pool_rollout(fleet, window, active, teach, seeds):
            # K fused pool timesteps in ONE engine launch (the rollout
            # megakernel): same per-session seed semantics as _pool_step —
            # step k of the window draws from seeds + k, exactly the
            # sequence K single steps would draw.
            return snn.rollout_window(cfg, fleet, theta, window, teach=teach,
                                      active=active, seed=seeds)

        def _pool_step_tel(fleet, drive, active, teach, seeds):
            # the telemetry trace VARIANT of _pool_step: `telemetry` is a
            # static flag, so this is a second stable program per entry
            # point (compiled once, never per step), not a runtime branch
            return snn.timestep(cfg, fleet, theta, drive, teach=teach,
                                active=active, seed=seeds, telemetry=True)

        def _pool_rollout_tel(fleet, window, active, teach, seeds):
            return snn.rollout_window(cfg, fleet, theta, window, teach=teach,
                                      active=active, seed=seeds,
                                      telemetry=True)

        quant = cfg.quant is not None
        hcfg = health

        def _record(ns, res_tail, rec, pos, active):
            # shared tail of the record trace VARIANTS: telemetry channels
            # + weight norm -> flight-recorder ring + streaming detectors,
            # all fused into the same program (no extra launch, no host
            # sync — the verdict stays on device until the host asks)
            tel = res_tail[-1]
            wnorm = _recorder.network_weight_norm(ns, quant)
            ch = jnp.stack([tel.spike_rate, tel.mean_abs_dw, tel.sat_frac,
                            wnorm], axis=-1)
            rec2, verdict = _recorder.recorder_update(hcfg, rec, ch, pos,
                                                      active)
            return rec2, verdict

        def _pool_step_rec(fleet, drive, active, teach, seeds, rec, pos):
            res = snn.timestep(cfg, fleet, theta, drive, teach=teach,
                               active=active, seed=seeds, telemetry=True)
            rec2, verdict = _record(res[0], res, rec, pos, active)
            return res + (rec2, verdict)

        def _pool_rollout_rec(fleet, window, active, teach, seeds, rec, pos):
            res = snn.rollout_window(cfg, fleet, theta, window, teach=teach,
                                     active=active, seed=seeds,
                                     telemetry=True)
            rec2, verdict = _record(res[0], res, rec, pos, active)
            return res + (rec2, verdict)

        def _meshed(core, *, window: bool, tel: bool):
            # Lower `core` under shard_map over the slot axis
            # (`engine.fleet_spmd`): the NetworkState is flattened into its
            # slot-mapped fields; the pool clock `t` rides in REPLICATED
            # (shard_map with check_vma=False cannot return an unmapped
            # output, and Pallas carries no replication rule) and advances
            # OUTSIDE the mapped region — bit-exactly what the unmeshed
            # step computes, since `t` only feeds the t+k bump here (the
            # quant rounding streams draw from the per-session seeds).
            def body(w, v, tr, scl, t, x, active, teach, seeds):
                st = NetworkState(w=w, v=v, trace=tr, t=t, w_scale=scl)
                res = core(st, x, active, teach, seeds)
                ns = res[0]
                return (ns.w, ns.v, ns.trace, ns.w_scale) + tuple(res[1:])

            x_ax = 1 if window else 0          # (K, B, n) windows vs (B, n)
            mapped = engine.fleet_spmd(
                body, mesh,
                in_axes=(0, 0, 0, 0, None, x_ax, 0, 0, 0),
                out_axes=(0, 0, 0, 0, x_ax) + ((0,) if tel else ()))

            def run(fleet, x, active, teach, seeds):
                out = mapped(fleet.w, fleet.v, fleet.trace, fleet.w_scale,
                             fleet.t, x, active, teach, seeds)
                k = x.shape[0] if window else 1
                ns = NetworkState(w=out[0], v=out[1], trace=out[2],
                                  t=fleet.t + k, w_scale=out[3])
                return (ns,) + tuple(out[4:])

            return run

        def _meshed_rec(core, *, window: bool):
            # the record variants mesh like the telemetry ones: every
            # RecorderState leaf is slot-major (axis 0), so the whole rec
            # pytree rides one mapped arg; the ring cursor `pos` is
            # replicated like the clock (all slots record in lockstep)
            def body(w, v, tr, scl, t, x, active, teach, seeds, rec, pos):
                st = NetworkState(w=w, v=v, trace=tr, t=t, w_scale=scl)
                res = core(st, x, active, teach, seeds, rec, pos)
                ns = res[0]
                return (ns.w, ns.v, ns.trace, ns.w_scale) + tuple(res[1:])

            x_ax = 1 if window else 0
            mapped = engine.fleet_spmd(
                body, mesh,
                in_axes=(0, 0, 0, 0, None, x_ax, 0, 0, 0, 0, None),
                out_axes=(0, 0, 0, 0, x_ax, 0, 0, 0))

            def run(fleet, x, active, teach, seeds, rec, pos):
                out = mapped(fleet.w, fleet.v, fleet.trace, fleet.w_scale,
                             fleet.t, x, active, teach, seeds, rec, pos)
                k = x.shape[0] if window else 1
                ns = NetworkState(w=out[0], v=out[1], trace=out[2],
                                  t=fleet.t + k, w_scale=out[3])
                return (ns,) + tuple(out[4:])

            return run

        if mesh is not None:
            _pool_step = _meshed(_pool_step, window=False, tel=False)
            _pool_rollout = _meshed(_pool_rollout, window=True, tel=False)
            _pool_step_tel = _meshed(_pool_step_tel, window=False, tel=True)
            _pool_rollout_tel = _meshed(_pool_rollout_tel, window=True,
                                        tel=True)
            _pool_step_rec = _meshed_rec(_pool_step_rec, window=False)
            _pool_rollout_rec = _meshed_rec(_pool_rollout_rec, window=True)

        # Fixed shapes everywhere => each of these traces exactly once per
        # signature; `compiled_programs()` exposes the per-entry-point
        # executable counts the churn benchmark and compile audit pin.
        # The telemetry variants are registered up-front: an untraced jit
        # reports _cache_size() == 0, so a telemetry-off run still audits
        # them (as zeros) without compiling anything extra.
        self._step = jax.jit(_pool_step)
        self._rollout = jax.jit(_pool_rollout)
        self._step_tel = jax.jit(_pool_step_tel)
        self._rollout_tel = jax.jit(_pool_rollout_tel)
        # NOTE: the recorder buffer is NOT donated even though the caller's
        # copy is dead after every record step — on backends without
        # donation support (CPU) an unusable donation forces defensive
        # copies that cost more than the recorder itself (~+10% per call
        # at B=256, measured by benchmarks/obs_health.py)
        self._step_rec = jax.jit(_pool_step_rec)
        self._rollout_rec = jax.jit(_pool_rollout_rec)
        self._jitted.update({
            "pool_step": self._step,
            "pool_rollout": self._rollout,
            "pool_step_telemetry": self._step_tel,
            "pool_rollout_telemetry": self._rollout_tel,
            "pool_step_record": self._step_rec,
            "pool_rollout_record": self._rollout_rec,
        })

    # the historical attribute name: the pool pytree IS the fleet state
    @property
    def fleet(self) -> NetworkState:
        return self.pool

    @fleet.setter
    def fleet(self, value: NetworkState) -> None:
        self.pool = value

    def _session_factory(self):
        return snn.init_state(self.cfg)

    def _finalize_session(self, user: NetworkState, step: int) -> NetworkState:
        # the generic swap-out zeroes the SHARED pool clock; stamp the
        # session's true host-side step count before it is persisted
        return dataclasses.replace(
            user, t=jnp.asarray(step, jnp.int32))

    # ---- stepping --------------------------------------------------------

    def _gather_rows(self, drives: Mapping[str, jax.Array],
                     teach: Optional[Mapping[str, jax.Array]]
                     ) -> tuple[jax.Array, Optional[jax.Array]]:
        """Validate uid coverage and pack per-session rows into slot order."""
        missing = [u for u in self.user_slot if u not in drives]
        extra = [u for u in drives if u not in self.user_slot]
        if missing or extra:
            raise ValueError(
                f"drives must cover exactly the admitted sessions; missing "
                f"{missing}, not admitted {extra}")
        n_in = self.cfg.layer_sizes[0]
        drive = np.zeros((self.slots, n_in), np.float32)
        for uid, row in drives.items():
            drive[self.user_slot[uid]] = np.asarray(row, np.float32)
        tarr = None
        if teach is not None:
            ghosts = [u for u in teach if u not in self.user_slot]
            if ghosts:
                raise ValueError(
                    f"teach signals for sessions not in the pool: {ghosts}")
            m_out = self.cfg.layer_sizes[-1]
            tarr = np.zeros((self.slots, m_out), np.float32)
            for uid, row in teach.items():
                tarr[self.user_slot[uid]] = np.asarray(row, np.float32)
            tarr = jnp.asarray(tarr)
        return jnp.asarray(drive), tarr

    def _unpack(self, outs: jax.Array, k: int,
                slot_axis: int) -> Dict[str, jax.Array]:
        """Advance the step counters by `k` and hand each admitted session
        its slice of `outs` along `slot_axis` (the span ``pool.unpack``)."""
        lead = (slice(None),) * slot_axis
        with phase("pool.unpack"):
            self.advance_steps(k)
            return {uid: outs[lead + (slot,)]
                    for uid, slot in self.user_slot.items()}

    def step(self, drives: Mapping[str, jax.Array],
             teach: Optional[Mapping[str, jax.Array]] = None,
             telemetry: bool = False, record: bool = False):
        """One fused SNN timestep for the WHOLE pool.

        `drives` maps uid -> input drive ``(obs_dim,)`` (already encoded;
        the pool is deterministic, matching ``encoding="current"``).  Every
        admitted session must receive a drive.  Vacant slots get zero drive
        and are frozen by the active mask.  Returns uid -> readout row.

        ``telemetry=True`` dispatches the telemetry trace variant instead
        (one extra stable program, compiled on first use) and returns
        ``(outputs, FleetTelemetry)``; fleet-level summary gauges are
        recorded into ``self.metrics``.

        ``record=True`` (needs ``health=HealthConfig(...)``) dispatches the
        RECORD trace variant: the same telemetry channels plus the weight
        norm feed the flight-recorder ring and the streaming detectors
        inside the one program — still no host sync per step; the latched
        verdict waits on device for `flagged_sessions`/`remediate`.  Pass
        ``telemetry=True`` too to ALSO get the host-side tuple return and
        summary gauges (same single program either way).
        """
        with phase("pool.pack"):
            drive, tarr = self._gather_rows(drives, teach)
        if record:
            rec = self._ensure_recorder()
            with phase("pool.step"):
                res = self._step_rec(
                    self.fleet, drive, self._active_mask(), tarr,
                    jnp.asarray(self._steps.astype(np.int32)),
                    rec, jnp.int32(self._rec_pos))
            self.fleet, out = res[0], res[1]
            self._rec, self.last_verdict = res[3], res[4]
            self._rec_pos += 1
        else:
            fn = self._step_tel if telemetry else self._step
            with phase("pool.step"):
                res = fn(self.fleet, drive, self._active_mask(), tarr,
                         jnp.asarray(self._steps.astype(np.int32)))
            self.fleet, out = res[0], res[1]
        outputs = self._unpack(out, 1, slot_axis=0)
        if not telemetry:
            return outputs
        tel: FleetTelemetry = res[2]
        record_fleet_telemetry(self.metrics, tel)
        return outputs, tel

    def pool_step(self, drives: Mapping[str, jax.Array],
                  timesteps: Optional[int] = None,
                  teach: Optional[Mapping[str, jax.Array]] = None,
                  telemetry: bool = False, record: bool = False):
        """K fused SNN timesteps for the WHOLE pool in ONE engine launch.

        The time-fused form of calling `step` K times on held drives: the
        whole (K timesteps x layers x slots) window runs as a single
        `engine.rollout` launch (one `pallas_call` on the Pallas backends),
        with per-session step counters seeding each step of the window
        exactly as K single steps would.  ``timesteps`` defaults to
        ``cfg.timesteps``; occupancy is frozen across the window
        (admissions/evictions happen between windows, which is already the
        scheduler's contract — they are host-side events).

        Returns uid -> (K, act_dim) readout WINDOW (callers reduce:
        `control_step` takes the mean).

        ``telemetry=True`` dispatches the telemetry trace variant (one
        extra stable program) and returns ``(outputs, FleetTelemetry)``
        with window-averaged per-slot rates, recording fleet summary
        gauges into ``self.metrics``.

        ``record=True`` (needs ``health=HealthConfig(...)``) dispatches the
        record trace variant: the window's (averaged) telemetry channels
        write ONE flight-recorder row and one detector update per call —
        a recorded window is one observation, matching the per-step path's
        cadence in recorded samples per launch.
        """
        k = self.cfg.timesteps if timesteps is None else int(timesteps)
        if k < 1:
            raise ValueError(f"pool_step needs timesteps >= 1, got {k}")
        n_in = self.cfg.layer_sizes[0]
        with phase("pool.pack"):
            drive, tarr = self._gather_rows(drives, teach)
            window = jnp.broadcast_to(drive[None], (k, self.slots, n_in))
        if record:
            rec = self._ensure_recorder()
            with phase("pool.rollout"):
                res = self._rollout_rec(
                    self.fleet, window, self._active_mask(), tarr,
                    jnp.asarray(self._steps.astype(np.int32)),
                    rec, jnp.int32(self._rec_pos))
            self.fleet, outs = res[0], res[1]
            self._rec, self.last_verdict = res[3], res[4]
            self._rec_pos += 1
        else:
            fn = self._rollout_tel if telemetry else self._rollout
            with phase("pool.rollout"):
                res = fn(self.fleet, window, self._active_mask(), tarr,
                         jnp.asarray(self._steps.astype(np.int32)))
            self.fleet, outs = res[0], res[1]
        outputs = self._unpack(outs, k, slot_axis=1)
        if not telemetry:
            return outputs
        tel: FleetTelemetry = res[2]
        record_fleet_telemetry(self.metrics, tel)
        return outputs, tel

    def control_step(self, obs: Mapping[str, jax.Array]
                     ) -> Dict[str, jax.Array]:
        """One CONTROL step = ``cfg.timesteps`` pool timesteps on held
        observations (mirrors `snn.controller_step`: mean readout over the
        window, tanh-squashed unless the readout spikes).  The window runs
        as ONE fused `pool_step` launch instead of ``timesteps`` separate
        pool steps."""
        outs = self.pool_step(obs)
        actions = {}
        for uid, window in outs.items():
            a = window.mean(axis=0)
            actions[uid] = a if self.cfg.spiking_readout else jnp.tanh(a)
        return actions
