"""Plan-driven query engine over a :class:`~repro.query.index.KNNIndex`.

The engine is host bookkeeping around ONE serving abstraction: a
:class:`~repro.query.plan.DescentPlan` — the cross-product of placement
(single device | N LPT cluster shards), batching (closed waves |
continuous slots), and scorer (jnp | fused Pallas hop). Every request
takes the same path: ``submit → plan.step → collect``. The plan owns
the device state and compiled programs for its combination; this module
owns the queue, completion records, serving stats, and online mutation
(insertion + cohort refresh).

:class:`QueryConfig` is the flag-pile-compatible front door (CLI flags
map straight onto it); :meth:`QueryConfig.spec` maps it onto the
validated :class:`~repro.query.plan.PlanSpec` the plan is built from —
unsupported values fail loudly there instead of silently dropping a
flag.

Online insertion: :meth:`QueryEngine.insert` searches for the new
profile's neighbors *through the engine's own plan* (the sharded
placement repairs its per-shard tensors incrementally per version bump
— ``ShardedDescent.sync`` — so a sharded engine no longer needs the
full-index device copy inserts used to route through), appends the
fingerprint + forward edges to the index (O(degree) into spare
capacity), patches reverse edges, and registers the user with the FRH
router. Inserted profiles accumulate in a *cohort*; once it exceeds
``QueryConfig.refresh_every`` the engine re-runs C² clustering on the
cohort (:meth:`KNNIndex.refresh_cohort`) so drifting insert streams
grow fresh routable clusters.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import numpy as np

from repro.eval.metrics import knn_recall
from repro.lifecycle import LifecycleConfig, LifecycleManager
from repro.query.index import KNNIndex
from repro.query.plan import DescentPlan, PlanSpec
from repro.query.rebalance import RebalanceConfig, Rebalancer
from repro.query.router import (fingerprint_profiles, placements,
                                profiles_to_csr)
from repro.query.search import exact_knn


@dataclasses.dataclass
class QueryRequest:
    rid: int
    profile: np.ndarray                  # int32[|P|] item ids
    hops: Optional[int] = None           # per-request hop budget
                                         # (None → QueryConfig.hops)
    priority: int = 0                    # SLO class (0 = highest; higher
                                         # classes are shed first)
    deadline: Optional[float] = None     # absolute perf_counter() expiry
                                         # (None = never; expired pending
                                         # requests are shed, not served)
    # Filled by the engine:
    ids: Optional[np.ndarray] = None     # int32[k] neighbor ids
    sims: Optional[np.ndarray] = None    # float32[k] similarities
    t_submit: float = 0.0
    t_done: float = 0.0
    status: str = "pending"              # pending | done | rejected
    degraded: bool = False               # served while >=1 shard was
                                         # masked out (bounded recall
                                         # loss; never cached)

    @property
    def rejected(self) -> bool:
        """True when admission shed this request (deadline expired or
        bounded-queue overflow) — it completed WITHOUT a result."""
        return self.status == "rejected"

    @property
    def latency(self) -> Optional[float]:
        """Seconds from submit to completion, or None while unserved.

        An unserved request has ``t_done == 0.0``; the old behavior of
        returning ``0.0 - t_submit`` silently poisoned any percentile
        computed over a mixed done/pending list with large negative
        values. None makes that misuse fail loudly instead.
        """
        if self.t_done == 0.0 or self.t_submit == 0.0:
            return None
        return self.t_done - self.t_submit


@dataclasses.dataclass(frozen=True)
class QueryConfig:
    k: int = 10                # neighbors returned per query
    beam: int = 32             # descent frontier width
    hops: int = 3              # descent depth (fixed, compiled in)
    max_wave: int = 256        # queries per jitted wave
    seeds_per_config: int = 16 # routed seed candidates per hash config
    shards: int = 1            # >1: LPT cluster shards + cross-shard merge
    shard_oversample: float = 1.5  # fleet frontier vs single-device beam
    refresh_every: int = 64    # cohort size triggering re-clustering
    continuous: bool = False   # slot-based streaming admission (sched/)
    slots: int = 32            # in-flight capacity in continuous mode
    kernel: bool = False       # fused Pallas descent-scoring hop
                               # (kernels/descent_score; bitwise-identical
                               # results, interpret mode per
                               # kernels/config.py)
    dma: bool = False          # with kernel: HBM-resident tables +
                               # per-chunk candidate-row DMA (the
                               # "pallas_dma" scorer; bitwise-identical,
                               # reports dma_bytes/bytes_saved)
    ttl: int = 0               # lifecycle: ticks before an untouched row
                               # expires (0 = never)
    repair_every: int = 0      # lifecycle: churn-repair cadence in ticks
                               # (0 = off)
    admission: str = "fifo"    # "slo": priority classes + deadline-aware
                               # admission, explicit shedding (sched/)
    max_pending: int = 0       # pending-queue bound under slo admission
                               # (0 = unbounded; overflow is shed)
    adaptive: int = 0          # >0: free continuous slots once the top-k
                               # prefix held for this many hops (patience)
    cache: int = 0             # >0: fingerprint-keyed result-cache
                               # capacity (journal-invalidated)
    resident_configs: int = 0  # tiered residency: only clusters of the
                               # first m hash configurations contribute
                               # shard residents (0 = all t; shards > 1)
    rebalance_every: int = 0   # background re-balance check cadence in
                               # scheduler steps (0 = off; shards > 1)
    rebalance_threshold: float = 1.25  # measured imbalance that triggers
                               # a blue/green plan swap

    def spec(self) -> PlanSpec:
        """Map the flag pile onto a validated plan on the three axes."""
        if self.dma and not self.kernel:
            raise ValueError(
                "dma selects the HBM-resident placement OF the fused "
                "kernel hop; it needs kernel=True")
        scorer = ("pallas_dma" if self.dma
                  else "pallas" if self.kernel else "jnp")
        return PlanSpec(
            placement=self.shards,
            batching="continuous" if self.continuous else "wave",
            scorer=scorer,
            k=self.k, beam=self.beam, hops=self.hops,
            max_wave=self.max_wave, slots=self.slots,
            seeds_per_config=self.seeds_per_config,
            shard_oversample=self.shard_oversample,
            admission=self.admission, max_pending=self.max_pending,
            adaptive=self.adaptive, cache=self.cache,
            resident_configs=self.resident_configs)


class QueryEngine:
    def __init__(self, index: KNNIndex, qc: QueryConfig | None = None, *,
                 clock=None, faults=None, store=None):
        self.index = index
        self.qc = qc or QueryConfig()
        # Injectable clock (same pattern as SlotScheduler): tests drive
        # a sched.ManualClock so latency / deadline / backoff behavior
        # is deterministic without a single time.sleep.
        self.clock = clock or time.perf_counter
        self.plan = DescentPlan(index, self.qc.spec(), clock=self.clock)
        self.queue: deque[QueryRequest] = deque()
        self.done: list[QueryRequest] = []
        self.n_inserted = 0
        self.n_refreshes = 0
        self._cohort: list[tuple[int, np.ndarray]] = []  # (uid, profile)
        self.lifecycle = LifecycleManager(
            self, LifecycleConfig(ttl=self.qc.ttl,
                                  repair_every=self.qc.repair_every))
        if self.qc.rebalance_every > 0 and self.qc.shards <= 1:
            raise ValueError(
                "rebalance_every re-balances the SHARD partition; a "
                "single-device placement has nothing to re-balance "
                "(use shards > 1)")
        self.rebalance = Rebalancer(
            self.plan, RebalanceConfig(
                every=self.qc.rebalance_every,
                threshold=self.qc.rebalance_threshold))
        # Fault pipeline (repro/faults): injector → health/failover →
        # crash store. Deferred imports keep repro.query importable
        # without the faults package in the graph.
        self.faults = faults
        self.failover = None
        if faults is not None:
            from repro.faults.failover import FailoverManager
            self.failover = FailoverManager(self.plan, faults)
        self.store = store
        if store is not None:
            store.attach(self)

    @property
    def n_ticks(self) -> int:
        """Continuous slot-step invocations (0 for wave plans)."""
        return self.plan.n_ticks

    # -- batched search (the plan's raw wave program) ----------------------

    def query_batch(self, profiles, k: int | None = None,
                    hops: int | None = None):
        """Answer a batch of raw profiles: (ids int32[q, k], sims f32[q, k])."""
        return self.plan.query_batch(profiles, k=k, hops=hops)

    def sharded_state(self):
        """The plan's delta-synced ShardedDescent (built on demand), or
        None when it serves single-device. Public accessor for
        diagnostics."""
        return self.plan.sharded_state()

    # -- queue / serving loop ----------------------------------------------

    def submit(self, req: QueryRequest):
        req.t_submit = self.clock()
        self.queue.append(req)

    @property
    def degraded(self) -> bool:
        """True while the fleet serves with >=1 shard masked out."""
        return self.failover is not None and self.failover.degraded

    def busy(self) -> bool:
        """True while requests are queued or (continuous) in flight."""
        return bool(self.queue) or self.plan.busy()

    def step(self) -> int:
        """Serve one scheduler step — one wave, or one continuous tick.

        The open-loop benchmark drives this directly so arrivals can be
        interleaved with service; :meth:`run` loops it until drained.
        Lifecycle maintenance (TTL expiry, churn repair) fires AFTER the
        plan step — between compiled programs — so continuous slots
        in flight never see a half-applied mutation mid-hop. The shard
        re-balancer runs after lifecycle: its imbalance measurement
        (and any blue/green swap) sees the step's lifecycle mutations
        already journaled, and the swap lands before the next compiled
        program.

        The fault pipeline brackets all of it: the injector's
        ``begin_step`` fires FIRST (a ``crash@T`` lands before any work
        of step T — the boundary the WAL guarantees consistency at) and
        the failover probe masks newly-dead shards before the plan step
        serves. Failover recovery and the crash store run LAST, so a
        recovery swap / snapshot sees the step's mutations journaled.
        """
        if self.faults is not None:
            self.faults.begin_step()  # may raise EngineCrash
        if self.failover is not None:
            self.failover.observe()
        n = self.plan.step(self.queue, self.done)
        self.lifecycle.maintain()
        self.rebalance.maintain()
        if self.failover is not None:
            self.failover.maintain()
        if self.store is not None:
            self.store.maintain(self)
        return n

    def tick(self) -> int:
        """One continuous tick (alias of :meth:`step` for slot plans)."""
        if not self.qc.continuous:
            raise ValueError("tick() is the continuous step; this engine "
                             f"serves {self.plan.describe()}")
        return self.step()

    def run(self, on_tick=None) -> dict:
        """Drain the queue through the plan; returns aggregate stats.

        ``on_tick`` (continuous plans only): host callback
        ``f(engine, tick)`` invoked between scheduler steps — the hook
        the interleaved insert-under-load tests (and any mid-stream
        mutation) use.
        """
        t0 = self.clock()
        n_steps = 0
        n_new_done = 0
        continuous = self.qc.continuous
        while self.busy():
            if continuous and on_tick is not None:
                on_tick(self, n_steps)
            n_new_done += self.step()
            n_steps += 1
        dt = max(self.clock() - t0, 1e-9)
        recent = self.done[-n_new_done:] if n_new_done else []
        # Latency percentiles cover SERVED requests only: a rejected
        # (shed) request's submit→shed interval is queueing, not
        # service, and an unserved latency is None by contract.
        lats = [r.latency for r in recent
                if r.status == "done" and r.latency is not None]
        n_shed = sum(1 for r in recent if r.rejected)
        stats = {
            "requests": n_new_done,
            "served": n_new_done - n_shed,
            "shed": n_shed,
            "mode": "continuous" if continuous else "wave",
            "plan": self.plan.describe(),
            "waves": n_steps,
            "qps": (n_new_done - n_shed) / dt,
            "mean_latency_s": float(np.mean(lats)) if lats else 0.0,
            "p50_latency_s": float(np.percentile(lats, 50)) if lats else 0.0,
            "p95_latency_s": float(np.percentile(lats, 95)) if lats else 0.0,
            "inserted": self.n_inserted,
            "shards": self.qc.shards,
            "refreshes": self.n_refreshes,
        }
        if self.plan.spec.kernel:
            # Memory-hierarchy accounting from the fused hop (cumulative
            # over the plan's lifetime; the DMA scorer fills the byte
            # counters, the VMEM scorer only scored_lanes).
            stats["descent"] = dict(self.plan.descent_stats)
        if self.plan.cache is not None:
            stats["cache"] = self.plan.cache.stats()
        if self.rebalance.active:
            stats["rebalance"] = self.rebalance.stats()
        if self.faults is not None:
            faults = dict(self.faults.stats())
            if self.failover is not None:
                faults.update(self.failover.stats())
            faults["degraded_served"] = sum(
                1 for r in recent if getattr(r, "degraded", False))
            stats["faults"] = faults
        if self.store is not None:
            stats["store"] = self.store.stats()
        return stats

    # -- online insertion --------------------------------------------------

    def insert(self, profile) -> int:
        """Add a new user online; returns its id in the index.

        Links the user via its own search result (graph-degree k), then
        registers it with the FRH router so later queries seed from it.
        The neighbor search runs through the engine's own plan: under a
        sharded placement each insert costs one O(degree) delta reshard
        (row + membership journals), NOT a rebuild — and no full-index
        device copy is ever materialized.
        """
        ix = self.index
        items, offsets = profiles_to_csr([profile])
        qgf = fingerprint_profiles(items, offsets, ix)
        placed = placements(ix, items, offsets)
        ids, sims = self.plan.search(items, offsets, qgf, ix.k,
                                     placed=placed)
        u = ix.append_user(np.asarray(qgf.words)[0], int(qgf.card[0]),
                           ids[0], sims[0])
        for matched in placed[0]:
            if matched:  # deepest matching cluster of this configuration
                ix.add_cluster_member(matched[0], u)
        self.n_inserted += 1
        # Keep the materialized CSR row, not the caller's object — a
        # one-shot iterable profile is already exhausted by now.
        self._cohort.append((u, items[offsets[0]:offsets[1]].copy()))
        self.lifecycle.note_insert(u)
        if len(self._cohort) >= self.qc.refresh_every:
            self.flush_cohort()
        return u

    # -- lifecycle (deletes / updates / TTL — src/repro/lifecycle) ---------

    def remove_user(self, u: int):
        """Delete user ``u`` online: tombstone, patch incident edges,
        deregister from routing. Queries in flight and later never see
        it (the tombstone mask is threaded through every plan)."""
        self.lifecycle.remove(u)

    def update_user(self, u: int, profile):
        """Replace ``u``'s profile online: re-sketch, re-score incident
        edges, and re-link via a localized neighborhood descent."""
        return self.lifecycle.update(u, profile)

    def touch(self, u: int):
        """Record activity on ``u`` (resets its TTL window)."""
        self.lifecycle.touch(u)

    def flush_cohort(self) -> int:
        """Re-run C² clustering on the accumulated insert cohort (see
        :meth:`KNNIndex.refresh_cohort`); returns new clusters registered."""
        if not self._cohort:
            return 0
        uids = np.array([u for u, _ in self._cohort], dtype=np.int32)
        items, offsets = profiles_to_csr([p for _, p in self._cohort])
        n_new = self.index.refresh_cohort(items, offsets, uids)
        self._cohort = []  # drained only after the refresh succeeded
        self.n_refreshes += 1
        return n_new

    # -- crash recovery (snapshot + WAL replay — src/repro/faults/wal) -----

    @classmethod
    def recover(cls, path, qc: QueryConfig | None = None, *,
                clock=None, faults=None, store=None) -> "QueryEngine":
        """Rebuild an engine from a :class:`~repro.faults.wal.CrashStore`
        directory: load the last snapshot, replay the WAL suffix, and —
        for sharded configs — restore the frozen base plan from its
        sidecar so the serving partition extends the SAME lineage the
        crashed engine was on (``extend_plan`` composes: extending the
        restored base over the replayed index lands bitwise where the
        live plan was). Passing ``store`` re-attaches persistence: the
        first act of the recovered engine is a fresh snapshot, so a
        second crash replays from there, not from before the first.
        """
        from repro.faults.wal import CrashStore
        index, base_plan, manifest = CrashStore.load(path)
        eng = cls(index, qc, clock=clock, faults=faults)
        eng.lifecycle.clock = int(manifest.get("lifecycle_clock", 0))
        if base_plan is not None and eng.qc.shards == base_plan.n_shards:
            from repro.query.sharded import ShardedDescent, extend_plan
            spec = eng.qc.spec()
            eng.plan._sharded = ShardedDescent(
                index, base_plan.n_shards,
                plan=extend_plan(base_plan, index),
                oversample=spec.shard_oversample,
                resident_configs=spec.resident_configs)
        if store is not None:
            eng.store = store
            store.attach(eng)  # snapshot AFTER the plan restore
        return eng

    # -- quality -----------------------------------------------------------

    def recall_vs_brute_force(self, requests: list[QueryRequest] | None = None,
                              ) -> float:
        """Mean recall@k of served results vs brute force over the index.

        Rejected/unserved requests (``ids is None``) are excluded.
        Request sets may mix per-request k (callers serve through
        engines with different ``k``): results are grouped by their k
        and each group is scored against its own brute-force truth —
        the old ``np.stack`` over ragged id rows raised instead.
        """
        reqs = requests if requests is not None else self.done
        reqs = [r for r in reqs if r.ids is not None]
        if not reqs:
            return 0.0
        by_k: dict[int, list[QueryRequest]] = {}
        for r in reqs:
            by_k.setdefault(len(r.ids), []).append(r)
        total = 0.0
        for k, group in sorted(by_k.items()):
            items, offsets = profiles_to_csr([r.profile for r in group])
            qgf = fingerprint_profiles(items, offsets, self.index)
            exact_ids, _ = exact_knn(self.index.words, self.index.card,
                                     np.asarray(qgf.words),
                                     np.asarray(qgf.card), k,
                                     tomb=self.index.tombstone)
            total += knn_recall(np.stack([r.ids for r in group]),
                                exact_ids) * len(group)
        return total / len(reqs)
