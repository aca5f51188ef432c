"""Composable descent plans: placement × batching × scorer.

A :class:`DescentPlan` is the one serving abstraction behind
:class:`~repro.query.engine.QueryEngine`. Where the engine used to
enumerate hand-rolled paths (single-device wave, continuous slots,
sharded wave) a plan is the CROSS-PRODUCT of three independent axes:

* **placement** — ``1`` (single device) or ``N`` LPT cluster shards
  (``query/sharded.py``: owner-partitioned seeds, per-shard local
  subgraphs, cross-shard top-k merge);
* **batching** — ``"wave"`` (closed batches, one jitted program per
  wave capacity) or ``"continuous"`` (slot scheduler from ``sched/``,
  streaming admission, per-slot hop budgets);
* **scorer** — ``"jnp"`` (unfused reference hop), ``"pallas"`` (the
  fused ``kernels/descent_score`` hop, tables staged through blocked
  VMEM), or ``"pallas_dma"`` (same fused hop with HBM-resident tables
  and per-chunk candidate-row DMA); all three bitwise-identical.

Any combination is a valid plan; every axis composes with every other
because the hop itself is row-independent (``query/search.py``) — the
shard axis vmaps over it, the slot axis scatters into it, and the
scorer swaps inside it. Each plan compiles one program per (plan,
shape) — tagged with :attr:`PlanSpec.key` in the ``sched.trace``
counters so ``trace.compile_count(plan.key)`` can assert compile-once
across admissions and reshards — and OWNS its device state:

* single placement: journal-repaired padded index copies (the former
  ``QueryEngine._sync``);
* sharded placement: a delta-reshardable
  :class:`~repro.query.sharded.ShardedDescent` — no full-index device
  copy exists in sharded mode (which halves sharded serving's index
  memory vs the pre-plan engine).

Result invariants (locked down by ``tests/test_plan.py``): for a fixed
placement, batching and scorer NEVER change a result — continuous ==
wave and pallas == jnp, bitwise on (ids, sims). Placement is the one
axis that trades results for scale (disjoint seed basins + dropped
cross-shard edges), and it does so identically under every batching ×
scorer combination.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.local_knn import capacity_of
from repro.query.cache import ResultCache
from repro.query.index import KNNIndex
from repro.query.router import (fingerprint_profiles, placements,
                                profiles_to_csr, route)
from repro.query.search import (batched_descent, shard_slot_admit,
                                shard_slot_hop, shard_slot_topk,
                                slot_admit, slot_hop, slot_prefix_stable)
from repro.sched import ADMISSION_POLICIES, SlotScheduler, shed_and_select
from repro.sched import trace
from repro.types import NEG_INF, PAD_ID

BATCHINGS = ("wave", "continuous")
SCORERS = ("jnp", "pallas", "pallas_dma")


def _csr_subset(items: np.ndarray, offsets: np.ndarray,
                idxs) -> tuple[np.ndarray, np.ndarray]:
    """CSR rows ``idxs`` of a (items, offsets) profile batch."""
    rows = [items[offsets[i]:offsets[i + 1]] for i in idxs]
    sizes = np.array([len(r) for r in rows], dtype=np.int64)
    out_offsets = np.zeros(len(rows) + 1, dtype=np.int64)
    np.cumsum(sizes, out=out_offsets[1:])
    out_items = (np.concatenate(rows) if rows
                 else np.zeros((0,), np.int32)).astype(np.int32)
    return out_items, out_offsets


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """Static description of a descent plan (hashable, validated).

    ``QueryConfig.spec()`` maps the engine's flag pile onto one of
    these; benchmarks and tests can also build them directly.
    """

    placement: int = 1          # shards (1 = single device)
    batching: str = "wave"      # "wave" | "continuous"
    scorer: str = "jnp"         # "jnp" | "pallas" | "pallas_dma"
    k: int = 10
    beam: int = 32
    hops: int = 3
    max_wave: int = 256         # wave batching: queries per program
    slots: int = 32             # continuous batching: in-flight capacity
    seeds_per_config: int = 16
    shard_oversample: float = 1.5
    admission: str = "fifo"     # "fifo" | "slo" (priority + deadline
                                # admission with explicit shedding)
    max_pending: int = 0        # slo: pending-queue bound (0 = unbounded)
    adaptive: int = 0           # continuous: free a slot once its top-k
                                # prefix held this many hops (0 = off)
    cache: int = 0              # fingerprint result-cache capacity (0=off)
    resident_configs: int = 0   # tiered residency: clusters of the first
                                # m hash configurations contribute shard
                                # residents (0 = all t; sharded only)

    def __post_init__(self):
        if self.placement < 1:
            raise ValueError(
                f"plan placement must be >= 1 shard, got {self.placement}")
        if self.batching not in BATCHINGS:
            raise ValueError(
                f"unknown batching {self.batching!r}; supported: "
                f"{BATCHINGS} (every batching composes with every "
                f"placement and scorer)")
        if self.scorer not in SCORERS:
            raise ValueError(
                f"unknown scorer {self.scorer!r}; supported: {SCORERS}")
        if self.batching == "continuous" and self.slots < 1:
            raise ValueError(f"continuous plans need slots >= 1, "
                             f"got {self.slots}")
        if self.batching == "wave" and self.max_wave < 1:
            raise ValueError(f"wave plans need max_wave >= 1, "
                             f"got {self.max_wave}")
        if self.k < 1 or self.hops < 0:
            raise ValueError(f"invalid k={self.k} / hops={self.hops}")
        if self.admission not in ADMISSION_POLICIES:
            raise ValueError(
                f"unknown admission {self.admission!r}; supported: "
                f"{ADMISSION_POLICIES}")
        if self.max_pending < 0:
            raise ValueError(
                f"max_pending must be >= 0, got {self.max_pending}")
        if self.max_pending > 0 and self.admission != "slo":
            raise ValueError(
                "max_pending bounds the slo admission queue; pure FIFO "
                "never sheds (set admission='slo' to bound the queue)")
        if self.adaptive < 0:
            raise ValueError(f"adaptive patience must be >= 0, "
                             f"got {self.adaptive}")
        if self.adaptive > 0 and self.batching != "continuous":
            raise ValueError(
                "adaptive hop budgets free continuous slots on top-k "
                "prefix stability; wave batching has no per-request "
                "termination (use batching='continuous')")
        if self.cache < 0:
            raise ValueError(f"cache capacity must be >= 0, "
                             f"got {self.cache}")
        if self.resident_configs < 0:
            raise ValueError(f"resident_configs must be >= 0, "
                             f"got {self.resident_configs}")
        if self.resident_configs > 0 and self.placement == 1:
            raise ValueError(
                "resident_configs restricts SHARD residency to a subset "
                "of hash configurations; a single-device placement hosts "
                "every row (use placement > 1)")

    @property
    def kernel(self) -> bool:
        return self.scorer in ("pallas", "pallas_dma")

    @property
    def dma(self) -> bool:
        """HBM-resident table placement with per-chunk candidate DMA
        (``kernels/descent_score/ops.descent_hop(dma=True)``)."""
        return self.scorer == "pallas_dma"

    @property
    def key(self) -> tuple:
        """The plan's identity on the serving axes — the jit-trace tag
        (``sched.trace.compile_count``) and the bench row key."""
        return (self.placement, self.batching, self.scorer)

    def describe(self) -> str:
        place = ("single" if self.placement == 1
                 else f"sharded({self.placement})")
        batch = ("wave" if self.batching == "wave"
                 else f"continuous(slots={self.slots})")
        base = f"{place} x {batch} x {self.scorer}"
        extras = []
        if self.admission != "fifo":
            extras.append(f"slo(max_pending={self.max_pending})")
        if self.adaptive:
            extras.append(f"adaptive({self.adaptive})")
        if self.cache:
            extras.append(f"cache({self.cache})")
        if self.resident_configs:
            extras.append(f"resident_configs({self.resident_configs})")
        return base + (" + " + ", ".join(extras) if extras else "")


class _SlotState:
    """Device-resident per-slot state for a continuous plan.

    Mirrors PR 3's single-device slot arrays, with one twist: under a
    sharded placement the beams carry a leading shard axis
    (``[S, n_slots, shard_beam]``) — every shard advances its own beam
    per slot, and the cross-shard merge happens at release time. Query
    fingerprints, hop counters, and the scheduler stay shard-agnostic.
    """

    def __init__(self, index: KNNIndex, spec: PlanSpec, beam: int,
                 pin=None, clock=None):
        n_slots = spec.slots
        self.beam = beam
        self.admit_cap = int(np.clip(n_slots // 4, 8, 32))
        self.seed_cols = index.t * spec.seeds_per_config
        self.sched = SlotScheduler(n_slots, policy=spec.admission,
                                   max_pending=spec.max_pending,
                                   clock=clock)
        self.q_words = jnp.zeros((n_slots, index.words.shape[1]),
                                 jnp.uint32)
        self.q_card = jnp.zeros(n_slots, jnp.int32)
        if spec.placement > 1:
            shape = (spec.placement, n_slots, beam)
        else:
            shape = (n_slots, beam)
        beam_ids = np.full(shape, PAD_ID, np.int32)
        beam_sims = np.full(shape, NEG_INF, np.float32)
        # On a mesh, per-shard beams live on their shard's device.
        self.beam_ids = pin(beam_ids) if pin else jnp.asarray(beam_ids)
        self.beam_sims = pin(beam_sims) if pin else jnp.asarray(beam_sims)
        self.hops_done = np.zeros(n_slots, np.int64)
        self.budget = np.full(n_slots, spec.hops, np.int64)
        # Adaptive-budget bookkeeping (allocated only when the policy is
        # on): per-slot count of consecutive hops whose top-k prefix was
        # unchanged, the device-resident previous prefix it compares
        # against, and a freshness flag so a re-admitted slot never
        # inherits its previous occupant's prefix (identical repeated
        # queries would otherwise look "stable" at hop one).
        self.streak = np.zeros(n_slots, np.int64)
        self.fresh = np.ones(n_slots, bool)
        self.prefix_ids = None
        if spec.adaptive > 0:
            pshape = ((spec.placement, n_slots, spec.k)
                      if spec.placement > 1 else (n_slots, spec.k))
            prefix = np.full(pshape, PAD_ID, np.int32)
            self.prefix_ids = pin(prefix) if pin else jnp.asarray(prefix)


class DescentPlan:
    """One placement × batching × scorer combination, compiled once per
    shape, owning its device state and serving loop.

    The engine's whole serving surface is ``submit → plan.step(queue,
    done) → collect``; ``search``/``query_batch`` expose the raw wave
    program (used for insert searches and benchmarks under any plan).
    """

    def __init__(self, index: KNNIndex, spec: PlanSpec, clock=None):
        self.index = index
        self.spec = spec
        self.key = spec.key
        self.beam = max(spec.beam, spec.k)
        # Injectable clock (defaults to wall time): every completion /
        # shed / deadline stamp in the serving loop reads it, so fault
        # and SLO tests drive latency deterministically (sched.ManualClock).
        self.clock = clock or time.perf_counter
        self._single = None     # (version, cap, device arrays)
        self._sharded = None    # ShardedDescent (delta-synced)
        self._slots: Optional[_SlotState] = None
        self.n_ticks = 0
        # Memory-hierarchy accounting for kernel scorers, accumulated
        # over every hop this plan ran (real query rows only — pad rows
        # and inactive slots are masked out before they land here).
        # ``scored_lanes`` counts candidate lanes that survived
        # suppression; for the DMA scorer ``dma_bytes`` is the
        # fingerprint traffic actually moved HBM→VMEM and
        # ``bytes_saved`` the traffic the suppressed-lane skip avoided.
        # The jnp scorer contributes zeros (it moves no explicit DMA).
        self.descent_stats = {"scored_lanes": 0, "dma_bytes": 0,
                              "bytes_saved": 0, "hop_queries": 0}
        # Fingerprint-keyed result cache (query/cache.py), flushed on
        # journal-visible index mutations — exact hits serve without a
        # descent, bitwise-identically to one.
        self.cache = ResultCache(index, spec.cache) if spec.cache else None

    def describe(self) -> str:
        return self.spec.describe()

    def _note_stats(self, stats) -> None:
        """Fold one program's hop accounting (i32[rows, 3] of
        ``(n_scored, dma_bytes, bytes_saved)``, already masked to real
        rows) into :attr:`descent_stats`."""
        s = np.asarray(stats, dtype=np.int64)
        if s.size == 0:
            return
        self.descent_stats["scored_lanes"] += int(s[:, 0].sum())
        self.descent_stats["dma_bytes"] += int(s[:, 1].sum())
        self.descent_stats["bytes_saved"] += int(s[:, 2].sum())
        self.descent_stats["hop_queries"] += int(s.shape[0])

    # -- device state ------------------------------------------------------

    def sync(self):
        """Repair this plan's device state to the index's version.

        Single placement: journal-driven row scatter into the padded
        full-index copy. Sharded placement: delta reshard
        (:meth:`ShardedDescent.sync`) — the plan never materializes a
        full-index device copy in sharded mode.
        """
        if self.spec.placement > 1:
            return self._sync_sharded()
        return self._sync_single()

    def _sync_single(self):
        """Device copies of the index, padded to a power-of-two row count.

        Stale copies are repaired incrementally when possible: an insert
        touches only the new row plus its patched neighbors (the index
        journals them — :meth:`KNNIndex.rows_changed_since`), so those
        rows are scattered into the resident device arrays instead of
        re-padding and re-uploading all n rows per version bump. The full
        upload happens only on first use, capacity crossings, or after
        enough mutations that the journal no longer helps."""
        ix = self.index
        if self._single is not None and self._single[0] == ix.version:
            return self._single[2]
        n, cap = ix.n, capacity_of(ix.n, minimum=64)
        if self._single is not None and self._single[1] == cap:
            changed = ix.rows_changed_since(self._single[0])
            if changed is not None and len(changed) <= max(64, n // 8):
                arrays = self._single[2]
                if changed:
                    rows = np.fromiter(sorted(changed), dtype=np.int64,
                                       count=len(changed))
                    idx = jnp.asarray(rows)
                    g, r, w, c, t = arrays
                    arrays = (
                        g.at[idx].set(jnp.asarray(ix.graph_ids[rows])),
                        r.at[idx].set(jnp.asarray(ix.rev_ids[rows])),
                        w.at[idx].set(jnp.asarray(ix.words[rows])),
                        c.at[idx].set(jnp.asarray(ix.card[rows])),
                        t.at[idx].set(jnp.asarray(ix.tombstone[rows])),
                    )
                self._single = (ix.version, cap, arrays)
                return arrays
        pad = cap - n
        arrays = (
            jnp.asarray(np.pad(ix.graph_ids, ((0, pad), (0, 0)),
                               constant_values=PAD_ID)),
            jnp.asarray(np.pad(ix.rev_ids, ((0, pad), (0, 0)),
                               constant_values=PAD_ID)),
            jnp.asarray(np.pad(ix.words, ((0, pad), (0, 0)))),
            jnp.asarray(np.pad(ix.card, (0, pad))),
            jnp.asarray(np.pad(ix.tombstone, (0, pad))),
        )
        self._single = (ix.version, cap, arrays)
        return arrays

    def _sync_sharded(self):
        from repro.query.sharded import ShardedDescent

        if (self._sharded is None
                or self._sharded.n_shards != self.spec.placement):
            self._sharded = ShardedDescent(
                self.index, self.spec.placement,
                oversample=self.spec.shard_oversample,
                resident_configs=self.spec.resident_configs)
        else:
            self._sharded.sync()
        return self._sharded

    def sharded_state(self):
        """The delta-synced ShardedDescent, or None for single-device
        placements. Public accessor for diagnostics."""
        return self._sync_sharded() if self.spec.placement > 1 else None

    def _degraded(self) -> bool:
        """True while any shard is masked out of serving (fault layer).
        Completions stamped in a degraded window carry
        ``req.degraded = True`` and are never cached."""
        sd = self._sharded
        return sd is not None and bool(sd.dead.any())

    def mask_shard_slots(self, down) -> None:
        """Wipe the in-flight per-shard slot beams of newly-downed
        shards (bool[S] mask): their lanes drop to PAD/NEG_INF so a
        dead shard's pre-failure beam content cannot win a release-time
        merge. Survivor shards' beams are untouched — in-flight
        requests keep descending on the healthy fleet. No-op for wave
        plans (no slot state) and single placements."""
        if self._slots is None or self.spec.placement <= 1:
            return
        down = np.asarray(down, dtype=bool)
        if not down.any():
            return
        st = self._slots
        d = jnp.asarray(down)[:, None, None]
        st.beam_ids = jnp.where(d, PAD_ID, st.beam_ids)
        st.beam_sims = jnp.where(d, NEG_INF, st.beam_sims)
        if self.spec.adaptive > 0:
            # Prefixes were computed against the full fleet — restart
            # every stability streak rather than free a slot on a
            # pre-failure comparison.
            st.streak[:] = 0
            st.fresh[:] = True

    def note_replan(self):
        """A blue/green re-balance swapped the sharded partition
        (``query/rebalance.py``). No index content changed — every
        journal would PROVE a no-op — but placement is the one axis
        that legitimately changes results, so cached pre-swap entries
        must never be served: flush explicitly. The flush counter bump
        also stops in-flight continuous requests (admitted pre-swap,
        completing post-swap) from populating the cache with straddled
        results."""
        if self.cache is not None:
            self.cache.invalidate()

    # -- raw wave-program search (any plan; insert + benchmarks use it) ----

    def search(self, items, offsets, qgf, k: int, *,
               hops: int | None = None, placed=None):
        """Route + beam-descend already-fingerprinted query profiles
        through this plan's placement (one closed wave, whatever the
        plan's batching — the raw batch API).

        With a result cache configured, exact-fingerprint hits are
        served from it (bitwise what the descent would return — the
        cache flushes on any journal-visible index mutation) and only
        the misses route + descend.
        """
        if qgf.sketch != self.index.sketch:
            raise ValueError(f"queries fingerprinted as {qgf.sketch!r} "
                             f"against an index of {self.index.sketch!r} "
                             f"rows")
        hops = self.spec.hops if hops is None else hops
        if self.cache is None:
            with trace.span("repro.wave.route"):
                seeds = route(self.index, items, offsets,
                              self.spec.seeds_per_config, placed=placed)
            return self.descend_rows(qgf.words, qgf.card, seeds, k,
                                     hops=hops)
        self.cache.sync()
        qw, qc = np.asarray(qgf.words), np.asarray(qgf.card)
        qn = qw.shape[0]
        keys = [self.cache.key(qw[i], qc[i], k, hops) for i in range(qn)]
        out_ids = np.empty((qn, k), np.int32)
        out_sims = np.empty((qn, k), np.float32)
        miss = []
        for i, cache_key in enumerate(keys):
            hit = self.cache.get(cache_key)
            if hit is None:
                miss.append(i)
            else:
                out_ids[i], out_sims[i] = hit
        if miss:
            m_items, m_offsets = _csr_subset(items, offsets, miss)
            m_placed = ([placed[i] for i in miss]
                        if placed is not None else None)
            with trace.span("repro.wave.route"):
                seeds = route(self.index, m_items, m_offsets,
                              self.spec.seeds_per_config, placed=m_placed)
            m_ids, m_sims = self.descend_rows(qw[miss], qc[miss], seeds,
                                              k, hops=hops)
            degraded = self._degraded()
            for j, i in enumerate(miss):
                out_ids[i], out_sims[i] = m_ids[j], m_sims[j]
                if degraded:
                    self.cache.degraded_skips += 1
                else:
                    self.cache.put(keys[i], m_ids[j], m_sims[j])
        return out_ids, out_sims

    def descend_rows(self, q_words, q_card, seeds, k: int, *,
                     hops: int | None = None, beam: int | None = None):
        """Beam-descend from EXPLICIT seed rows — no FRH routing.

        The lifecycle subsystem's localized re-linking runs through this:
        an updated (or repair-pass) user seeds descent from its current
        graph neighborhood instead of hash placement, so the search cost
        stays bounded by the neighborhood, not the index. Same compiled
        programs as :meth:`search` (the seed width — and the optional
        ``beam`` override — are the only new shape axes, and callers
        keep them static)."""
        spec = self.spec
        beam = max(self.beam if beam is None else beam, k)
        hops = spec.hops if hops is None else hops
        with trace.span("repro.wave.descent"):
            q_words = np.asarray(q_words)
            q_card = np.asarray(q_card)
            seeds = np.asarray(seeds)
            qn = q_words.shape[0]
            qcap = capacity_of(qn, minimum=8)
            qw = np.zeros((qcap, q_words.shape[1]), dtype=np.uint32)
            qw[:qn] = q_words
            qcard = np.zeros(qcap, dtype=np.int32)
            qcard[:qn] = q_card
            qseeds = np.full((qcap, seeds.shape[1]), PAD_ID, dtype=np.int32)
            qseeds[:qn] = seeds
            if spec.placement > 1:
                sd = self._sync_sharded()
                ids, sims = sd.descend(
                    qw, qcard, qseeds, k=k, beam=beam, hops=hops,
                    kernel=spec.kernel, dma=spec.dma, tag=self.key)
                self._note_stats(sd.last_hop_stats[:qn])
            else:
                graph_ids, rev_ids, words, card, tomb = self._sync_single()
                ids, sims, stats = batched_descent(
                    graph_ids, rev_ids, words, card,
                    jnp.asarray(qw), jnp.asarray(qcard), jnp.asarray(qseeds),
                    k=k, beam=beam, hops=hops, kernel=spec.kernel,
                    dma=spec.dma, tag=self.key, tomb=tomb)
                self._note_stats(np.asarray(stats)[:qn])
            return np.asarray(ids)[:qn], np.asarray(sims)[:qn]

    def query_batch(self, profiles, k: int | None = None,
                    hops: int | None = None):
        """Answer raw profiles: (ids int32[q, k], sims float32[q, k])."""
        with trace.span("repro.wave"):
            with trace.span("repro.wave.fingerprint"):
                items, offsets = profiles_to_csr(profiles)
                qgf = fingerprint_profiles(items, offsets, self.index)
            return self.search(items, offsets, qgf, k or self.spec.k,
                               hops=hops)

    # -- the serving loop --------------------------------------------------

    @property
    def scheduler(self) -> Optional[SlotScheduler]:
        """The continuous slot scheduler (None for wave plans)."""
        return self._slots.sched if self._slots is not None else None

    def busy(self) -> bool:
        """True while this plan holds in-flight work (continuous slots)."""
        return self._slots is not None and self._slots.sched.has_work()

    def step(self, queue, done) -> int:
        """Serve one scheduler step — one wave, or one continuous tick.

        Drains/admits from ``queue`` (a deque of QueryRequest-likes),
        appends completed requests to ``done`` with results + ``t_done``
        stamped, and returns how many completed. This is the ONLY
        serving path: every placement × batching × scorer combination
        goes through it.
        """
        if self.spec.batching == "continuous":
            return self._step_continuous(queue, done)
        return self._step_wave(queue, done)

    # -- wave batching -----------------------------------------------------

    def _reject(self, shed, done) -> int:
        """Complete shed requests with the ``rejected`` marker — they
        enter ``done`` (counted, latency-excluded) rather than vanish."""
        if not shed:
            return 0
        now = self.clock()
        for r in shed:
            r.status = "rejected"
            r.t_done = now
            done.append(r)
        return len(shed)

    def _step_wave(self, queue, done) -> int:
        """Close one wave from the queue; returns requests completed.

        A wave runs to the MAX hop budget of its members (the compiled
        program has one static hop count) — one deep request convoys
        every shallow request behind it. Continuous batching's per-slot
        hop budgets are the fix. Under slo admission the wave closes
        over the best (class, deadline) requests and expired/overflow
        requests are shed with a rejected marker; the default FIFO path
        is byte-identical to the pre-SLO wave.
        """
        spec = self.spec
        n_done = 0
        if spec.admission == "slo":
            wave, shed = shed_and_select(queue, spec.max_wave,
                                         self.clock(),
                                         spec.max_pending)
            n_done = self._reject(shed, done)
        else:
            wave = []
            while queue and len(wave) < spec.max_wave:
                wave.append(queue.popleft())
        if not wave:
            return n_done
        hops = max(r.hops if r.hops is not None else spec.hops
                   for r in wave)
        ids, sims = self.query_batch([r.profile for r in wave], hops=hops)
        now = self.clock()
        degraded = self._degraded()
        for j, r in enumerate(wave):
            r.ids, r.sims = ids[j], sims[j]
            r.t_done = now
            r.status = "done"
            r.degraded = degraded
            done.append(r)
        return len(wave) + n_done

    # -- continuous batching -----------------------------------------------

    def _slot_state(self) -> _SlotState:
        if self._slots is None:
            beam = self.beam
            pin = None
            if self.spec.placement > 1:
                sd = self._sync_sharded()
                beam = sd.shard_beam(self.beam, self.spec.k)
                if sd.mesh is not None:
                    pin = sd._pin
            self._slots = _SlotState(self.index, self.spec, beam, pin=pin,
                                     clock=self.clock)
        return self._slots

    def _slot_results(self, st: _SlotState):
        """(ids int32[n_slots, k], sims f32[n_slots, k]) host snapshots.

        Single placement: the beam is canonical, so top-k is its prefix.
        Sharded placement: per-shard prefixes merged cross-shard in
        global ids (:func:`~repro.query.search.shard_slot_topk`) —
        byte-identical to the wave path's closing merges either way.

        Every call is one host-side snapshot dispatch —
        ``trace.launch_count(("slot_results", plan.key))`` lets tests
        assert a tick costs ONE snapshot however many admission chunks
        (including zero-hop bursts) fed it.
        """
        trace.launch(("slot_results", self.key))
        k = self.spec.k
        if self.spec.placement > 1:
            ids, sims = shard_slot_topk(self._sharded._dev[4], st.beam_ids,
                                        st.beam_sims, k=k, tag=self.key)
            return np.asarray(ids), np.asarray(sims)
        return (np.asarray(st.beam_ids)[:, :k],
                np.asarray(st.beam_sims)[:, :k])

    def _admit(self, st: _SlotState, admitted, done) -> int:
        """Scatter an admission generation into the slot arrays,
        bucketed to ``admit_cap`` rows so one program compiles per
        bucket shape no matter how requests stream in.

        With a result cache, each admitted request is first looked up by
        exact fingerprint: hits complete immediately (slot released
        without ever entering the scatter — their rows keep the
        ``n_slots`` drop sentinel) and only misses are routed and
        scattered. Returns the number of cache-served completions so the
        tick loop can re-admit into the freed slots.
        """
        spec = self.spec
        items, offsets = profiles_to_csr([r.profile for _, r in admitted])
        qgf = fingerprint_profiles(items, offsets, self.index)
        qw, qc = np.asarray(qgf.words), np.asarray(qgf.card)
        n_hit = 0
        if self.cache is None:
            rows = [(j, slot, req)
                    for j, (slot, req) in enumerate(admitted)]
            m_items, m_offsets = items, offsets
        else:
            rows = []
            now = self.clock()
            for j, (slot, req) in enumerate(admitted):
                budget = req.hops if req.hops is not None else spec.hops
                ck = self.cache.key(qw[j], qc[j], spec.k, budget)
                hit = self.cache.get(ck)
                if hit is not None:
                    st.sched.release(slot)
                    req.ids, req.sims = hit
                    req.t_done = now
                    req.status = "done"
                    done.append(req)
                    n_hit += 1
                else:
                    # Completion caches this result only if the cache
                    # was never flushed while the request was in flight
                    # (flush count unchanged == every intervening
                    # version bump was provably a no-op).
                    req._cache_key = ck
                    req._cache_flushes = self.cache.flushes
                    rows.append((j, slot, req))
            if not rows:
                return n_hit
            m_items, m_offsets = _csr_subset(items, offsets,
                                             [j for j, _, _ in rows])
        seeds = route(self.index, m_items, m_offsets,
                      spec.seeds_per_config)
        A = st.admit_cap
        sharded = spec.placement > 1
        for lo in range(0, len(rows), A):
            chunk = rows[lo:lo + A]
            new_w = np.zeros((A, st.q_words.shape[1]), np.uint32)
            new_c = np.zeros(A, np.int32)
            new_s = np.full((A, st.seed_cols), PAD_ID, np.int32)
            # n_slots = one-past-the-end sentinel; the admit scatter
            # drops those rows (mode="drop").
            idx = np.full(A, st.sched.n_slots, np.int32)
            for p, (j, slot, req) in enumerate(chunk):
                new_w[p] = qw[j]
                new_c[p] = int(qc[j])
                new_s[p] = seeds[lo + p]
                idx[p] = slot
                st.hops_done[slot] = 0
                st.budget[slot] = (req.hops if req.hops is not None
                                   else spec.hops)
                st.streak[slot] = 0
                st.fresh[slot] = True
            if sharded:
                l_seeds = self._sharded.shard_seeds(new_s)  # [S, A, cols]
                st.q_words, st.q_card, st.beam_ids, st.beam_sims = \
                    shard_slot_admit(
                        self._sharded._dev[2], self._sharded._dev[3],
                        jnp.asarray(new_w), jnp.asarray(new_c),
                        jnp.asarray(l_seeds), jnp.asarray(idx),
                        st.q_words, st.q_card, st.beam_ids, st.beam_sims,
                        beam=st.beam, tag=self.key,
                        l_tomb=self._sharded._dev[5])
            else:
                words, card, tomb = self._sync_single()[2:5]
                st.q_words, st.q_card, st.beam_ids, st.beam_sims = \
                    slot_admit(words, card, jnp.asarray(new_w),
                               jnp.asarray(new_c), jnp.asarray(new_s),
                               jnp.asarray(idx), st.q_words, st.q_card,
                               st.beam_ids, st.beam_sims, beam=st.beam,
                               tag=self.key, tomb=tomb)
        return n_hit

    def _step_continuous(self, queue, done) -> int:
        """One continuous tick: admit into free slots, advance every
        in-flight beam one hop, complete converged/exhausted slots.

        Returns the number of requests completed this tick (cache hits,
        rejections, and descents alike). Admission is mid-flight: rows
        freed by a previous tick take fresh requests while the remaining
        rows keep descending — no wave barrier. Zero-hop admissions stay
        resident through the tick (excluded from the hop, finished by
        ``hops_done >= budget``) so a tick's completions cost ONE
        slot-result snapshot however many admission chunks fed it.
        """
        spec = self.spec
        self.sync()  # placement state must be current before any program
        had_state = self._slots is not None
        st = self._slot_state()
        if spec.placement > 1:
            # A reshard since the last tick may have relabeled shard-
            # local ids (per-shard rematerialization after a cohort
            # refresh); in-flight beams hold locals, so relabel them too.
            remap = self._sharded.take_beam_remap()
            if remap is not None and had_state:
                mp = jnp.asarray(remap)
                safe = jnp.where(st.beam_ids == PAD_ID, 0, st.beam_ids)
                st.beam_ids = jnp.where(
                    st.beam_ids == PAD_ID, PAD_ID,
                    jax.vmap(lambda m, b: m[b])(mp, safe))
                # A re-balance swap may have EVICTED beam rows from
                # their shard (the map sends them to PAD): mask their
                # sims so dead lanes cannot win a merge. Under the
                # monotone frozen-base extension no live lane maps to
                # PAD, so this is the identity there.
                st.beam_sims = jnp.where(st.beam_ids == PAD_ID, NEG_INF,
                                         st.beam_sims)
                if spec.adaptive > 0:
                    # Stored prefixes are in pre-reshard local labels —
                    # restart every stability streak rather than risk a
                    # stale comparison.
                    st.streak[:] = 0
                    st.fresh[:] = True
        sched = st.sched
        while queue:
            sched.submit(queue.popleft())
        if self.cache is not None:
            self.cache.sync()
        n_done = 0
        admitted = sched.admit()
        while admitted:
            freed = self._admit(st, admitted, done)
            n_done += freed
            if not freed:
                break
            # Cache hits released their slots mid-admission; keep
            # draining the pending queue into them.
            admitted = sched.admit()
        n_done += self._reject(sched.drain_shed(), done)
        active = sched.active_mask()
        if not active.any():
            return n_done
        # Zero-budget slots never enter the hop (wave parity: a hops=0
        # wave runs a length-0 scan) — they ride to the snapshot below.
        hop_active = active & (st.hops_done < st.budget)
        changed = np.zeros(active.shape[0], bool)
        if hop_active.any():
            if spec.placement > 1:
                sd = self._sharded
                st.beam_ids, st.beam_sims, changed, hop_stats = \
                    shard_slot_hop(
                        *sd._dev[:4], st.q_words, st.q_card,
                        st.beam_ids, st.beam_sims,
                        jnp.asarray(hop_active), kernel=spec.kernel,
                        dma=spec.dma, tag=self.key, l_tomb=sd._dev[5])
            else:
                graph_ids, rev_ids, words, card, tomb = \
                    self._sync_single()
                st.beam_ids, st.beam_sims, changed, hop_stats = slot_hop(
                    graph_ids, rev_ids, words, card, st.q_words,
                    st.q_card, st.beam_ids, st.beam_sims,
                    jnp.asarray(hop_active), kernel=spec.kernel,
                    dma=spec.dma, tag=self.key, tomb=tomb)
            changed = np.asarray(changed)
            # The compiled tick hops EVERY slot row (static shapes);
            # only count the rows the host actually considers active.
            self._note_stats(np.asarray(hop_stats)[hop_active])
            st.hops_done[hop_active] += 1
            self.n_ticks += 1
            if spec.adaptive > 0:
                stable, st.prefix_ids = slot_prefix_stable(
                    st.beam_ids, st.prefix_ids, k=spec.k, tag=self.key)
                stable = np.asarray(stable)
                # A slot's FIRST hop compares against its previous
                # occupant's prefix — `fresh` keeps it out of the streak.
                gained = hop_active & stable & ~st.fresh
                st.streak[gained] += 1
                st.streak[hop_active & ~gained] = 0
                st.fresh[hop_active] = False
        # Exact completions: budget exhausted, or the full beam hit its
        # fixed point this hop (no further hop can change it — the
        # result IS the full-budget result, hence cacheable). Adaptive
        # frees on top-k-prefix stability are approximate: served, but
        # never cached.
        exact = (st.hops_done >= st.budget) | (hop_active & ~changed)
        finished = active & exact
        if spec.adaptive > 0:
            finished = finished | (hop_active
                                   & (st.streak >= spec.adaptive))
        if not finished.any():
            return n_done
        ids, sims = self._slot_results(st)
        now = self.clock()
        degraded = self._degraded()
        slots = np.flatnonzero(finished)
        for slot, req in zip(slots, sched.release_many(slots)):
            req.ids = ids[slot].copy()
            req.sims = sims[slot].copy()
            req.t_done = now
            req.status = "done"
            req.degraded = degraded
            done.append(req)
            n_done += 1
            if (self.cache is not None and exact[slot]
                    and getattr(req, "_cache_flushes", -1)
                    == self.cache.flushes):
                if degraded:
                    # A masked-fleet answer is NOT what a healthy
                    # descent would return — serving it later as a
                    # cache hit would outlive the failure window.
                    self.cache.degraded_skips += 1
                else:
                    self.cache.put(req._cache_key, req.ids, req.sims)
        return n_done
