"""Sharded query serving: partition a :class:`KNNIndex` across devices.

The build already scales Step 2 across the mesh by LPT bin-packing FRH
clusters onto devices (``core/distributed.py``). Serving reuses exactly
that partition axis: clusters are LPT-assigned to shards by member count,
each shard owns the *residents* of its clusters (the union of their
members, plus an id-strided share of unclustered users so every indexed
row lives somewhere), and each shard materializes a self-contained local
subgraph — adjacency rows of its residents with neighbor ids remapped to
shard-local indices (cross-shard edges drop to PAD), its residents'
fingerprints, and a local→global id map.

A query is routed once (global FRH placement); each routed seed is then
handed to exactly ONE shard — the shard that *owns* the seed user (users
are claimed by their largest cluster in LPT order, so ownership follows
the cluster partition). This matters: residents overlap across shards
(every user sits in up to t clusters), so broadcasting identical seeds
everywhere would make the per-shard descents redundant copies of each
other; ownership partitions the search basins instead. Beam descent runs
*per shard* over the shard-local subgraph — under ``shard_map`` when the
mesh has a device per shard (SPMD, no collectives inside, like
``distributed_local_knn``), or vmapped over the shard axis on a single
device (identical numerics; this is the CPU/CI path). Per-shard top-k
results return in global ids and are merged with ``knn/topk.merge_topk``
— the partition-then-merge strategy of "On the Merge of k-NN Graph"
(Zhao et al.).

Each shard's beam defaults to ``oversample · beam / n_shards`` (floored
at k): the fleet's total frontier stays ~``oversample ×`` the
single-device configuration, but every ``top_k`` row is ``n_shards ×``
narrower — which is what makes the vmapped CPU path competitive and the
mesh path a near-linear scale-out.

Incremental resharding (:meth:`ShardedDescent.sync`): the partition is
FROZEN at construction and *extended* — never re-balanced — as the index
mutates, mirroring the online-update discipline of Debatty et al.'s
incremental graph building. New clusters go round-robin to shards, new
users to their home shard ``u % S`` plus wherever their clusters live,
and both rules are pure functions of (base plan, current index), so a
delta-maintained state is bitwise-equal to a from-scratch
rematerialization under :func:`extend_plan` (property-tested in
``tests/test_plan.py``). An insert burst therefore costs one O(degree)
row scatter per shard — consuming the same row journal the single-device
sync uses (:meth:`KNNIndex.rows_changed_since`) plus the membership
journal (:meth:`KNNIndex.members_added_since`) — instead of a
full-tensor rebuild, and the serving programs keep their compiled shapes
(capacity rows double geometrically, like the index's own buffers). Full
per-shard rematerialization happens only when a *pre-existing* user
gains residency (cohort refresh registering it in a new cluster — its
in-edges must be remapped, and bounded reverse adjacency cannot name
them all), when capacity crosses a doubling boundary, or when a journal
no longer reaches back to the synced version.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import lpt_assign, lpt_loads
from repro.core.local_knn import capacity_of
from repro.knn.topk import merge_topk
from repro.query.index import KNNIndex
from repro.query.search import descent_kernel
from repro.sched import trace
from repro.types import NEG_INF, PAD_ID


@dataclasses.dataclass
class ShardPlan:
    """Static cluster → shard partition of an index."""

    n_shards: int
    cluster_shard: np.ndarray     # int64[n_clusters]
    residents: list[np.ndarray]   # sorted unique global user ids per shard
    owner: np.ndarray             # int64[n] — the one shard seeding each user
    imbalance: float              # max/mean assigned cluster-size load
    version: int = -1             # index.version at derivation (journal
                                  # floor for extend_plan's scoped scans)
    resident_configs: int = 0     # tiered residency: only clusters of hash
                                  # configurations < this contribute
                                  # residents (0 = all t configurations)

    @property
    def base_n(self) -> int:
        """Users covered by this plan (== index.n when it was derived)."""
        return len(self.owner)

    def validate(self) -> "ShardPlan":
        """Assert the ``owner ∈ residents`` invariant.

        Every user's routed seeds are explored ONLY on the shard owning
        it (:meth:`ShardedDescent.shard_seeds`); if that shard does not
        host the user's rows, ``_g2l`` maps the seed to PAD and the
        whole basin silently vanishes. Derivation paths call this once
        per plan (the per-insert delta sync keeps the invariant by
        construction and skips the O(n log n) check).
        """
        for s, res in enumerate(self.residents):
            owned = np.flatnonzero(self.owner == s)
            hosted = np.isin(owned, res, assume_unique=False)
            if not hosted.all():
                bad = owned[~hosted][:8]
                raise AssertionError(
                    f"shard {s} owns users it does not host "
                    f"(e.g. {bad.tolist()}): their owner-partitioned "
                    f"seeds would be silently dropped")
        return self


def plan_shards(index: KNNIndex, n_shards: int, *,
                resident_configs: int = 0) -> ShardPlan:
    """LPT bin-packing of FRH clusters onto ``n_shards`` serving shards.

    Serving cost is linear in resident rows (descent gathers + scoring),
    so clusters are weighed by member count — unlike the build, whose
    brute-force cost is quadratic. Besides the (overlapping) resident
    sets, the plan fixes a disjoint *ownership*: every user belongs to
    exactly one shard — the shard of the largest cluster claiming it —
    which is where routed seeds naming that user are explored.

    ``resident_configs`` = m > 0 restricts residency (and ownership
    claims) to clusters of the first m hash configurations — tiered
    residency. With t configurations every user is resident on up to t
    shards; a subset trades a little recall (fewer local rows → more
    cross-shard edges dropped) for ~t/m per-shard memory. Users in no
    selected cluster ride the leftover stride, so coverage stays total;
    routing is untouched (seeds from any configuration descend on their
    owner shard).
    """
    rc = resident_configs if 0 < resident_configs < index.t else 0
    sizes = index.cluster_sizes().astype(np.float64)
    res_cluster = (np.asarray(index.cluster_config) < rc if rc
                   else np.ones(index.n_clusters, dtype=bool))
    eff = np.where(res_cluster, sizes, 0.0)
    assign = lpt_assign(eff, n_shards)
    residents: list[np.ndarray] = []
    covered = np.zeros(index.n, dtype=bool)
    for s in range(n_shards):
        mems = [index.cluster_users(ci)
                for ci in np.flatnonzero((assign == s) & res_cluster)]
        res = (np.unique(np.concatenate(mems)).astype(np.int64)
               if mems else np.zeros(0, np.int64))
        res = res[(res >= 0) & (res < index.n)]
        residents.append(res)
        covered[res] = True
    owner = np.full(index.n, -1, dtype=np.int64)
    for ci in np.argsort(-eff, kind="stable"):  # big clusters claim first
        if not res_cluster[ci]:
            continue  # non-resident configurations cannot claim owners
        mem = index.cluster_users(int(ci))
        mem = mem[(mem >= 0) & (mem < index.n)]
        free = mem[owner[mem] < 0]
        owner[free] = assign[ci]
    # Unclustered users (singleton clusters are dropped at build; fresh
    # inserts may not be registered yet; non-resident configurations
    # under tiered residency) still need a home shard. The same stride
    # assigns residency AND ownership, so ``owner ∈ residents`` holds by
    # construction — ownership is never handed to a shard that does not
    # host the user's rows (that would silently drop its seeds).
    leftovers = np.flatnonzero(~covered)
    if len(leftovers):
        residents = [np.union1d(res, leftovers[s::n_shards])
                     for s, res in enumerate(residents)]
        for s in range(n_shards):
            owner[leftovers[s::n_shards]] = s
    # Balance metric: assigned resident cluster-size mass per shard
    # (residency alone under-reports skew — clusters overlap across
    # configurations; non-resident configurations carry no rows).
    loads = lpt_loads(eff, assign, n_shards)
    imbalance = float(loads.max() / max(loads.mean(), 1e-9))
    return ShardPlan(n_shards=n_shards, cluster_shard=assign,
                     residents=residents, owner=owner, imbalance=imbalance,
                     version=index.version,
                     resident_configs=rc).validate()


def extend_plan(base: ShardPlan, index: KNNIndex) -> ShardPlan:
    """Extend a frozen partition to the index's current state.

    The base assignment never re-balances (that would reshuffle resident
    tensors wholesale); growth follows deterministic rules that are pure
    functions of (base, current index) — so incremental journal-driven
    extension and this one-shot re-derivation agree exactly:

    * clusters unseen by ``base`` go round-robin: shard ``ci % S``;
    * users unseen by ``base`` live on (and are owned by) their home
      shard ``u % S``, plus every shard whose clusters register them;
    * membership is append-only, so resident sets only grow — a user
      never migrates off a shard until a fresh :func:`plan_shards`
      (the background re-balancer's blue/green swap,
      ``query/rebalance.py``, is that one exception).

    Membership scans are scoped by the journal: only clusters born or
    membership-touched since ``base`` was derived can contribute
    residents beyond ``base.residents`` (an untouched base cluster's
    members are already in it), so the one-shot re-derivation costs
    O(journal + new clusters) scans instead of O(S·C). When the
    membership journal no longer reaches back to ``base.version`` the
    full scan runs instead — same result, never a wrong one.
    """
    S = base.n_shards
    base_nc = len(base.cluster_shard)
    n = index.n
    rc = base.resident_configs
    cluster_shard = np.concatenate([
        base.cluster_shard,
        np.arange(base_nc, index.n_clusters, dtype=np.int64) % S])
    res_cluster = (np.asarray(index.cluster_config) < rc if rc
                   else np.ones(index.n_clusters, dtype=bool))
    owner = np.concatenate([
        base.owner, np.arange(base.base_n, n, dtype=np.int64) % S])
    home = np.arange(base.base_n, n, dtype=np.int64)
    mems = (index.members_added_since(base.version)
            if base.version >= 0 else None)
    if mems is None:  # journal expired (or a pre-journal plan): full scan
        scan = [np.flatnonzero((cluster_shard == s) & res_cluster)
                for s in range(S)]
    else:
        touched = ({int(ci) for ci, _ in mems}
                   | set(range(base_nc, index.n_clusters)))
        scan = [sorted(ci for ci in touched
                       if cluster_shard[ci] == s and res_cluster[ci])
                for s in range(S)]
    residents = []
    for s in range(S):
        parts = [base.residents[s], home[home % S == s]]
        for ci in scan[s]:
            mem = index.cluster_users(int(ci)).astype(np.int64)
            parts.append(mem[(mem >= 0) & (mem < n)])
        residents.append(np.unique(np.concatenate(parts)))
    sizes = index.cluster_sizes().astype(np.float64)
    loads = lpt_loads(np.where(res_cluster, sizes, 0.0), cluster_shard, S)
    imbalance = float(loads.max() / max(loads.mean(), 1e-9))
    return ShardPlan(n_shards=S, cluster_shard=cluster_shard,
                     residents=residents, owner=owner, imbalance=imbalance,
                     version=base.version, resident_configs=rc).validate()


class ShardedDescent:
    """Per-shard local subgraphs + the descent/merge program over them.

    Owned by a :class:`~repro.query.plan.DescentPlan`'s sharded
    placement; :meth:`sync` repairs the resident tensors incrementally
    after index mutations (see the module docstring) so an insert burst
    costs row scatters, not a rebuild — and a sharded engine never holds
    a full-index device copy.
    """

    def __init__(self, index: KNNIndex, n_shards: int,
                 plan: ShardPlan | None = None, use_mesh: bool | None = None,
                 oversample: float = 1.5, resident_configs: int = 0):
        assert n_shards >= 1
        self.index = index
        self.oversample = oversample
        self.base_plan = plan or plan_shards(
            index, n_shards, resident_configs=resident_configs)
        self.plan = self.base_plan
        # Bumped by every blue/green swap (query/rebalance.py): all
        # device tensors + plan + pending beam remap move together
        # between scheduler steps, so a generation is never observed
        # half-swapped.
        self.generation = 0
        # Degraded-serving mask (repro/faults): True shards are down —
        # their owned seeds drop at shard_seeds and their merge lanes
        # are neutralized, so survivors keep answering (bounded recall
        # loss) until the failover rebuild swaps the shard back in.
        self.dead = np.zeros(self.plan.n_shards, dtype=bool)
        S = self.plan.n_shards
        if use_mesh is None:  # auto: one device per shard when available
            use_mesh = S > 1 and jax.device_count() >= S
        self.mesh = None
        self._sharding = None
        if use_mesh:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.mesh = jax.sharding.Mesh(
                np.asarray(jax.devices()[:S]), ("shards",))
            self._sharding = lambda ndim: NamedSharding(
                self.mesh, P("shards", *([None] * (ndim - 1))))
        # Pending old-local → new-local id remap for in-flight slot
        # beams ([S, cap-at-snapshot] or None); see take_beam_remap().
        self._beam_remap: np.ndarray | None = None
        self._materialize()

    # -- tensor materialization / repair -----------------------------------

    @staticmethod
    def _remap(g2l_row: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Global → shard-local ids; non-resident targets become PAD."""
        safe = np.where(ids == PAD_ID, 0, ids)
        return np.where(ids == PAD_ID, PAD_ID, g2l_row[safe])

    def _shard_block(self, s: int, cap: int, src=None):
        """Host tensors of shard ``s`` at ``cap`` rows (rebuild unit).

        ``src`` overrides WHERE row content is read from: anything with
        ``graph_ids / rev_ids / words / card / tombstone`` [n]-row
        arrays — by default the index itself, during a re-balance swap
        the symmetric-merge reconstruction of the old shard subgraphs
        (:func:`repro.query.rebalance.merge_subgraph_rows`). Shapes and
        the g2l width always come from the index.
        """
        ix = self.index
        if src is None:
            src = ix
        res = self.plan.residents[s]
        m = len(res)
        kg, kr = ix.k, ix.rev_ids.shape[1]
        W = ix.words.shape[1]
        l2g = np.full(cap, PAD_ID, dtype=np.int32)
        l2g[:m] = res
        # Capacity-width (not n-width): the map then grows only on the
        # index's own doubling boundaries, so per-insert delta syncs
        # never re-copy the whole [S, n] table.
        g2l = np.full(ix.capacity, PAD_ID, dtype=np.int32)
        g2l[res] = np.arange(m, dtype=np.int32)
        graph = np.full((cap, kg), PAD_ID, dtype=np.int32)
        rev = np.full((cap, kr), PAD_ID, dtype=np.int32)
        words = np.zeros((cap, W), dtype=np.uint32)
        card = np.zeros(cap, dtype=np.int32)
        tomb = np.zeros(cap, dtype=bool)
        graph[:m] = self._remap(g2l, src.graph_ids[res])
        rev[:m] = self._remap(g2l, src.rev_ids[res])
        words[:m] = src.words[res]
        card[:m] = src.card[res]
        tomb[:m] = src.tombstone[res]
        return l2g, g2l, graph, rev, words, card, tomb

    def _materialize(self, src=None):
        """Full (re)build of every shard's resident tensors.

        First use, capacity crossings, and journal-expiry fall back here;
        steady-state mutations go through :meth:`sync`'s delta path. Each
        shard's subgraph is pinned to its device once when a mesh is
        active — per-call resharding would move the whole index every
        wave.
        """
        ix = self.index
        S = self.plan.n_shards
        cap = max(capacity_of(len(r), minimum=64)
                  for r in self.plan.residents)
        self.cap = cap
        blocks = [self._shard_block(s, cap, src=src) for s in range(S)]
        self._g2l = np.stack([b[1] for b in blocks])
        arrays = (
            np.stack([b[2] for b in blocks]),   # l_graph
            np.stack([b[3] for b in blocks]),   # l_rev
            np.stack([b[4] for b in blocks]),   # l_words
            np.stack([b[5] for b in blocks]),   # l_card
            np.stack([b[0] for b in blocks]),   # l2g
            np.stack([b[6] for b in blocks]),   # l_tomb
        )
        self._dev = tuple(self._pin(a) for a in arrays)
        self.version = ix.version
        self._n_seen = ix.n

    def _pin(self, a):
        if self._sharding is not None:
            return jax.device_put(a, self._sharding(np.ndim(a)))
        return jnp.asarray(a)

    def sync(self) -> str:
        """Repair device state to the index's current version.

        Returns "noop" | "delta" | "rebuild". The delta path consumes
        the index's row + membership journals and scatters only touched
        rows into affected shards; see the module docstring for when a
        rebuild (full or per-shard) is forced instead.
        """
        ix = self.index
        if self.version == ix.version:
            return "noop"
        # Snapshot the local→global map before any mutation: if local
        # ids shift (per-shard rematerialization), in-flight slot beams
        # hold stale locals and need the old→new remap this produces.
        old_l2g = np.asarray(self._dev[4])
        rows = ix.rows_changed_since(self.version)
        mems = ix.members_added_since(self.version)
        tombs = ix.tombstones_since(self.version)
        if rows is None or mems is None or tombs is None:  # journal expired
            self.plan = extend_plan(self.base_plan, ix)
            self._materialize()
            self._record_remap(old_l2g)
            return "rebuild"
        # Liveness flips always ride the row journal too (remove_user and
        # free-row reuse journal the flipped row), so rows ⊇ tombs when
        # both journals reach back — the union is defensive.
        rows = rows | tombs
        old_n = self._n_seen
        S = self.plan.n_shards
        # Incremental plan extension (== extend_plan(base_plan, ix);
        # the bitwise-vs-rebuild property test locks this equality down).
        cluster_shard = np.concatenate([
            self.plan.cluster_shard,
            np.arange(len(self.plan.cluster_shard), ix.n_clusters,
                      dtype=np.int64) % S])
        owner = np.concatenate([
            self.plan.owner, np.arange(old_n, ix.n, dtype=np.int64) % S])
        g2l = self._g2l
        if g2l.shape[1] < ix.n:  # index crossed a doubling boundary
            g2l = np.pad(g2l, ((0, 0), (0, ix.capacity - g2l.shape[1])),
                         constant_values=PAD_ID)
        rc = self.plan.resident_configs
        adds: list[set[int]] = [set() for _ in range(S)]
        for u in range(old_n, ix.n):
            adds[u % S].add(u)
        for ci, u in mems:
            if rc and int(ix.cluster_config[ci]) >= rc:
                continue  # tiered residency: configuration not resident
            s = int(cluster_shard[ci])
            if g2l[s, u] == PAD_ID:
                adds[s].add(u)
        residents = []
        stale: list[int] = []  # shards whose old rows need a remap pass
        for s in range(S):
            new = np.array(sorted(a for a in adds[s]
                                  if g2l[s, a] == PAD_ID), dtype=np.int64)
            if len(new) and new[0] < old_n:
                # A pre-existing user gained residency here (cohort
                # refresh): its in-edges on this shard predate the row
                # journal window, so the whole shard remaps.
                stale.append(s)
                residents.append(np.unique(
                    np.concatenate([self.plan.residents[s], new])))
            elif len(new):
                residents.append(
                    np.concatenate([self.plan.residents[s], new]))
            else:
                residents.append(self.plan.residents[s])
        # Imbalance stays stale on the delta path (cluster_sizes +
        # lpt_loads are O(members) host work per sync — per INSERT under
        # a sharded engine); rebuilds and extend_plan refresh it.
        self.plan = ShardPlan(
            n_shards=S, cluster_shard=cluster_shard, residents=residents,
            owner=owner, imbalance=self.plan.imbalance,
            version=self.plan.version, resident_configs=rc)
        cap = max(capacity_of(len(r), minimum=64) for r in residents)
        if cap != self.cap:  # doubling boundary: shapes change anyway
            self._materialize()
            self._record_remap(old_l2g)
            return "rebuild"
        self._g2l = g2l
        dev = list(self._dev)
        for s in range(S):
            if s in stale:
                l2g_b, g2l_b, graph, rev, words, card, tomb = \
                    self._shard_block(s, cap)
                self._g2l[s] = g2l_b
                updates = (graph, rev, words, card, l2g_b, tomb)
                dev = [a.at[s].set(jnp.asarray(u))
                       for a, u in zip(dev, updates)]
                continue
            res = residents[s]
            # Delta adds are all fresh rows (ids >= old_n) here, so the
            # sorted resident array grew by pure appends — existing
            # local indices are untouched.
            new = res[np.searchsorted(res, old_n):]
            m_old = len(res) - len(new)
            if len(new):
                self._g2l[s, new] = np.arange(m_old, len(res),
                                              dtype=np.int32)
            # Touched rows resident here: journaled mutations + the new
            # rows themselves (their adjacency may also reference other
            # fresh residents, so remap with the UPDATED g2l).
            touch = np.array(sorted({int(r) for r in rows
                                     if g2l_local(self._g2l[s], r)}
                                    | set(int(u) for u in new)),
                             dtype=np.int64)
            if not len(touch):
                continue
            loc = self._g2l[s, touch]
            li = jnp.asarray(loc.astype(np.int32))
            gr = self._remap(self._g2l[s], ix.graph_ids[touch])
            rv = self._remap(self._g2l[s], ix.rev_ids[touch])
            dev[0] = dev[0].at[s, li].set(jnp.asarray(gr))
            dev[1] = dev[1].at[s, li].set(jnp.asarray(rv))
            dev[2] = dev[2].at[s, li].set(jnp.asarray(ix.words[touch]))
            dev[3] = dev[3].at[s, li].set(jnp.asarray(ix.card[touch]))
            dev[4] = dev[4].at[s, li].set(
                jnp.asarray(touch.astype(np.int32)))
            dev[5] = dev[5].at[s, li].set(jnp.asarray(ix.tombstone[touch]))
        if self._sharding is not None:  # keep the per-device pinning
            dev = [a if a.sharding == self._sharding(a.ndim)
                   else jax.device_put(a, self._sharding(a.ndim))
                   for a in dev]
        self._dev = tuple(dev)
        self.version = ix.version
        self._n_seen = ix.n
        if stale:  # locals shifted on the rematerialized shards
            self._record_remap(old_l2g)
        return "delta"

    def adopt_plan(self, plan: ShardPlan, src=None) -> None:
        """Blue/green swap: install a freshly derived partition and
        rebuild every resident tensor in one shot.

        The re-balancer (``query/rebalance.py``) calls this BETWEEN
        scheduler steps with a fresh :func:`plan_shards` — the one
        reshard where residency is NOT monotone (rows migrate off
        shards). ``src`` supplies row content reconstructed by symmetric
        merge of the old shard subgraphs; None re-scatters from the
        index (bitwise the same tensors — the merge is audited against
        the index, see ``merge_subgraph_rows``). In-flight slot beams
        survive through the recorded old→new local map: rows still
        resident keep descending under new labels, evicted rows drop to
        PAD (their sims are masked to NEG_INF when the continuous plan
        applies the map). The plan, tensors, g2l, and pending remap all
        move in this one host-side call, so no request ever observes a
        half-swapped generation.
        """
        old_l2g = np.asarray(self._dev[4])
        self.base_plan = plan
        self.plan = plan
        self._materialize(src=src)
        self._record_remap(old_l2g)
        self.generation += 1
        # The swap installs freshly rebuilt tensors for every shard; the
        # failover manager re-masks any shard that is still unhealthy.
        self.dead = np.zeros(self.plan.n_shards, dtype=bool)

    def _record_remap(self, old_l2g: np.ndarray):
        """Accumulate an old-local → new-local id map after a reshard
        that may have shifted local ids. Under the frozen-base extension
        residency is monotone, so every previously-resident row still
        has a local id — the map is total on live lanes (PAD stays
        PAD). After a re-balance swap (:meth:`adopt_plan`) rows may have
        left their shard: those lanes map to PAD, and the continuous
        plan masks their sims out of the beam."""
        S = old_l2g.shape[0]
        rows = np.arange(S)[:, None]
        safe = np.where(old_l2g == PAD_ID, 0, old_l2g)
        mp = np.where(old_l2g == PAD_ID, PAD_ID, self._g2l[rows, safe])
        if self._beam_remap is not None:  # compose with an unconsumed map
            prev = self._beam_remap
            psafe = np.where(prev == PAD_ID, 0, prev)
            mp = np.where(prev == PAD_ID, PAD_ID, mp[rows, psafe])
        self._beam_remap = mp.astype(np.int32)

    def take_beam_remap(self) -> np.ndarray | None:
        """Consume the pending old→new local-id map (int32[S, old_cap]),
        or None when local ids were stable since the last take. The
        continuous plan applies it to in-flight per-shard slot beams
        before the next hop — beam *contents* (global identity + sims)
        are unchanged, only their local labels move, so results stay
        bitwise wave-identical across mid-stream reshards. Lanes the map
        sends to PAD (rows evicted by a re-balance swap) must also have
        their sims masked to NEG_INF by the consumer."""
        mp, self._beam_remap = self._beam_remap, None
        return mp

    # -- serving -----------------------------------------------------------

    @property
    def n_shards(self) -> int:
        return self.plan.n_shards

    def set_dead(self, mask) -> None:
        """Install the degraded-serving mask (bool[n_shards]); dead
        shards stop receiving seeds and stop contributing to merges
        from the next descent on."""
        mask = np.asarray(mask, dtype=bool)
        assert mask.shape == (self.plan.n_shards,), mask.shape
        self.dead = mask.copy()

    def shard_seeds(self, seeds: np.ndarray) -> np.ndarray:
        """Partition routed global seeds by ownership and remap to local.

        Returns int32[S, q, S_cols]: seed ids in shard-local coordinates;
        a seed appears on exactly the shard owning that user (PAD
        elsewhere), so the fleet explores disjoint basins. Seeds owned
        by a dead shard are dropped entirely — their basins are the
        degraded-mode recall loss — rather than re-homed: survivors do
        not host those rows (tiered residency may not host them at
        all), and a deterministic drop is what the masked-seed parity
        test pins against a shard-excluded rebuild.
        """
        S = self.n_shards
        safe = np.where(seeds == PAD_ID, 0, seeds)
        owned = ((self.plan.owner[safe][None]
                  == np.arange(S)[:, None, None])
                 & (seeds[None] != PAD_ID))              # [S, q, cols]
        if self.dead.any():
            owned &= ~self.dead[:, None, None]
        local = self._g2l[:, safe]
        return np.where(owned, local, PAD_ID)

    def descend(self, q_words, q_card, seeds: np.ndarray, *,
                k: int, beam: int, hops: int, kernel: bool = False,
                dma: bool = False, tag=None):
        """Route-seeded descent on every shard + cross-shard top-k merge.

        ``seeds`` are global ids (router output, PAD padded); ``beam`` is
        the single-device frontier width, divided among shards (with
        ``self.oversample`` slack, floored at k). ``kernel`` selects the
        fused Pallas hop, ``dma`` its HBM-resident placement
        (bitwise-identical results either way). ``tag`` (a
        hashable plan key) lands in the jit-trace counter so
        ``sched.trace.compile_count`` can assert compile-once per plan.
        Returns (ids int32[q, k], sims float32[q, k]) in global ids.
        As a side effect, ``self.last_hop_stats`` holds this call's
        per-query ``(n_scored, dma_bytes, bytes_saved)`` i32[q, 3],
        summed over ALIVE shards (the plan reads it right after the
        call to feed serving stats).
        """
        l_seeds = jnp.asarray(self.shard_seeds(seeds))
        shard_beam = self.shard_beam(beam, k)
        args = (*self._dev, jnp.asarray(q_words), jnp.asarray(q_card),
                l_seeds)
        if self.mesh is not None:
            program = _mesh_program(self.mesh, k=k, beam=shard_beam,
                                    hops=hops, kernel=kernel, dma=dma,
                                    tag=tag)
            ids, sims, stats = program(*args)
        else:
            ids, sims, stats = _vmapped_descent(
                *args, k=k, beam=shard_beam, hops=hops, kernel=kernel,
                dma=dma, tag=tag)
        if self.dead.any():
            # Belt and braces on top of the seed drop: a dead shard
            # contributes nothing to the merge even if a stale seed
            # slipped in (e.g. a continuous slot admitted pre-failure).
            alive = jnp.asarray(~self.dead)[:, None, None]
            ids = jnp.where(alive, ids, PAD_ID)
            sims = jnp.where(alive, sims, NEG_INF)
            stats = jnp.where(alive, stats, 0)
        self.last_hop_stats = np.asarray(jnp.sum(stats, axis=0))
        return _merge_shard_topk(ids, sims, k)

    @property
    def devices(self) -> set:
        """Devices holding the resident shard tensors (one per shard on
        a mesh, the single default device otherwise)."""
        return {d for a in self._dev for d in a.devices()}

    def shard_beam(self, beam: int, k: int) -> int:
        """Per-shard frontier width for a fleet-level ``beam``."""
        return max(k, int(np.ceil(self.oversample * beam / self.n_shards)))

    def resident_bytes(self) -> list[int]:
        """Per-shard bytes of RESIDENT rows (adjacency + reverse +
        fingerprint words + card + l2g + tombstone) — the quantity
        tiered residency trades recall against (padding to ``cap``
        excluded: it is shared dead weight, not per-row cost)."""
        per_row = self.index.row_bytes
        return [len(r) * per_row for r in self.plan.residents]


def g2l_local(g2l_row: np.ndarray, r: int) -> bool:
    """True when global row ``r`` is resident in this shard's map."""
    return r < len(g2l_row) and g2l_row[r] != PAD_ID


def _per_shard(graph, rev, words, card, l2g, tomb, q_words, q_card, seeds,
               *, k, beam, hops, kernel=False, dma=False):
    """One shard's descent; results mapped back to global ids."""
    ids, sims, stats = descent_kernel(graph, rev, words, card,
                                      q_words, q_card, seeds,
                                      k=k, beam=beam, hops=hops,
                                      kernel=kernel, dma=dma, tomb=tomb)
    safe = jnp.where(ids == PAD_ID, 0, ids)
    return jnp.where(ids == PAD_ID, PAD_ID, l2g[safe]), sims, stats


@functools.partial(jax.jit,
                   static_argnames=("k", "beam", "hops", "kernel", "dma",
                                    "tag"))
def _vmapped_descent(l_graph, l_rev, l_words, l_card, l2g, l_tomb,
                     q_words, q_card, l_seeds, *, k, beam, hops,
                     kernel=False, dma=False, tag=None):
    """Single-device fallback: the shard axis is a vmap axis (the fused
    Pallas hop batches through its pallas_call batching rule)."""
    trace.bump(("query_wave_sharded", tag, l_graph.shape[0],
                q_words.shape[0], k, beam, hops, kernel, dma))
    return jax.vmap(
        lambda g, r, w, c, m, t, s: _per_shard(
            g, r, w, c, m, t, q_words, q_card, s, k=k, beam=beam,
            hops=hops, kernel=kernel, dma=dma)
    )(l_graph, l_rev, l_words, l_card, l2g, l_tomb, l_seeds)


@functools.lru_cache(maxsize=64)
def _mesh_program(mesh, *, k, beam, hops, kernel=False, dma=False,
                  tag=None):
    """SPMD path: one shard per device, no collectives inside (the merge
    happens after the shard-parallel top-k, mirroring
    distributed_local_knn's reduce phase). Returns a jitted callable.

    Cached at module level (jax.sharding.Mesh hashes by devices + axis
    names), so resharding after an insert burst reuses the compiled
    program as long as shapes and (k, beam, hops) are unchanged —
    symmetric with the module-level jitted ``_vmapped_descent``."""
    from jax.sharding import PartitionSpec as P

    def device_fn(g, r, w, c, m, t, qw, qc, s):
        trace.bump(("query_wave_sharded", tag, len(mesh.devices),
                    qw.shape[0], k, beam, hops, kernel, dma))
        ids, sims, stats = _per_shard(g[0], r[0], w[0], c[0], m[0], t[0],
                                      qw, qc, s[0],
                                      k=k, beam=beam, hops=hops,
                                      kernel=kernel, dma=dma)
        return ids[None], sims[None], stats[None]

    in_specs = (P("shards", None, None), P("shards", None, None),
                P("shards", None, None), P("shards", None),
                P("shards", None), P("shards", None),
                P(), P(), P("shards", None, None))
    out_specs = (P("shards", None, None), P("shards", None, None),
                 P("shards", None, None))
    return jax.jit(jax.shard_map(device_fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_shard_topk(ids, sims, k: int):
    """[S, q, k'] per-shard results → global top-k per query."""
    S, q, kk = ids.shape
    flat_ids = jnp.swapaxes(ids, 0, 1).reshape(q, S * kk)
    flat_sims = jnp.swapaxes(sims, 0, 1).reshape(q, S * kk)
    return merge_topk(flat_ids, flat_sims, k)
