"""Deterministic battery for the fault-tolerance stack (repro/faults/):
the fault plan grammar + injector schedule semantics, the per-shard
health machine (backoff sequence, caps, transient recovery), degraded
serving (seed masking, result stamping, cache exclusion), the failover
rebuild + blue/green swap, the write-ahead log + crash store (bitwise
recovery), the save/load journal-persistence fix, and the injectable
engine clock. The hypothesis batteries live in
tests/test_faults_properties.py.
"""
import copy

import numpy as np
import pytest

from repro.core.params import C2Params
from repro.data.synthetic import make_dataset
from repro.faults import (CrashStore, EngineCrash, FaultInjector, FaultPlan,
                          FleetHealth, HealthConfig, WriteAheadLog, replay)
from repro.query.engine import QueryConfig, QueryEngine, QueryRequest
from repro.query.index import _ROWS, build_index
from repro.query.router import fingerprint_profiles, profiles_to_csr, route
from repro.query.sharded import ShardedDescent
from repro.sched import ManualClock
from repro.types import PAD_ID


@pytest.fixture(scope="module")
def index():
    ds = make_dataset("synth", scale=0.1, seed=3)
    return build_index(ds, C2Params(k=10, b=64, t=8, max_cluster=48))


@pytest.fixture(scope="module")
def query_profiles():
    qds = make_dataset("synth", scale=0.1, seed=77)
    return [qds.profile(u) for u in range(32)]


@pytest.fixture(scope="module")
def insert_profiles():
    ids = make_dataset("synth", scale=0.1, seed=99)
    return [ids.profile(u) for u in range(32)]


def _serve(engine, profiles):
    for rid, p in enumerate(profiles):
        engine.submit(QueryRequest(rid=rid, profile=p))
    engine.run()
    return {r.rid: (np.asarray(r.ids), np.asarray(r.sims))
            for r in engine.done[-len(profiles):]}


def _assert_same(a, b, msg=""):
    assert set(a) == set(b)
    for rid in a:
        np.testing.assert_array_equal(a[rid][0], b[rid][0],
                                      err_msg=f"{msg} ids rid={rid}")
        np.testing.assert_array_equal(a[rid][1], b[rid][1],
                                      err_msg=f"{msg} sims rid={rid}")


# -- fault plan grammar ----------------------------------------------------

def test_fault_plan_parse_roundtrip():
    spec = "kill:1@4;fail:0@2+3;slow:2@5+2:1.5;crash@9"
    plan = FaultPlan.parse(spec)
    kinds = sorted(e.kind for e in plan.events)
    assert kinds == ["crash", "fail", "kill", "slow"]
    # describe() re-parses to the same schedule (canonical order).
    assert FaultPlan.parse(plan.describe()) == plan
    slow = next(e for e in plan.events if e.kind == "slow")
    assert slow.latency_s == pytest.approx(1.5e-3)
    assert slow.duration == 2


@pytest.mark.parametrize("bad", [
    "kill:1", "fail:0@2", "slow:1@2+3", "crash@x", "boom:0@1", "kill:@3"])
def test_fault_plan_rejects_bad_specs(bad):
    with pytest.raises(ValueError):
        FaultPlan.parse(bad)


def test_fault_plan_random_is_seeded():
    a = FaultPlan.random(4, 20, seed=11)
    b = FaultPlan.random(4, 20, seed=11)
    c = FaultPlan.random(4, 20, seed=12)
    assert a == b
    assert a != c


# -- injector schedule semantics -------------------------------------------

def test_injector_schedule_windows():
    inj = FaultInjector(FaultPlan.parse("kill:0@2;fail:1@1+2"))
    down = []
    for _ in range(5):
        inj.begin_step()
        down.append((inj.shard_down(0), inj.shard_down(1)))
    # kill: permanent from step 2; fail: steps 1-2 only.
    assert down == [(False, False), (False, True), (True, True),
                    (True, False), (True, False)]
    inj.clear_shard(0)  # failover cleared the fired kill
    assert not inj.shard_down(0)


def test_injector_crash_and_arm():
    inj = FaultInjector(FaultPlan.parse("crash@1"), armed=False)
    for _ in range(5):
        inj.begin_step()  # disarmed: nothing fires, step stays frozen
    assert inj.step == -1
    inj.arm()
    inj.begin_step()  # step 0
    with pytest.raises(EngineCrash):
        inj.begin_step()  # step 1
    assert inj.n_crashes == 1


def test_injector_slow_advances_manual_clock():
    clock = ManualClock()
    inj = FaultInjector(FaultPlan.parse("slow:0@1+2:250"), clock=clock)
    t = [clock()]
    for _ in range(4):
        inj.begin_step()
        t.append(clock())
    # 250ms injected at steps 1 and 2, nothing elsewhere — and no
    # real time.sleep anywhere in this test.
    deltas = np.diff(t)
    np.testing.assert_allclose(deltas, [0.0, 0.25, 0.25, 0.0])
    assert inj.n_slow_steps == 2
    assert inj.injected_latency_s == pytest.approx(0.5)


# -- health machine --------------------------------------------------------

def test_health_backoff_sequence_to_death():
    cfg = HealthConfig(max_retries=3, backoff_cap=8, recover_after=4)
    h = FleetHealth(1, cfg)
    h.observe([False])          # step 0: healthy
    assert h.state[0] == "healthy"
    h.observe([True])           # step 1: first failure -> suspect
    assert h.state[0] == "suspect"
    # Re-probes land at steps 2 (backoff 1), 4 (backoff 2), 8
    # (backoff 4); each failure doubles the backoff; the third failed
    # re-probe is the max_retries-th -> dead.
    transitions = {}
    for step in range(2, 9):
        h.observe([True])
        transitions[step] = (h.state[0], int(h.retries[0]))
    assert transitions[2] == ("suspect", 1)
    assert transitions[3] == ("suspect", 1)   # waiting out backoff 2
    assert transitions[4] == ("suspect", 2)
    assert transitions[7] == ("suspect", 2)   # waiting out backoff 4
    assert transitions[8] == ("dead", 3)
    assert h.dead_since[0] == 8
    assert h.n_deaths == 1
    assert h.backoff_steps > 0
    # Dead shards wait out the grace period before recovery.
    assert h.ready_for_recovery() == []
    for _ in range(cfg.recover_after):
        h.observe([True])
    assert h.ready_for_recovery() == [0]


def test_health_backoff_is_capped():
    cfg = HealthConfig(max_retries=50, backoff_cap=4, recover_after=4)
    h = FleetHealth(1, cfg)
    h.observe([True])
    for _ in range(40):
        h.observe([True])
    assert int(h.backoff[0]) == 4  # never exceeds the cap
    assert h.state[0] == "suspect"


def test_health_transient_failure_recovers_without_failover():
    h = FleetHealth(2, HealthConfig(max_retries=3))
    h.observe([False, True])    # shard 1 suspect
    assert h.serving_mask().tolist() == [False, True]
    h.observe([False, False])   # re-probe succeeds -> healthy again
    assert h.state[1] == "healthy"
    assert h.serving_mask().tolist() == [False, False]
    assert h.n_deaths == 0


# -- degraded serving ------------------------------------------------------

def test_masked_seed_descent_parity(index, query_profiles):
    """Killing a shard == never seeding it: descend with the dead mask
    matches descend on a healthy fleet whose seeds were pre-filtered to
    drop the dead shard's owned basins."""
    items, offsets = profiles_to_csr(query_profiles)
    qgf = fingerprint_profiles(items, offsets, index)
    seeds = route(index, items, offsets, 16)
    qw = np.asarray(qgf.words)
    qc = np.asarray(qgf.card)

    sd_dead = ShardedDescent(index, 2)
    sd_dead.set_dead([False, True])
    i1, s1 = sd_dead.descend(qw, qc, seeds, k=10, beam=32, hops=3)

    sd_ok = ShardedDescent(index, 2)
    owner = sd_ok.plan.owner
    safe = np.where(seeds == PAD_ID, 0, seeds)
    filtered = np.where((seeds != PAD_ID) & (owner[safe] == 1),
                        PAD_ID, seeds).astype(np.int32)
    i2, s2 = sd_ok.descend(qw, qc, filtered, k=10, beam=32, hops=3)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_degraded_serving_keeps_answering(index, query_profiles):
    """1 of 2 shards dead: every request is still served (stamped
    degraded), no dead-only id appears, and recall stays bounded."""
    ix = copy.deepcopy(index)
    inj = FaultInjector(FaultPlan.parse("kill:1@0"),
                        health=HealthConfig(max_retries=1, backoff_cap=1,
                                            recover_after=10**6))
    eng = QueryEngine(ix, QueryConfig(k=10, shards=2, max_wave=16),
                      clock=ManualClock(), faults=inj)
    res = _serve(eng, query_profiles)
    assert len(res) == len(query_profiles)
    recent = eng.done[-len(query_profiles):]
    assert all(r.status == "done" for r in recent)
    assert sum(r.degraded for r in recent) > 0
    assert eng.degraded
    stats = eng.failover.stats()
    assert stats["shards_down"] == 1
    deg = [r for r in recent if r.degraded]
    assert eng.recall_vs_brute_force(deg) >= 0.2  # bounded, not zero
    # Deterministic: an identical run serves identical degraded answers.
    eng2 = QueryEngine(copy.deepcopy(index),
                       QueryConfig(k=10, shards=2, max_wave=16),
                       clock=ManualClock(),
                       faults=FaultInjector(
                           FaultPlan.parse("kill:1@0"),
                           health=HealthConfig(max_retries=1, backoff_cap=1,
                                               recover_after=10**6)))
    _assert_same(res, _serve(eng2, query_profiles), "degraded determinism")


def test_degraded_results_never_cached(index, query_profiles):
    ix = copy.deepcopy(index)
    inj = FaultInjector(FaultPlan.parse("kill:1@0"),
                        health=HealthConfig(recover_after=10**6))
    eng = QueryEngine(ix, QueryConfig(k=10, shards=2, max_wave=16, cache=32),
                      clock=ManualClock(), faults=inj)
    _serve(eng, query_profiles[:8])
    _serve(eng, query_profiles[:8])  # exact repeats: would hit if cached
    cache = eng.plan.cache
    assert len(cache) == 0
    assert cache.degraded_skips > 0
    assert cache.hits == 0


def test_maintenance_defers_while_degraded(index, query_profiles):
    """Lifecycle TTL/repair and the re-balancer both stand down while a
    shard is masked out — degraded descents must not be baked into the
    graph."""
    ix = copy.deepcopy(index)
    inj = FaultInjector(FaultPlan.parse("kill:1@0"),
                        health=HealthConfig(recover_after=10**6))
    eng = QueryEngine(ix, QueryConfig(k=10, shards=2, max_wave=16, ttl=1,
                                      rebalance_every=1),
                      clock=ManualClock(), faults=inj)
    _serve(eng, query_profiles[:8])
    assert eng.degraded
    out = eng.lifecycle.maintain()
    assert out.get("deferred") and out["expired"] == 0
    assert eng.lifecycle.n_expired == 0  # TTL=1 would expire rows if live
    assert eng.rebalance.n_deferred > 0
    assert eng.rebalance.n_swaps == 0


# -- failover rebuild + swap -----------------------------------------------

def test_failover_swaps_once_and_restores_answers(index, query_profiles):
    ix = copy.deepcopy(index)
    inj = FaultInjector(FaultPlan.parse("kill:1@1"), armed=False,
                        health=HealthConfig(max_retries=2, backoff_cap=2,
                                            recover_after=3))
    eng = QueryEngine(ix, QueryConfig(k=10, shards=2, max_wave=16, cache=32),
                      clock=ManualClock(), faults=inj)
    pre = _serve(eng, query_profiles)
    flushes0 = eng.plan.cache.flushes
    inj.arm()
    _serve(eng, query_profiles)           # the kill lands mid-window
    for _ in range(24):                   # idle steps: dead -> recovered
        eng.step()
    assert eng.failover.n_failovers == 1
    assert eng.failover.health.state == ["healthy", "healthy"]
    assert not eng.degraded
    sd = eng.sharded_state()
    assert sd.generation == 1             # exactly one blue/green swap
    assert not sd.dead.any()
    assert eng.plan.cache.flushes > flushes0   # swap flushed the cache
    assert eng.failover.recovery_steps         # dwell was recorded
    assert eng.failover.last_merge_stats["excluded"] == [1]
    # Post-recovery answers are bitwise what the healthy fleet served.
    _assert_same(pre, _serve(eng, query_profiles), "post-failover")


# -- WAL + crash store -----------------------------------------------------

def test_wal_replay_is_bitwise(index, insert_profiles, tmp_path):
    ix_live = copy.deepcopy(index)
    ix_rec = copy.deepcopy(index)
    wal = WriteAheadLog(tmp_path / "wal.jsonl", append=False)
    ix_live.attach_wal(wal)
    eng = QueryEngine(ix_live, QueryConfig(k=10, refresh_every=8))
    for p in insert_profiles[:10]:  # crosses a cohort refresh at 8
        eng.insert(p)
    eng.remove_user(3)
    eng.update_user(7, insert_profiles[10])
    eng.touch(11)
    ix_live.detach_wal()
    replay(ix_rec, WriteAheadLog.read(tmp_path / "wal.jsonl"))
    assert ix_rec.version == ix_live.version
    for name in _ROWS:
        np.testing.assert_array_equal(getattr(ix_rec, name),
                                      getattr(ix_live, name), err_msg=name)
    ix_live.consolidate(), ix_rec.consolidate()
    for name in ("cluster_members", "cluster_offsets", "cluster_paths",
                 "cluster_config"):
        np.testing.assert_array_equal(getattr(ix_rec, name),
                                      getattr(ix_live, name), err_msg=name)


def test_crash_store_recovers_engine_bitwise(index, insert_profiles,
                                             query_profiles, tmp_path):
    """Crash mid-stream, recover from snapshot + WAL: index tensors AND
    served answers match a never-crashed mirror driven identically."""
    qc = QueryConfig(k=10, shards=2, max_wave=16)
    store = CrashStore(tmp_path / "store", every=3)
    eng = QueryEngine(copy.deepcopy(index), qc, clock=ManualClock(),
                      faults=FaultInjector(FaultPlan.parse("crash@5")),
                      store=store)
    mirror = QueryEngine(copy.deepcopy(index), qc, clock=ManualClock())
    crashed = False
    for t in range(10):
        for e in (eng, mirror):
            e.insert(insert_profiles[t])
            if t % 3 == 2:
                e.remove_user(10 * t)
        try:
            eng.step()
        except EngineCrash:
            crashed = True
            break
        mirror.step()
    assert crashed
    mirror.step()  # the mirror runs the step the crash pre-empted
    rec = QueryEngine.recover(tmp_path / "store", qc, clock=ManualClock())
    assert rec.index.version == mirror.index.version
    for name in _ROWS:
        np.testing.assert_array_equal(getattr(rec.index, name),
                                      getattr(mirror.index, name),
                                      err_msg=name)
    _assert_same(_serve(rec, query_profiles),
                 _serve(mirror, query_profiles), "post-recovery answers")


def test_crash_store_compaction_bounds_wal(index, insert_profiles,
                                           tmp_path):
    store = CrashStore(tmp_path / "store", every=2)
    eng = QueryEngine(copy.deepcopy(index), QueryConfig(k=10, max_wave=16),
                      clock=ManualClock(), store=store)
    for t in range(9):
        eng.insert(insert_profiles[t])
        eng.step()
    # Snapshots fired on cadence; the LIVE wal only holds the suffix
    # since the last one (about one insert's records), not the whole
    # mutation history.
    assert store.n_snapshots >= 4
    wals = sorted((tmp_path / "store").glob("wal_*.jsonl"))
    assert len(wals) == store.n_snapshots
    total = sum(len(WriteAheadLog.read(w)) for w in wals)
    assert 0 < store.wal.n_records <= total / 2


# -- satellite: save/load persists journal state ---------------------------

def test_save_load_persists_journals(index, insert_profiles, tmp_path):
    """A saved+loaded index continues the mutate/delta-sync trajectory
    bitwise-equal to the unsaved one — the journals (row / member /
    tombstone logs) now survive persistence, so the loaded side delta-
    syncs instead of silently full-rebuilding (or worse, missing
    rows)."""
    ix_a = copy.deepcopy(index)
    eng_a = QueryEngine(ix_a, QueryConfig(k=10))
    for p in insert_profiles[:4]:
        eng_a.insert(p)
    eng_a.remove_user(5)

    ix_a.save(tmp_path / "ix.npz")
    from repro.query.index import KNNIndex
    ix_b = KNNIndex.load(tmp_path / "ix.npz")
    assert ix_b.version == ix_a.version
    assert ix_b.rows_changed_since(0) == ix_a.rows_changed_since(0)
    assert ix_b.tombstones_since(0) == ix_a.tombstones_since(0)
    assert ix_b.members_added_since(0) == ix_a.members_added_since(0)

    # Same sharded plan, same further mutations: the two delta syncs
    # must land on bitwise-identical device tensors.
    sd_a = ShardedDescent(ix_a, 2)
    sd_b = ShardedDescent(ix_b, 2)
    eng_b = QueryEngine(ix_b, QueryConfig(k=10))
    for p in insert_profiles[4:8]:
        eng_a.insert(p)
        eng_b.insert(p)
    eng_a.remove_user(9), eng_b.remove_user(9)
    assert sd_a.sync() == "delta"
    assert sd_b.sync() == "delta"
    for a, b in zip(sd_a._dev, sd_b._dev):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- satellite: injectable clock -------------------------------------------

def test_manual_clock_contract():
    clock = ManualClock(start=5.0)
    assert clock() == 5.0
    clock.advance(0.25)
    assert clock() == 5.25
    clock.sleep(0.75)  # sleep == advance: no real time passes
    assert clock() == 6.0
    with pytest.raises(ValueError):
        clock.advance(-1.0)


def test_engine_latencies_deterministic_under_manual_clock(index,
                                                           query_profiles):
    def run():
        # start > 0: QueryRequest.latency treats t_submit == 0.0 as
        # "never submitted", so the epoch must not be exactly zero.
        eng = QueryEngine(copy.deepcopy(index),
                          QueryConfig(k=10, continuous=True, slots=8),
                          clock=ManualClock(start=1.0))
        for rid, p in enumerate(query_profiles[:16]):
            eng.submit(QueryRequest(rid=rid, profile=p))
            eng.clock.advance(0.001)
        eng.run()
        return [r.latency for r in eng.done[-16:]]

    a, b = run(), run()
    assert a == b  # bitwise-equal latencies: zero wall-clock in the loop
    assert all(lat is not None and lat >= 0 for lat in a)
