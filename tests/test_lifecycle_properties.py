"""Hypothesis battery for the lifecycle subsystem: ANY interleaving of
insert / remove / update / repair / query stays coherent across the
whole plan matrix —

* results parity: an engine on any (batching × scorer) combination
  returns BITWISE the same (ids AND sims) as the wave × jnp reference
  driven through the identical interleaving (batching and scorer are
  results-transparent, and every mutation routes through both engines'
  own plans identically);
* no served id is tombstoned at serve time;
* device state equals a from-scratch rebuild of the surviving rows:
  the sharded placement's delta-maintained shard tensors (including the
  per-shard tombstone column) match a fresh rematerialization
  (tests/test_plan.py comparator), and the single placement's
  journal-scattered padded copies match a fresh full upload.
"""
import copy

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # [test] extra; skip, don't break collection
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core.params import C2Params
from repro.data.synthetic import make_dataset
from repro.query.engine import QueryConfig, QueryEngine, QueryRequest
from repro.query.plan import DescentPlan

from test_plan import _assert_matches_rebuild  # same-dir test module

OPS = ("insert", "remove", "update", "repair", "query", "serve")


@pytest.fixture(scope="module")
def small_index():
    from repro.query.index import build_index

    ds = make_dataset("synth", scale=0.05, seed=5)
    return build_index(ds, C2Params(k=8, b=64, t=4, max_cluster=32))


@pytest.fixture(scope="module")
def profiles():
    qds = make_dataset("synth", scale=0.05, seed=7)
    return [qds.profile(u) for u in range(24)]


def _assert_single_matches_rebuild(engine):
    """Journal-scattered single-placement device copies == a fresh full
    upload of the same host index, bitwise (tomb column included)."""
    delta = engine.plan._sync_single()
    fresh = DescentPlan(engine.index, engine.plan.spec)._sync_single()
    for a, b, name in zip(delta, fresh,
                          ("graph", "rev", "words", "card", "tomb")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def _drive(engine, ops, profiles, seed):
    """Apply an op sequence; mutation targets are drawn from a seeded
    rng over the engine's own live set, so two engines with identical
    result semantics walk identical index trajectories."""
    rng = np.random.default_rng(seed)
    n_ins = 0
    for op in ops:
        ix = engine.index
        if op == "insert":
            engine.insert(profiles[8 + (n_ins % 16)])
            n_ins += 1
        elif op == "remove":
            alive = ix.alive_ids()
            if len(alive) > ix.k + 2:
                engine.remove_user(int(rng.choice(alive)))
        elif op == "update":
            alive = ix.alive_ids()
            engine.update_user(int(rng.choice(alive)),
                               profiles[int(rng.integers(0, 8))])
        elif op == "repair":
            engine.lifecycle.repair()
        elif op == "query":
            engine.query_batch(profiles[:4])
        else:  # serve through the scheduler loop (maintain fires)
            first = len(engine.done)
            for i in range(3):
                engine.submit(QueryRequest(
                    rid=i, profile=np.asarray(profiles[i], np.int32)))
            engine.run()
            # No tombstoned id is served: checked at serve time, since a
            # later remove may legitimately take a user served here.
            tomb = engine.index.tombstone
            for r in engine.done[first:]:
                assert not tomb[r.ids[r.ids != -1]].any()
    return engine.query_batch(profiles[:4])  # the final probe wave


@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.function_scoped_fixture])
@given(ops=st.lists(st.sampled_from(OPS), min_size=1, max_size=10),
       shards=st.integers(min_value=1, max_value=3),
       continuous=st.booleans(),
       kernel=st.booleans(),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_any_interleaving_matches_reference_and_rebuild(
        small_index, profiles, ops, shards, continuous, kernel, seed):
    def build(cont, kern):
        eng = QueryEngine(copy.deepcopy(small_index),
                          QueryConfig(k=8, beam=12, hops=2, shards=shards,
                                      slots=8, continuous=cont, kernel=kern,
                                      refresh_every=10**9))
        eng.query_batch(profiles[:4])  # freeze the base plan
        return eng

    eng = build(continuous, kernel)
    ids, sims = _drive(eng, ops, profiles, seed)

    # No tombstoned id is served by the probe wave (scheduler runs are
    # checked as they are served, in _drive).
    tomb = eng.index.tombstone
    live = ids[ids != -1]
    assert not tomb[live].any()

    # Device state == from-scratch rebuild over the surviving rows.
    if shards > 1:
        _assert_matches_rebuild(eng)
    else:
        _assert_single_matches_rebuild(eng)

    # Batching × scorer are results-transparent under churn: the wave ×
    # jnp reference walks the identical trajectory, bitwise.
    if continuous or kernel:
        ref = build(False, False)
        ref_ids, ref_sims = _drive(ref, ops, profiles, seed)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(sims, ref_sims)
        assert eng.index.version == ref.index.version
        np.testing.assert_array_equal(eng.index.graph_ids,
                                      ref.index.graph_ids)
        np.testing.assert_array_equal(eng.index.tombstone,
                                      ref.index.tombstone)
