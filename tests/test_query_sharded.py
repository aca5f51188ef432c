"""Sharded query serving: shard-plan invariants, shard-count equivalence
vs single-device descent, mesh/vmap parity, and the serving CLI."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.params import C2Params
from repro.data.synthetic import make_dataset
from repro.eval.metrics import knn_recall
from repro.query.engine import QueryConfig, QueryEngine
from repro.query.index import build_index
from repro.query.router import fingerprint_profiles, profiles_to_csr
from repro.query.search import exact_knn
from repro.query.sharded import ShardedDescent, plan_shards
from repro.types import PAD_ID

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


@pytest.fixture(scope="module")
def index():
    ds = make_dataset("synth", scale=0.15, seed=3)
    return build_index(ds, C2Params(k=10, b=64, t=8, max_cluster=48))


@pytest.fixture(scope="module")
def query_profiles():
    qds = make_dataset("synth", scale=0.15, seed=77)
    return [qds.profile(u) for u in range(96)]


@pytest.fixture(scope="module")
def exact(index, query_profiles):
    items, offsets = profiles_to_csr(query_profiles)
    qgf = fingerprint_profiles(items, offsets, index)
    ids, _ = exact_knn(index.words, index.card, np.asarray(qgf.words),
                       np.asarray(qgf.card), 10)
    return ids


@pytest.mark.parametrize("n_shards", [2, 3, 4])
def test_shard_plan_invariants(index, n_shards):
    plan = plan_shards(index, n_shards)
    # Every cluster is assigned to exactly one shard.
    assert plan.cluster_shard.shape == (index.n_clusters,)
    assert ((plan.cluster_shard >= 0)
            & (plan.cluster_shard < n_shards)).all()
    # Every indexed user is resident on ≥ 1 shard, and owned by exactly
    # one shard where it is also resident (seeds must be explorable).
    covered = np.zeros(index.n, dtype=bool)
    for s, res in enumerate(plan.residents):
        covered[res] = True
        assert len(np.unique(res)) == len(res)
    assert covered.all()
    assert ((plan.owner >= 0) & (plan.owner < n_shards)).all()
    for s in range(n_shards):
        owned = np.flatnonzero(plan.owner == s)
        assert np.isin(owned, plan.residents[s]).all()
    assert plan.imbalance < 3.0


def test_owned_seeds_partition(index):
    sd = ShardedDescent(index, 3)
    seeds = np.array([[0, 5, PAD_ID, 17], [index.n - 1, 2, 3, PAD_ID]],
                     dtype=np.int32)
    l_seeds = sd.shard_seeds(seeds)
    assert l_seeds.shape == (3,) + seeds.shape
    live = l_seeds != PAD_ID
    # Each non-PAD global seed appears on exactly one shard.
    np.testing.assert_array_equal(live.sum(axis=0),
                                  (seeds != PAD_ID).astype(int))
    # And maps back to the same global id through that shard's l2g.
    l2g = np.asarray(sd._dev[4])
    for s in range(3):
        sel = live[s]
        np.testing.assert_array_equal(l2g[s][l_seeds[s][sel]], seeds[sel])


@pytest.mark.parametrize("n_shards", [2, 3, 4])
def test_sharded_equivalence(index, query_profiles, exact, n_shards):
    """The shard-count equivalence check: sharded descent must match
    single-device recall@10 (±0.01) on the same dataset and seed."""
    single = QueryEngine(index, QueryConfig(k=10))
    ids1, _ = single.query_batch(query_profiles)
    r1 = knn_recall(ids1, exact)
    sharded = QueryEngine(index, QueryConfig(k=10, shards=n_shards))
    ids_s, sims_s = sharded.query_batch(query_profiles)
    r_s = knn_recall(ids_s, exact)
    assert r_s >= r1 - 0.01, (n_shards, r_s, r1)
    # Result hygiene: valid global ids, sim-descending, no duplicates.
    valid = ids_s != PAD_ID
    assert ((ids_s >= 0) | ~valid).all() and (ids_s < index.n).all()
    assert (np.diff(np.where(valid, sims_s, -1.0), axis=1) <= 1e-6).all()
    for row in ids_s:
        live = row[row != PAD_ID]
        assert len(live) == len(set(live.tolist()))


def test_sharded_serves_inserted_users(index, query_profiles):
    """Insertion under sharded serving: the lazily-resharded state picks
    up the new user and routes queries to it."""
    import copy

    ix = copy.deepcopy(index)  # keep the module-scoped fixture pristine
    engine = QueryEngine(ix, QueryConfig(k=10, shards=2))
    profile = query_profiles[0]
    u = engine.insert(profile)
    ids, sims = engine.query_batch([profile])
    assert ids[0, 0] == u
    assert sims[0, 0] == pytest.approx(1.0)
    # The delta-resharded plan covers the appended row (on its home
    # shard and/or the shards of the clusters that registered it).
    sd = engine.sharded_state()
    assert sd.version == ix.version
    assert any(u in res for res in sd.plan.residents)


@pytest.mark.slow
def test_mesh_matches_vmap():
    """shard_map over 4 emulated devices returns exactly what the
    single-device vmap fallback returns (subprocess so the device count
    doesn't leak into this session)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.core.params import C2Params
from repro.data.synthetic import make_dataset
from repro.query.engine import QueryConfig, QueryEngine
from repro.query.index import build_index
from repro.query.sharded import ShardedDescent, plan_shards
from repro.core.local_knn import capacity_of
from repro.query.router import profiles_to_csr, fingerprint_profiles, route
from repro.types import PAD_ID

assert jax.device_count() == 4
ds = make_dataset("synth", scale=0.1, seed=3)
index = build_index(ds, C2Params(k=10, b=64, t=8, max_cluster=48))
qds = make_dataset("synth", scale=0.1, seed=77)
profiles = [qds.profile(u) for u in range(32)]
items, offsets = profiles_to_csr(profiles)
qgf = fingerprint_profiles(items, offsets, index)
seeds = route(index, items, offsets, 16)
qn = len(profiles); qcap = capacity_of(qn, minimum=8)
qw = np.zeros((qcap, np.asarray(qgf.words).shape[1]), np.uint32); qw[:qn] = qgf.words
qc = np.zeros(qcap, np.int32); qc[:qn] = qgf.card
qs = np.full((qcap, seeds.shape[1]), PAD_ID, np.int32); qs[:qn] = seeds
plan = plan_shards(index, 4)
mesh_sd = ShardedDescent(index, 4, plan=plan, use_mesh=True)
vmap_sd = ShardedDescent(index, 4, plan=plan, use_mesh=False)
assert mesh_sd.mesh is not None and vmap_sd.mesh is None
i1, s1 = mesh_sd.descend(qw, qc, qs, k=10, beam=32, hops=3)
i2, s2 = vmap_sd.descend(qw, qc, qs, k=10, beam=32, hops=3)
np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)
print("MESH_PARITY_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], env=ENV,
                       capture_output=True, text=True, timeout=420)
    assert "MESH_PARITY_OK" in r.stdout, r.stdout + r.stderr


def test_serve_cli_sharded_smoke(capsys):
    from repro.launch.knn_serve import main

    stats, recall = main(["--dataset", "synth", "--scale", "0.05",
                          "--queries", "16", "--shards", "2"])
    out = capsys.readouterr().out
    assert "sharded: 2 shards" in out
    assert stats["requests"] == 16 and stats["shards"] == 2
    assert recall >= 0.6  # tiny index; full-size bars live in test_query
