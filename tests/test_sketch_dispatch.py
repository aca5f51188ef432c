"""The sketch a build asks for, on the build and the query paths.

``C2Params.use_goldfinger`` picks GoldFinger sketches or incidence rows
of the whole item universe (Table V's raw data), whose sims are the
exact Jaccard. Checked here against plain numpy on seeded data at a
small size: the sketch entry point, both similarity branches, a whole
C² build through ``cluster_and_conquer`` and through ``knn_build``, and
an index that records its kind, saves it, and fingerprints its queries
the same way.
"""
import dataclasses
import types

import numpy as np
import pytest

from repro.core.clustering import build_plan
from repro.core.params import C2Params
from repro.core.pipeline import cluster_and_conquer
from repro.data.synthetic import make_dataset
from repro.launch import knn_build
from repro.query.engine import QueryConfig, QueryEngine, QueryRequest
from repro.query.index import KNNIndex, build_index
from repro.query.router import fingerprint_profiles, profiles_to_csr
from repro.query.search import exact_knn
from repro.sched import trace
from repro.sketch.goldfinger import (GOLDFINGER, INCIDENCE,
                                     fingerprint_dataset,
                                     incidence_fingerprint, jaccard_pairwise,
                                     jaccard_pairwise_auto,
                                     jaccard_pairwise_mxu, sketch_dataset)
from repro.types import PAD_ID, Dataset

RAW = C2Params(k=10, b=256, t=4, max_cluster=120, use_goldfinger=False)


@pytest.fixture(scope="module")
def ds():
    return make_dataset("ml1M", scale=0.08, seed=7)  # 3,533 items: W = 111


@pytest.fixture(scope="module")
def queries():
    qds = make_dataset("ml1M", scale=0.08, seed=91)
    return [qds.profile(u) for u in range(24)]


def exact_sim(a, b) -> np.float32:
    """Set Jaccard in float32 from integer counts."""
    inter = len(np.intersect1d(a, b))
    union = np.float32(len(a)) + np.float32(len(b)) - np.float32(inter)
    return np.float32(inter) / union if union > 0 else np.float32(0)


@pytest.mark.parametrize("use_goldfinger", [True, False])
def test_sketch_dataset_honours_use_goldfinger(ds, use_goldfinger):
    p = dataclasses.replace(RAW, use_goldfinger=use_goldfinger, n_bits=512)
    trace.reset()
    trace.enable()
    try:
        gf = sketch_dataset(ds, p)
        summary = trace.summary()
    finally:
        trace.disable()
        trace.reset()
    if use_goldfinger:
        want = fingerprint_dataset(ds, n_bits=512, seed=p.seed)
        assert gf.sketch == GOLDFINGER
    else:
        want = incidence_fingerprint(ds)
        assert gf.sketch == INCIDENCE
        assert gf.n_bits == 32 * ((ds.n_items + 31) // 32)
    np.testing.assert_array_equal(gf.words, want.words)
    np.testing.assert_array_equal(gf.card, want.card)
    assert summary["counters"]["repro.fingerprint.words"] == \
        gf.words.shape[1]
    assert summary["spans"]["repro.fingerprint"]["count"] == 1


@pytest.mark.parametrize("score", [jaccard_pairwise, jaccard_pairwise_mxu,
                                   jaccard_pairwise_auto])
def test_incidence_sims_are_exact_jaccard_in_float32(ds, score):
    gf = incidence_fingerprint(ds)
    sims = np.asarray(score(gf.words[:12], gf.card[:12], gf.words[:60],
                            gf.card[:60]))
    want = np.array([[exact_sim(ds.profile(u), ds.profile(v))
                      for v in range(60)] for u in range(12)], np.float32)
    np.testing.assert_array_equal(sims, want)


def test_incidence_row_of_a_query_counts_items_past_the_universe():
    items, offsets = profiles_to_csr([[1, 5, 40], [2, 70, 5000]])
    index = types.SimpleNamespace(sketch=INCIDENCE, n_bits=64, fp_seed=0)
    gf = fingerprint_profiles(items, offsets, index)
    assert gf.words.shape == (2, 2) and gf.sketch == INCIDENCE
    np.testing.assert_array_equal(gf.card, [3, 3])
    assert gf.words[1, 0] == 1 << 2 and gf.words[1, 1] == 0
    # A repeated item past the width counts once, as one inside does.
    rep = Dataset("q", 1, 5001, np.array([2, 70, 5000, 70, 2], np.int32),
                  np.array([0, 5], np.int64))
    np.testing.assert_array_equal(
        incidence_fingerprint(rep, n_bits=64).card, [3])


def test_both_branches_agree_at_incidence_width():
    rng = np.random.default_rng(11)
    words = rng.integers(0, 2**32, size=(40, 328), dtype=np.uint32)
    words &= rng.integers(0, 2**32, size=(40, 328), dtype=np.uint32)
    card = np.unpackbits(words.view(np.uint8), axis=1).sum(1).astype(
        np.int32)
    popc = np.asarray(jaccard_pairwise(words, card, words, card))
    mxu = np.asarray(jaccard_pairwise_mxu(words, card, words, card))
    np.testing.assert_array_equal(popc.view(np.uint32), mxu.view(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(jaccard_pairwise_auto(words, card, words, card)), mxu)


def _reference_rows(ds, plan, k):
    """Exact top-k over each user's co-members of every configuration."""
    comembers = [set() for _ in range(ds.n_users)]
    for mem in plan.members:
        for u in mem:
            comembers[u].update(int(v) for v in mem if v != u)
    out = []
    for u in range(ds.n_users):
        cand = sorted(comembers[u])
        sims = {v: exact_sim(ds.profile(u), ds.profile(v)) for v in cand}
        out.append((sims, sorted(sims.values(), reverse=True)[:k]))
    return out


@pytest.mark.parametrize("path", ["cluster_and_conquer", "knn_build"])
def test_raw_build_is_exact_top_k_over_co_members(ds, path):
    if path == "cluster_and_conquer":
        graph, _ = cluster_and_conquer(ds, RAW)
    else:
        graph, _ = knn_build.build(ds, RAW, verbose=False)
    plan = build_plan(ds, RAW)
    assert plan.sizes.max() < RAW.bf_threshold  # brute force throughout
    ids, sims = np.asarray(graph.ids), np.asarray(graph.sims)
    for u, (every, top) in enumerate(_reference_rows(ds, plan, RAW.k)):
        valid = ids[u] != PAD_ID
        np.testing.assert_array_equal(sims[u][valid],
                                      np.array(top, np.float32))
        assert all(every[int(v)] == s
                   for v, s in zip(ids[u][valid], sims[u][valid]))


@pytest.fixture(scope="module")
def raw_index(ds):
    return build_index(ds, dataclasses.replace(RAW, b=64, t=8,
                                               max_cluster=48))


def test_index_records_its_sketch_and_saves_it(raw_index, ds, tmp_path):
    assert raw_index.sketch == INCIDENCE
    assert raw_index.n_bits == 32 * ((ds.n_items + 31) // 32)
    raw_index.save(tmp_path / "raw.npz")
    assert KNNIndex.load(tmp_path / "raw.npz").sketch == INCIDENCE
    # An index saved before the kind was recorded loads as GoldFinger.
    gf_index = build_index(ds, dataclasses.replace(RAW, use_goldfinger=True,
                                                   b=64, t=8,
                                                   max_cluster=48))
    gf_index.save(tmp_path / "gf.npz")
    z = dict(np.load(tmp_path / "gf.npz"))
    del z["sketch"]
    np.savez(tmp_path / "legacy.npz", **z)
    legacy = KNNIndex.load(tmp_path / "legacy.npz")
    assert legacy.sketch == GOLDFINGER
    np.testing.assert_array_equal(legacy.words, gf_index.words)


@pytest.mark.parametrize("continuous", [False, True])
def test_raw_index_answers_with_exact_jaccard(raw_index, queries, ds,
                                              continuous):
    engine = QueryEngine(raw_index, QueryConfig(k=10, beam=32, hops=3,
                                                max_wave=32, slots=8,
                                                continuous=continuous))
    if continuous:
        for rid, p in enumerate(queries):
            engine.submit(QueryRequest(rid=rid, profile=p))
        engine.run()
        done = sorted(engine.done, key=lambda r: r.rid)
        ids = np.stack([r.ids for r in done])
        sims = np.stack([r.sims for r in done])
    else:
        ids, sims = engine.query_batch(queries)
    for q, p in enumerate(queries):
        for v, s in zip(ids[q], sims[q]):
            if v != PAD_ID:
                assert s == exact_sim(np.unique(p), ds.profile(int(v)))
    items, offsets = profiles_to_csr(queries)
    qgf = fingerprint_profiles(items, offsets, raw_index)
    ex_ids, ex_sims = exact_knn(raw_index.words, raw_index.card,
                                qgf.words, qgf.card, 10)
    for q, p in enumerate(queries):
        assert ex_sims[q, 0] == max(exact_sim(np.unique(p), ds.profile(v))
                                    for v in range(ds.n_users))
    hit = np.mean([len(set(ids[q]) & set(ex_ids[q])) / 10
                   for q in range(len(queries))])
    assert hit >= 0.6


def test_raw_index_refuses_goldfinger_hashed_queries(raw_index, queries):
    engine = QueryEngine(raw_index, QueryConfig(k=10, max_wave=32))
    items, offsets = profiles_to_csr(queries[:4])
    hashed = fingerprint_dataset(
        Dataset("q", 4, int(items.max()) + 1, items, offsets),
        n_bits=raw_index.n_bits, seed=raw_index.fp_seed)
    with pytest.raises(ValueError, match="fingerprinted as 'goldfinger'"):
        engine.plan.search(items, offsets, hashed, 10)


@pytest.mark.parametrize("op", ["insert", "update"])
def test_raw_index_refuses_to_store_items_past_its_universe(ds, queries,
                                                             op):
    index = build_index(ds, dataclasses.replace(RAW, b=64, t=8,
                                                max_cluster=48))
    engine = QueryEngine(index, QueryConfig(k=10, max_wave=32))
    outside = index.n_bits + 3  # an item no incidence row has a bit for
    n, version = index.n, index.version
    words, card = index.words.copy(), index.card.copy()
    for p in queries[:2]:  # two users sharing the item
        with pytest.raises(ValueError, match="past the incidence index"):
            if op == "insert":
                engine.insert(list(p) + [outside])
            else:
                engine.update_user(0, list(p) + [outside])
    assert (index.n, index.version) == (n, version)
    np.testing.assert_array_equal(index.words, words)
    np.testing.assert_array_equal(index.card, card)
    # Inside the universe the stored row and its edges stay exact.
    u = engine.insert(queries[0]) if op == "insert" else 0
    if op == "update":
        engine.update_user(u, queries[0])
    ids, sims = index.graph_ids[u], index.graph_sims[u]
    assert (ids != PAD_ID).any()
    for v, s in zip(ids, sims):
        if v != PAD_ID:
            assert s == exact_sim(np.unique(queries[0]), ds.profile(int(v)))


def test_goldfinger_index_answers_as_its_hashed_queries_do(ds, queries):
    index = build_index(ds, C2Params(k=10, b=64, t=8, max_cluster=48,
                                     n_bits=512))
    assert index.sketch == GOLDFINGER
    engine = QueryEngine(index, QueryConfig(k=10, max_wave=32))
    ids, sims = engine.query_batch(queries)
    items, offsets = profiles_to_csr(queries)
    n_items = int(items.max()) + 1
    qgf = fingerprint_dataset(Dataset("q", len(queries), n_items, items,
                                      offsets), n_bits=512, seed=0)
    want_ids, want_sims = engine.plan.search(items, offsets, qgf, 10)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(sims.view(np.uint32),
                                  np.asarray(want_sims).view(np.uint32))
