"""HBM-resident DMA placement of the fused descent hop.

The contract: ``descent_hop(dma=True)`` — tables in ANY/HBM memory,
per-chunk candidate-row DMA into rotating VMEM buffers, suppressed
lanes skipped at the DMA level — is *bitwise* (ids AND sims) equal to
the jnp oracle and to the VMEM placement, for arbitrary well-formed
inputs: sketch widths straddling the popcount→MXU boundary, score
chunks that do not divide the lane count, all-suppressed chunks,
tombstone-heavy tables, single- and double-buffered pipelines. On top
of parity, the byte accounting must be exact (``dma_bytes`` ==
``n_scored`` packed rows of ``4·dma_row_words(W)`` bytes;
``bytes_saved`` the complement over the full candidate count) and the
shape-keyed autotuner must keep the serving plans compile-once across
admissions and reshards.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.params import C2Params
from repro.data.synthetic import make_dataset
from repro.kernels.descent_score import ops as ds_ops
from repro.kernels.descent_score import ref as ds_ref
from repro.kernels.descent_score import tune
from repro.kernels.descent_score.descent_score import dma_row_words
from repro.query.engine import QueryConfig, QueryEngine, QueryRequest
from repro.query.index import build_index
from repro.sched import trace
from repro.types import NEG_INF, PAD_ID


def _random_words(rng, n, W):
    w = (rng.integers(0, 2**32, size=(n, W), dtype=np.uint64)
         & rng.integers(0, 2**32, size=(n, W), dtype=np.uint64)
         ).astype(np.uint32)
    card = np.unpackbits(w.view(np.uint8), axis=1).sum(1).astype(np.int32)
    return w, card


def _hop_inputs(rng, n, kg, kr, W, q, B, *, tomb_frac=0.0):
    g = rng.integers(-1, n, size=(n, kg)).astype(np.int32)
    r = rng.integers(-1, n, size=(n, kr)).astype(np.int32)
    w, c = _random_words(rng, n, W)
    qw, qc = _random_words(rng, q, W)
    bi = np.full((q, B), PAD_ID, np.int32)
    for i in range(q):
        m = int(rng.integers(0, min(n, B) + 1))
        bi[i, :m] = rng.choice(n, size=m, replace=False)
    bs = np.where(bi == PAD_ID, NEG_INF,
                  -np.sort(-rng.random((q, B)))).astype(np.float32)
    tomb = None
    if tomb_frac > 0:
        tomb = jnp.asarray(rng.random(n) < tomb_frac)
    args = tuple(jnp.asarray(x) for x in (g, r, w, c, qw, qc, bi, bs))
    return args, tomb


def _assert_dma_parity(args, tomb=None, **dma_kw):
    """ids AND sims bitwise vs the jnp oracle and the VMEM kernel, plus
    exact byte accounting against the scored-lane counter."""
    B = args[6].shape[1]
    row_bytes = 4 * dma_row_words(args[2].shape[1])
    C = B * (args[0].shape[1] + args[1].shape[1])
    ri, rs = ds_ref.descent_hop_ref(*args, tomb=tomb)
    ki, ks, nsc, kb, ksv = ds_ops.descent_hop(*args, tomb=tomb,
                                              with_counts=True)
    di, dsm, dnsc, dmab, saved = ds_ops.descent_hop(
        *args, tomb=tomb, dma=True, with_counts=True, **dma_kw)
    np.testing.assert_array_equal(np.asarray(di), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(dsm), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(di), np.asarray(ki))
    np.testing.assert_array_equal(np.asarray(dsm), np.asarray(ks))
    # Both placements suppress the same lanes; only the DMA placement
    # turns the suppression into byte traffic it never moves.
    np.testing.assert_array_equal(np.asarray(dnsc), np.asarray(nsc))
    assert (np.asarray(kb) == 0).all() and (np.asarray(ksv) == 0).all()
    np.testing.assert_array_equal(np.asarray(dmab),
                                  np.asarray(dnsc) * row_bytes)
    np.testing.assert_array_equal(np.asarray(saved),
                                  (C - np.asarray(dnsc)) * row_bytes)
    return np.asarray(dnsc), np.asarray(dmab), np.asarray(saved)


def test_dma_hop_parity_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(deadline=None, max_examples=25)
    @given(st.data())
    def battery(data):
        rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
        n = data.draw(st.integers(2, 60))
        kg = data.draw(st.integers(1, 6))
        kr = data.draw(st.integers(1, 6))
        # Straddle MXU_MIN_WORDS (=64): VPU popcount below, int8
        # bit-plane MXU matmul at/above — identical bits required.
        W = data.draw(st.sampled_from([1, 2, 64, 65]))
        q = data.draw(st.integers(1, 8))
        B = data.draw(st.integers(1, 6))
        tomb_frac = data.draw(st.sampled_from([0.0, 0.5, 0.9]))
        # Chunks that do NOT divide the lane count (and over-long ones),
        # single and double buffering.
        chunk = data.draw(st.sampled_from([None, 3, 7, 16, 1024]))
        n_buffers = data.draw(st.sampled_from([1, 2]))
        args, tomb = _hop_inputs(rng, n, kg, kr, W, q, B,
                                 tomb_frac=tomb_frac)
        kw = {"n_buffers": n_buffers}
        if chunk is not None:
            kw["score_chunk"] = chunk
        _assert_dma_parity(args, tomb=tomb, **kw)

    battery()


@pytest.mark.parametrize("W", [1, 2, 64, 65])
@pytest.mark.parametrize("chunk,n_buffers", [(3, 2), (7, 1), (None, 2)])
def test_dma_parity_sweep(W, chunk, n_buffers):
    """Deterministic slice of the battery above (runs even without
    hypothesis): MXU-boundary widths × non-dividing chunks × buffer
    depths, with tombstones in the mix."""
    rng = np.random.default_rng(W * 100 + (chunk or 0) * 10 + n_buffers)
    args, tomb = _hop_inputs(rng, 45, 4, 5, W, 6, 5, tomb_frac=0.4)
    kw = {"n_buffers": n_buffers}
    if chunk is not None:
        kw["score_chunk"] = chunk
    _assert_dma_parity(args, tomb=tomb, **kw)


def test_dma_all_suppressed_chunks():
    """Beams that already contain every reachable neighbor: every
    candidate lane is suppressed, so the hop fetches and scores NOTHING
    — zero DMA bytes, full bytes_saved — and still matches the oracle."""
    rng = np.random.default_rng(3)
    n, B, W = 6, 6, 4
    # Ring adjacency within {0..5}; every beam holds all six rows.
    g = np.stack([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n],
                 axis=1).astype(np.int32)
    r = np.stack([(np.arange(n) - 1) % n], axis=1).astype(np.int32)
    w, c = _random_words(rng, n, W)
    qw, qc = _random_words(rng, 5, W)
    bi = np.tile(np.arange(n, dtype=np.int32), (5, 1))
    bs = -np.sort(-rng.random((5, B))).astype(np.float32)
    args = tuple(jnp.asarray(x) for x in (g, r, w, c, qw, qc, bi, bs))
    C = B * (g.shape[1] + r.shape[1])
    nsc, dmab, saved = _assert_dma_parity(args, score_chunk=5)
    assert (nsc == 0).all()
    assert (dmab == 0).all()
    assert (saved == C * 4 * dma_row_words(W)).all()


def test_dma_tombstone_heavy():
    """Mostly-dead tables: tombstoned lanes are skipped at the DMA
    level, so the byte traffic shrinks vs the same hop on a live table
    (and parity with the masked oracle still holds bitwise)."""
    rng = np.random.default_rng(17)
    args, _ = _hop_inputs(rng, 50, 5, 4, 4, 9, 6)
    tomb = jnp.asarray(rng.random(50) < 0.8)
    _, live_bytes, _ = _assert_dma_parity(args)
    _, dead_bytes, dead_saved = _assert_dma_parity(args, tomb=tomb)
    assert dead_bytes.sum() < live_bytes.sum()
    assert dead_saved.sum() > 0


# -- serving-plan matrix ----------------------------------------------------


@pytest.fixture(scope="module")
def index():
    ds = make_dataset("synth", scale=0.05, seed=3)
    return build_index(ds, C2Params(k=8, b=64, t=4, max_cluster=48))


@pytest.fixture(scope="module")
def query_profiles():
    qds = make_dataset("synth", scale=0.05, seed=77)
    return [qds.profile(u) for u in range(10)]


def _serve(index, profiles, **kw):
    eng = QueryEngine(index, QueryConfig(k=8, beam=12, hops=2, **kw))
    for rid, p in enumerate(profiles):
        eng.submit(QueryRequest(rid=rid, profile=p))
    eng.run()
    by_rid = {r.rid: (r.ids, r.sims) for r in eng.done}
    ids = np.stack([by_rid[i][0] for i in range(len(profiles))])
    sims = np.stack([by_rid[i][1] for i in range(len(profiles))])
    return eng, ids, sims


@pytest.mark.parametrize("placement", [{}, {"shards": 2}],
                         ids=["single", "sharded"])
@pytest.mark.parametrize("batching", [{}, {"continuous": True, "slots": 8}],
                         ids=["wave", "continuous"])
def test_plan_matrix_dma_bitwise(index, query_profiles, placement,
                                 batching):
    """scorer="pallas_dma" is results-transparent across the full plan
    matrix: bitwise (ids, sims) vs the jnp scorer for every placement ×
    batching, with live byte accounting in the serving stats."""
    _, ri, rs = _serve(index, query_profiles, **placement, **batching)
    eng, di, dsm = _serve(index, query_profiles, kernel=True, dma=True,
                          **placement, **batching)
    np.testing.assert_array_equal(di, ri)
    np.testing.assert_array_equal(dsm, rs)
    d = eng.plan.descent_stats
    assert d["scored_lanes"] > 0
    assert d["bytes_saved"] > 0
    W = index.words.shape[1]
    # The DMA guard predicate IS the scoring mask: bytes moved must
    # agree with lanes scored exactly.
    assert d["dma_bytes"] == d["scored_lanes"] * 4 * dma_row_words(W)


# -- autotuner / compile-once ----------------------------------------------


def test_tune_memoizes_per_shape():
    tune.clear()
    p1 = tune.hop_params(1000, 16, 32, 20)
    assert tune.stats["misses"] == 1
    p2 = tune.hop_params(1000, 16, 32, 20)
    assert p2 == p1
    assert tune.stats["hits"] == 1
    # A different shape resolves independently...
    tune.hop_params(1000, 64, 32, 20)
    assert tune.stats["misses"] == 2
    # ...and the wave width only clamps block_q, never forks the key.
    p3 = tune.hop_params(1000, 16, 32, 20, q=2)
    assert p3.block_q <= 2
    assert tune.stats["misses"] == 2


def test_tune_heuristic_respects_scratch_budget():
    for n, W, beam, kdeg in [(100, 1, 4, 8), (10_000, 32, 32, 20),
                             (100_000, 256, 64, 32)]:
        p = tune.hop_params(n, W, beam, kdeg)
        assert p.block_q >= 1 and p.score_chunk >= 16
        assert p.n_buffers in (1, 2)
        buf = p.n_buffers * p.block_q * p.score_chunk * (W + 1) * 4
        assert buf <= 2 * tune._SCRATCH_BUDGET


def test_tune_disk_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv(tune.ENV_CACHE, str(tmp_path / "tune.json"))
    tune.clear()
    try:
        key = tune.shape_key(512, 16, 24, 18)
        tune.record(key, tune.HopParams(4, 32, 2))
        tune.clear()  # drop the memo; force the disk path
        p = tune.hop_params(*key)
        assert p == tune.HopParams(4, 32, 2)
        assert tune.stats["disk_hits"] == 1
    finally:
        tune.clear()


def test_dma_compile_once_across_admissions(index, query_profiles):
    """The tuner memo keeps the DMA scorer compile-once under streaming
    admission: however requests arrive, the fused slot programs trace
    once per shape and the tuner resolves each index shape once."""
    tune.clear()
    # beam/slots unique to this test: an outer program cached by an
    # earlier test would skip the trace (and the tuner) entirely.
    qc = QueryConfig(k=8, beam=14, hops=2, continuous=True, slots=9,
                     kernel=True, dma=True)
    engine = QueryEngine(index, qc)
    assert engine.plan.key == (1, "continuous", "pallas_dma")

    base = trace.compile_count(engine.plan.key)
    for rid, p in enumerate(query_profiles[:4]):
        engine.submit(QueryRequest(rid=rid, profile=p))
    engine.run()
    after = trace.compile_count(engine.plan.key)
    assert after - base >= 1
    misses = tune.stats["misses"]
    assert misses >= 1
    # Later admissions — bursty and one-by-one — reuse both caches.
    for rid, p in enumerate(query_profiles[4:8]):
        engine.submit(QueryRequest(rid=rid, profile=p))
    engine.run()
    for p in query_profiles[8:]:
        engine.submit(QueryRequest(rid=99, profile=p))
        engine.run()
    assert trace.compile_count(engine.plan.key) == after
    # No new resolutions: either the jit cache short-circuits before the
    # tuner is consulted (descent_hop runs only at trace time) or the
    # memo answers — never a fresh miss.
    assert tune.stats["misses"] == misses


def test_dma_compile_once_across_reshards(index, query_profiles):
    """Insert-driven delta reshards keep the sharded DMA wave program
    and the tuner resolution stable (padded capacities hold the shapes,
    the memo holds the params — no re-trace, no re-miss)."""
    tune.clear()
    qc = QueryConfig(k=8, beam=13, hops=2, shards=2, kernel=True,
                     dma=True)
    engine = QueryEngine(index, qc)
    _, ids_a, sims_a = _serve_through(engine, query_profiles)
    misses = tune.stats["misses"]
    ins = make_dataset("synth", scale=0.05, seed=123)
    for u in range(3):
        # Each insert delta-reshards AND runs its own 1-row search wave
        # (a new, narrower shape — one extra legitimate trace).
        engine.insert(ins.profile(u))
    after = trace.compile_count(engine.plan.key)
    _, ids_b, _ = _serve_through(engine, query_profiles)
    # The re-served wave re-uses its pre-reshard program, and the tuner
    # never re-missed: q clamps block_q without forking the cache key,
    # and padded capacities held the index shape across the reshard.
    assert trace.compile_count(engine.plan.key) == after
    assert tune.stats["misses"] == misses
    assert ids_b.shape == ids_a.shape


def _serve_through(engine, profiles):
    for rid, p in enumerate(profiles):
        engine.submit(QueryRequest(rid=rid, profile=p))
    engine.run()
    by_rid = {r.rid: (r.ids, r.sims) for r in engine.done}
    engine.done.clear()
    ids = np.stack([by_rid[i][0] for i in range(len(profiles))])
    sims = np.stack([by_rid[i][1] for i in range(len(profiles))])
    return engine, ids, sims
