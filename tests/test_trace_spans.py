"""Program spans and host counters (``repro.sched.trace``): off by default
at the cost of a flag test, nested records while on, compile attribution,
and the spans the build and the wave path emit."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.params import C2Params
from repro.core.pipeline import cluster_and_conquer
from repro.data.synthetic import make_dataset
from repro.query.engine import QueryConfig, QueryEngine
from repro.query.index import build_index
from repro.sched import trace

BENCH_PREFIXES = ("bench.", "build.", "steady.", "batch.")


@pytest.fixture
def tracing():
    trace.reset()
    trace.enable()
    yield trace
    trace.disable()
    trace.reset()


def _nesting(records):
    """{child name: parent name} over closed records (None: top level)."""
    return {name: (records[p][0] if p >= 0 else None)
            for name, p, _, _ in records}


def test_a_disabled_span_records_nothing_and_is_the_shared_noop():
    trace.reset()
    assert not trace.active()
    a, b = trace.span("repro.a"), trace.span("repro.b")
    assert a is b is trace._NOOP
    with a:
        with b:
            pass
    assert trace.records() == []
    assert trace.summary() == {"spans": {}, "counters": {}}


def test_spans_nest_with_parent_indices(tracing):
    with trace.span("repro.outer"):
        with trace.span("repro.outer.a"):
            with trace.span("repro.outer.a.deep"):
                pass
        with trace.span("repro.outer.b"):
            pass
    with trace.span("repro.next"):
        pass
    recs = trace.records()
    assert [(n, p) for n, p, _, _ in recs] == [
        ("repro.outer", -1), ("repro.outer.a", 0), ("repro.outer.a.deep", 1),
        ("repro.outer.b", 0), ("repro.next", -1)]
    for name, parent, t0, t1 in recs:
        assert t0 <= t1
        if parent >= 0:
            assert recs[parent][2] <= t0 and t1 <= recs[parent][3]


def test_summary_gives_total_self_time_and_count(tracing):
    for _ in range(3):
        with trace.span("repro.parent"):
            with trace.span("repro.child"):
                sum(range(20000))
            sum(range(20000))
    s = trace.summary()["spans"]
    parent, child = s["repro.parent"], s["repro.child"]
    assert parent["count"] == child["count"] == 3
    assert parent["self_s"] == pytest.approx(
        parent["total_s"] - child["total_s"], abs=1e-9)
    assert 0 < parent["self_s"] < parent["total_s"]
    assert child["self_s"] == pytest.approx(child["total_s"])


@pytest.mark.parametrize("on", [False, True])
def test_counters_add_only_while_on(on):
    trace.reset()
    if on:
        trace.enable()
    try:
        trace.add("repro.n")
        trace.add("repro.n", 4)
    finally:
        trace.disable()
    assert trace.summary()["counters"] == ({"repro.n": 5} if on else {})
    trace.reset()


def test_a_compile_is_charged_to_the_innermost_open_span(tracing):
    fresh = jax.jit(lambda x: x * 7 - 3)
    x = jnp.arange(5.0)
    with trace.span("repro.outer"):
        with trace.span("repro.first"):
            fresh(x).block_until_ready()
        with trace.span("repro.second"):
            fresh(x).block_until_ready()
    counters = trace.summary()["counters"]
    assert counters.get("repro.compiles/repro.first") == 1
    assert "repro.compiles/repro.second" not in counters
    assert "repro.compiles/repro.outer" not in counters


def test_reset_clears_spans_counters_and_trace_counts(tracing):
    trace.bump(("prog", 1))
    trace.launch("prog")
    trace.add("repro.n", 2)
    with trace.span("repro.s"):
        pass
    trace.reset()
    assert trace.records() == []
    assert trace.summary() == {"spans": {}, "counters": {}}
    assert trace.counts() == {} and trace.launch_count("prog") == 0


def test_spans_record_and_annotate_while_a_profiler_captures(tmp_path):
    trace.reset()
    assert not trace.active()
    with jax.profiler.trace(str(tmp_path)):
        assert trace.active()
        with trace.span("repro.captured"):
            pass
    with trace.span("repro.after"):
        pass
    assert [r[0] for r in trace.records()] == ["repro.captured"]
    trace.reset()


BUILD_NESTING = {
    "repro.fingerprint": None,
    "repro.cluster": None,
    "repro.cluster.hash": "repro.cluster",
    "repro.cluster.split": "repro.cluster",
    "repro.local_knn": None,
    "repro.local_knn.gather": "repro.local_knn",
    "repro.local_knn.device": "repro.local_knn",
    "repro.local_knn.scatter": "repro.local_knn",
    "repro.merge": None,
}


def test_a_build_emits_its_step_spans_and_pair_counters(small_ds, tracing):
    p = C2Params(k=10, b=256, t=4, max_cluster=120, n_bits=512)
    cluster_and_conquer(small_ds, p)
    recs = trace.records()
    assert _nesting(recs) == BUILD_NESTING
    top = [n for n, parent, _, _ in recs if parent < 0]
    assert top == ["repro.fingerprint", "repro.cluster", "repro.local_knn",
                   "repro.merge"]
    s = trace.summary()
    dispatches = s["spans"]["repro.local_knn.device"]["count"]
    assert dispatches >= 1
    assert s["spans"]["repro.local_knn.gather"]["count"] == dispatches
    c = s["counters"]
    assert 0 < c["repro.local_knn.pairs_useful"] \
        <= c["repro.local_knn.pairs_computed"]
    assert not any(n.startswith(BENCH_PREFIXES)
                   for n in list(s["spans"]) + list(c))


def test_the_hyrec_branch_is_one_span(small_ds, tracing):
    p = C2Params(k=5, b=4, t=1, max_cluster=10**6, rho=1, n_bits=512)
    cluster_and_conquer(small_ds, p)
    spans = trace.summary()["spans"]
    assert spans["repro.local_knn.hyrec"]["count"] == 1
    assert _nesting(trace.records())["repro.local_knn.hyrec"] \
        == "repro.local_knn"


@pytest.fixture(scope="module")
def engine():
    ds = make_dataset("synth", scale=0.15, seed=3)
    index = build_index(ds, C2Params(k=10, b=64, t=8, max_cluster=48))
    eng = QueryEngine(index, QueryConfig(k=10, beam=32, hops=3,
                                         max_wave=64))
    qds = make_dataset("synth", scale=0.15, seed=77)
    return eng, [qds.profile(u) for u in range(48)]


def test_index_packaging_emits_index_and_reverse_spans(tracing):
    ds = make_dataset("synth", scale=0.1, seed=5)
    build_index(ds, C2Params(k=10, b=64, t=4, max_cluster=48))
    nesting = _nesting(trace.records())
    assert nesting["repro.index"] is None
    assert nesting["repro.index.reverse"] == "repro.index"


def test_a_wave_emits_one_wave_span_with_its_three_children(engine, tracing):
    eng, profiles = engine
    eng.query_batch(profiles)
    recs = trace.records()
    waves = [i for i, r in enumerate(recs) if r[0] == "repro.wave"]
    assert len(waves) == 1
    children = [r[0] for r in recs if r[1] == waves[0]]
    assert children == ["repro.wave.fingerprint", "repro.wave.route",
                        "repro.wave.descent"]
    c = trace.summary()["counters"]
    assert c["repro.wave.seed_slots"] == len(profiles) * 8 * 16
    assert 0 < c["repro.wave.seeds"] <= c["repro.wave.seed_slots"]


def test_the_build_tables_users_on_the_device(small_ds, tracing):
    cluster_and_conquer(small_ds, C2Params(k=10, b=256, t=4,
                                           max_cluster=120, n_bits=512))
    c = trace.summary()["counters"]
    assert c["repro.hash.device_users"] == small_ds.n_users
    assert "repro.hash.host_users" not in c


def test_a_wave_tables_its_queries_on_the_host(engine, tracing):
    from repro.query.router import profiles_to_csr, query_hash_tables

    eng, profiles = engine
    eng.query_batch(profiles)
    c = trace.summary()["counters"]
    assert c["repro.hash.host_users"] == len(profiles)
    assert "repro.hash.device_users" not in c
    items, offsets = profiles_to_csr(profiles[:5])
    query_hash_tables(eng.index, items, offsets)
    c = trace.summary()["counters"]
    assert c["repro.hash.host_users"] == len(profiles) + 5
    assert "repro.hash.device_users" not in c
