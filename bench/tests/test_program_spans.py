"""The program's spans as the benchmark reads them: idle time charged to
the innermost program span, the readers of the program-span metrics,
and the benchmark's own reduction left as it was."""
import io
import json
import math
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import program_spans, run, trace
from bench.tests.tiny import ROOT, tiny

DATA = Path(__file__).parent / "data"
BATCH_TRACE = DATA / "batch_trace.xplane.pb.gz"
PROGRAM_TRACE = DATA / "batch_program_trace.xplane.pb.gz"
BENCH = ROOT / "bench"

BUILD_METRICS = ["build.cluster_hash_s", "build.cluster_split_s",
                 "build.local_knn_host_s", "build.group_wait_idle_s",
                 "build.group_useful_pct", "build.index_reverse_s"]
WAVE_METRICS = ["batch.fingerprint_ms", "batch.route_ms",
                "batch.descent_wait_ms", "batch.descent_wait_idle_ms",
                "batch.seed_fill_pct"]


@pytest.mark.parametrize("idle, spans, want", [
    # innermost wins; what no span covers is "other"
    ([(0, 10)], [("a", 2, 8), ("b", 4, 6)],
     {"other": 4, "a": 4, "b": 2}),
    # a child sharing its parent's start and end takes all of it
    ([(0, 5)], [("a", 0, 5), ("b", 0, 5)], {"b": 5}),
    # several idle intervals across siblings and a gap between them
    ([(1, 3), (4, 9)], [("p", 0, 10), ("c1", 2, 5), ("c2", 6, 7)],
     {"p": 1 + 1 + 2, "c1": 1 + 1, "c2": 1}),
    # spans outside every idle interval take nothing
    ([(10, 12)], [("a", 0, 5), ("b", 11, 20)], {"other": 1, "b": 1}),
    ([], [("a", 0, 5)], {}),
])
def test_idle_is_charged_to_the_innermost_open_span(idle, spans, want):
    got = program_spans.charge(idle, spans)
    assert got == want
    assert sum(got.values()) == sum(e - s for s, e in idle)


def test_the_benchmark_reduction_is_unchanged_on_the_recorded_trace():
    r = trace.reduce(str(BATCH_TRACE))
    assert json.dumps(r.breakdown()) == (
        '{"device_ops": [["descent_kernel", 0.7410452829999999]], '
        '"idle_gaps": [["batch.wave", 0.35396301900000005], '
        '["other", 0.0039713100000000005]]}')
    assert r.gaps == {"batch.wave": 0.35396301900000005,
                      "other": 0.0039713100000000005}


def _view(counts, program, programs):
    reduced = trace.Reduced(window_s=1.0, busy_s=0.0, programs=programs,
                            gaps={}, chips=1)
    return SimpleNamespace(counts=counts, program=program, trace=reduced,
                           spans=None)


def _read(name, view):
    return run.layer_reader(BENCH, name)(view)


def test_build_readers_on_program_records():
    spans = []
    for b in range(2):  # two builds, one second apart
        t = float(b)
        spans += [("repro.cluster", t, t + 0.5),
                  ("repro.cluster.hash", t, t + 0.1),
                  ("repro.cluster.split", t + 0.1, t + 0.45),
                  ("repro.local_knn", t + 0.5, t + 0.8),
                  ("repro.local_knn.device", t + 0.6, t + 0.7),
                  ("repro.index", t + 0.8, t + 0.95),
                  ("repro.index.reverse", t + 0.8, t + 0.9)]
    program = {"spans": spans,
               "counters": {"repro.local_knn.pairs_useful": 30,
                            "repro.local_knn.pairs_computed": 120}}
    view = _view({"builds": 2}, program, {"_group_knn": [0.12, 8]})
    got = {m: _read(m, view) for m in BUILD_METRICS}
    want = {"build.cluster_hash_s": 0.1, "build.cluster_split_s": 0.35,
            "build.local_knn_host_s": 0.2, "build.group_wait_idle_s": 0.04,
            "build.group_useful_pct": 25.0, "build.index_reverse_s": 0.1}
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", BUILD_METRICS + WAVE_METRICS)
def test_readers_leave_a_program_without_spans_out(metric):
    view = _view({"builds": 1, "waves": 1}, {"spans": [], "counters": {}},
                 {"_group_knn": [1.0, 1], "descent_kernel": [1.0, 1]})
    assert _read(metric, view) is None


def test_readers_find_no_spans_in_a_program_without_them(monkeypatch):
    from repro.sched import trace as program_trace
    monkeypatch.delattr(program_trace, "records")
    spans = SimpleNamespace(records=[("bench.window", 0.0, 1.0)])
    view = SimpleNamespace(counts={"waves": 1}, spans=spans, trace=None)
    assert program_spans.program(view) is None
    assert _read("batch.route_ms", view) is None


@pytest.fixture(scope="module")
def program_trace():
    return trace.reduce(str(PROGRAM_TRACE)), \
        program_spans.read_trace(str(PROGRAM_TRACE))


def test_program_gaps_sum_to_the_idle_time(program_trace):
    reduced, rec = program_trace
    idle = reduced.window_s - reduced.busy_s
    assert rec["idle_s"] == pytest.approx(idle, rel=1e-6)
    assert sum(rec["gaps"].values()) == pytest.approx(idle, rel=1e-6)
    assert all(n.startswith("repro.") or n == "other" for n in rec["gaps"])
    # the benchmark's own spans are read as before
    assert sum(reduced.gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert not any(n.startswith("repro.") for n in reduced.gaps)


def test_wave_readers_on_the_recorded_program_trace(program_trace):
    reduced, rec = program_trace
    waves = sum(1 for n, _, _ in rec["spans"] if n == "repro.wave")
    assert waves >= 2
    view = SimpleNamespace(counts={"waves": waves, "queries": 256 * waves},
                           program=rec, trace=reduced, spans=None)
    got = {m: _read(m, view) for m in WAVE_METRICS[:4]}
    assert all(math.isfinite(v) and v > 0 for v in got.values()), got
    dev = _read("batch.descent_device_ms", view)
    assert got["batch.descent_wait_ms"] >= dev
    # The span-less-busy reading is the exact charge of the trace: only
    # the wave program runs inside the descent span.
    charged = 1e3 * rec["gaps"]["repro.wave.descent"] / waves
    assert got["batch.descent_wait_idle_ms"] == pytest.approx(charged,
                                                             rel=1e-3)
    wave = 1e3 * program_spans.total(view, "repro.wave") / waves
    parts = sum(got[m] for m in WAVE_METRICS[:3])
    assert 0.9 * wave <= parts <= wave


@pytest.mark.parametrize("cell, metrics", [
    ("ml10M.build", BUILD_METRICS), ("ml10M.serve_batch", WAVE_METRICS)])
def test_a_traced_tiny_run_reports_the_program_span_metrics(cell, metrics):
    loaded = tiny(cell)
    result = run.execute(loaded, 2**33 + 5, 1.0, True, jax.devices()[:1],
                         err=io.StringIO())
    assert result["correct"], result["checks"]
    got = {m: v["value"] for m, v in result["metrics"].items()}
    device = {m for m in metrics if m.endswith(("idle_s", "idle_ms"))}
    # No TPU plane on the CPU: the device readings are left out.
    assert set(metrics) - device <= set(got)
    assert not device & set(got)
    assert all(math.isfinite(got[m]) and got[m] >= 0
               for m in set(metrics) - device)
    if cell == "ml10M.build":
        split = got["build.cluster_hash_s"] + got["build.cluster_split_s"]
        assert 0.5 * got["build.cluster_s"] < split <= got["build.cluster_s"]
        assert got["build.local_knn_host_s"] <= got["build.local_knn_s"]
        assert 0 < got["build.group_useful_pct"] <= 100
    else:
        parts = (got["batch.fingerprint_ms"] + got["batch.route_ms"]
                 + got["batch.descent_wait_ms"])
        assert 0.5 * got["batch.wave_ms"] < parts <= got["batch.wave_ms"]
        assert 0 < got["batch.seed_fill_pct"] <= 100
