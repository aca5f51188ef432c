"""The trace reduction, on a trace recorded on a TPU v5e: one traced
second of ``ml10M.serve_batch`` (ten 256-query waves)."""
from pathlib import Path

import pytest

from bench import trace, work
from bench.run import View, layer_reader
from bench.tests.tiny import ROOT

TRACE = Path(__file__).parent / "data" / "batch_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(TRACE))


def test_program_names_are_the_stable_function_names():
    assert trace.program_name("jit_slot_hop(15640806076240684338)") \
        == "slot_hop"
    assert trace.program_name("jit__group_knn(37)") == "_group_knn"
    assert trace.program_name("copy.3") == "copy.3"


def test_busy_window_and_programs(reduced):
    assert reduced.chips == 1
    assert reduced.window_s == pytest.approx(1.098979612, abs=1e-6)
    assert reduced.busy_s == pytest.approx(0.741045283, abs=1e-6)
    seconds, runs = reduced.program("descent_kernel")
    assert runs == 10 and seconds == pytest.approx(reduced.busy_s)
    assert reduced.program("slot_hop") == (0.0, 0)


def test_idle_time_is_attributed_to_host_spans(reduced):
    idle = reduced.window_s - reduced.busy_s
    assert sum(reduced.gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert max(reduced.gaps, key=reduced.gaps.get) == "batch.wave"
    b = reduced.breakdown()
    assert b["device_ops"][0][0] == "descent_kernel"
    assert len(b["idle_gaps"]) <= 10


def test_layer_readers_on_the_recorded_trace(reduced):
    win = {"counts": {"waves": 10, "queries": 2560},
           "window_s": reduced.window_s}
    w = {"beam": 32, "k_graph": 30, "r_max": 30, "words": 32, "hops": 3}
    view = View(None, win, reduced, w, "TPU v5 lite")
    bench = ROOT / "bench"
    idle = layer_reader(bench, "batch.idle_pct")(view)
    assert idle == pytest.approx(100 * (1 - 0.741045283 / 1.098979612))
    dev = layer_reader(bench, "batch.descent_device_ms")(view)
    assert dev == pytest.approx(74.1045283)
    roof = layer_reader(bench, "batch.descent_roofline_pct")(view)
    nbytes = 3 * 2560 * 32 * 60 * 136
    assert roof == pytest.approx(100 * nbytes / 819e9 / 0.741045283)
    assert 0 < roof < 100


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
