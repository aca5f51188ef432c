"""The raw-data build cell (``ml10M_raw.build``) at a tiny size on the
CPU: its driver comes out correct through the harness, the control and
every planted build fault fail its check, its reference's incidence
words are the program's rows, and the group programs' work is counted
as the algorithm needs it."""
import io
import types

import jax
import numpy as np
import pytest

from bench import data, faults, group_work, reference_raw, run, spans, system
from bench.reference import gf_inter, gf_sim
from bench.tests.tiny import tiny
from bench.trace import Reduced

CELL = "ml10M_raw.build"
SEED = 2**33 + 29


def tiny_raw() -> dict:
    loaded = tiny(CELL)
    # 2,048 items: rows of 64 words, the width at which the group
    # programs take the bit-plane matmul, and whole 64-bit words for the
    # reference.
    loaded["config"]["stats"]["n_items"] = 2048
    return loaded


def execute(loaded, trace=False):
    return run.execute(loaded, SEED, 0.5, trace, jax.devices()[:1],
                       err=io.StringIO())


def test_driver_runs_the_tiny_cell_correctly():
    loaded = tiny_raw()
    result = execute(loaded)
    assert result["correct"], result["checks"]
    assert all(c["value"] == 0 for name, c in result["checks"].items()
               if name not in ("sim_gap", "rank_gap"))
    assert set(result["metrics"]) == {m["name"]
                                      for m in loaded["end_to_end"]}
    assert {"build_s", "recall", "setup_s"} <= set(result["metrics"])
    assert result["metrics"]["recall"]["value"] > 0


def test_traced_run_reports_host_spans_and_leaves_device_shares_out():
    result = execute(tiny_raw(), trace=True)
    assert result["correct"], result["checks"]
    assert "build.local_knn_s" in result["metrics"]
    # No TPU plane on the CPU: no program time, so no roofline share.
    assert "build.group_knn_roofline_pct" not in result["metrics"]


def test_control_fails_a_limit():
    loaded = tiny_raw()
    drv = run.driver(loaded["traffic"]["driver"])
    ctx = system.Ctx(config=loaded["config"], traffic=loaded["traffic"],
                     seed=SEED, spans=spans.Spans())
    st = drv.setup(ctx)
    win = drv.measure(st, 0.2)
    limits = loaded["limits"]
    numbers = drv.check(st, win)["numbers"]
    assert all(v <= limits[k] for k, v in numbers.items()), numbers
    control = drv.check(st, win, control=True)["numbers"]
    assert not all(v <= limits[k] for k, v in control.items()), control


@pytest.mark.parametrize("fault", faults.BUILD)
def test_fault_makes_the_run_incorrect(fault):
    loaded = tiny_raw()
    with faults.build_fault(fault):
        result = execute(loaded)
    assert not result["correct"], result["checks"]


def test_reference_incidence_is_the_programs_rows_and_exact_jaccard():
    from repro.sketch.goldfinger import incidence_fingerprint, \
        jaccard_pairwise_auto
    cfg = tiny_raw()["config"]
    pop = data.generate(cfg["stats"], seed=SEED, n_pool=0)
    gf = incidence_fingerprint(system.dataset(pop, pop.n, "tiny"))
    words, card = reference_raw.incidence(pop.items, pop.offsets,
                                          pop.n_items)
    np.testing.assert_array_equal(np.asarray(gf.words).view(np.uint64),
                                  words)
    np.testing.assert_array_equal(np.asarray(gf.card), card)
    np.testing.assert_array_equal(card, pop.sizes)
    prog = np.asarray(jaccard_pairwise_auto(gf.words[:40], gf.card[:40],
                                            gf.words, gf.card))
    ref = gf_sim(gf_inter(words[:40, None, :], words[None, :, :]),
                 card[:40, None], card[None, :])
    np.testing.assert_array_equal(prog, ref)


def test_group_work_counts_a_hand_worked_case():
    # One cluster of 3 users on 328-word rows: 3 unordered pairs, each a
    # dot product of 10,496 bit planes (a multiply and an add per bit);
    # 8 configurations read each row of 1,312 bytes once.
    assert group_work.int8_ops(3 * 2, 328) == 3 * 2 * 10_496
    assert group_work.build_bytes(8, 3, 328) == 8 * 3 * 1_312
    kind = "TPU v5 lite"
    least = 31_488 / 819e9        # bytes bound: 62,976 ops take 0.16 ns
    assert group_work.roofline_pct(62_976, 31_488, 4 * least, kind) == \
        pytest.approx(25.0)
    with pytest.raises(KeyError):
        group_work.roofline_pct(1, 1, 1.0, "cpu")


def test_roofline_reader_reads_the_group_programs_and_the_counter():
    reader = run.layer_reader(run.BENCH, "build.group_knn_roofline_pct")
    trace = Reduced(window_s=10.0, busy_s=1.0, gaps={}, chips=1,
                    programs={"_group_knn": [0.5, 4]})
    view = types.SimpleNamespace(
        trace=trace, counts={"builds": 2}, device_kind="TPU v5 lite",
        work={"words": 328, "t": 8, "n_users": 69_816},
        program={"spans": [], "counters": {
            "repro.local_knn.pairs_useful": 2 * 3_450_000_000_000 // 10_496}})
    ops = 32 * 328 * view.program["counters"]["repro.local_knn.pairs_useful"]
    nbytes = 2 * 8 * 69_816 * 1_312
    want = 100 * max(ops / 393e12, nbytes / 819e9) / 0.5
    assert reader(view) == pytest.approx(want)
    view.trace = Reduced(window_s=10.0, busy_s=0.0, gaps={}, chips=1,
                         programs={})
    assert reader(view) is None
