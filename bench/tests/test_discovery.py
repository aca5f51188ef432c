"""A new configuration, traffic mix and per-layer metric are added by new
files and new entries alone: the harness finds each by its name."""
import io
import json
import shutil

import jax

from bench import run
from bench.tests.tiny import ROOT, TINY_BUILD, TINY_SERVE, TINY_STATS

METRIC = '''def read(run):
    return 1e3 * run.window_s / max(run.counts["waves"], 1)
'''


def test_new_files_and_entries_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "AM.json").read_text())
    cfg["name"] = "tinyAM"
    cfg["stats"].update(TINY_STATS)
    cfg["build"].update(TINY_BUILD)
    cfg["serve"].update(TINY_SERVE)
    cfg["query_pool"] = 128
    (bench / "configs" / "tinyAM.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "serve_batch.json").read_text())
    traffic.update(check_requests=32, warmup_waves=1)
    (bench / "traffic" / "small_waves.json").write_text(json.dumps(traffic))
    (bench / "layer_metrics" / "batch.window_per_wave_ms.py").write_text(
        METRIC)
    limits = json.loads((bench / "limits" / "ml10M.serve_batch.json")
                        .read_text())
    (bench / "limits" / "tinyAM.small_waves.json").write_text(
        json.dumps(limits))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tinyAM", "source": "test",
                            "file": "bench/configs/tinyAM.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tinyAM.small_waves",
                              "config": "tinyAM", "traffic": "small_waves",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "queries_per_s":
            m["workloads"].append("tinyAM.small_waves")
    spec["per_layer"].append({"name": "batch.window_per_wave_ms",
                              "unit": "ms", "better": "lower",
                              "source": "host_clock", "layer": "test",
                              "moves": "queries_per_s",
                              "workloads": ["tinyAM.small_waves"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    loaded = run.load("tinyAM.small_waves", root)
    assert loaded["config"]["name"] == "tinyAM"
    assert loaded["traffic"]["check_requests"] == 32
    assert [m["name"] for m in loaded["per_layer"]] == [
        "batch.window_per_wave_ms"]
    assert "queries_per_s" in {m["name"] for m in loaded["end_to_end"]}
    result = run.execute(loaded, 2**33 + 1, 0.5, True, jax.devices()[:1],
                         err=io.StringIO())
    assert result["correct"], result["checks"]
    assert result["metrics"]["batch.window_per_wave_ms"]["value"] > 0


def test_every_entry_of_the_benchmark_has_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = ROOT / "bench"
    for cell in spec["workloads"]:
        loaded = run.load(cell["name"])
        assert (bench / "drivers" / f"{loaded['traffic']['driver']}.py"
                ).exists()
        assert set(loaded["limits"]) >= {"sim_gap"}
        assert any(m["name"] == "setup_s" for m in loaded["end_to_end"])
        assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]
    for m in spec["per_layer"]:
        assert (bench / "layer_metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
