"""A tiny copy of a cell, for CPU tests: the same files and drivers at a
size a test run holds."""
from __future__ import annotations

import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_STATS = {"n_users": 1200, "n_items": 600, "mean_profile": 30.0,
              "n_topics": 8, "min_profile": 8}
TINY_BUILD = {"max_cluster": 150, "b": 512}
TINY_SERVE = {"slots": 8, "max_wave": 64}
TINY_TRAFFIC = {"check_users": 64, "recall_users": 32, "rate_qps": 200,
                "drain_s": 30, "warmup_queries": 32, "warmup_waves": 1,
                "check_requests": 48,
                "recall_requests": 96}


# The open-loop cell is parked (its p95 spread too widely on one chip to
# be admitted; PERF.md section 7); its files stay, and the CPU tests
# drive it through these entries.
PARKED = {
    "workloads": [{"name": "ml10M.serve_steady", "config": "ml10M",
                   "traffic": "serve_steady", "chips": 1, "why": "parked"}],
    "end_to_end": [{"name": "query_p95_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["ml10M.serve_steady"]}],
    "per_layer": [{"name": n, "unit": u, "better": b, "source": src,
                   "layer": "parked", "moves": "query_p95_ms",
                   "workloads": ["ml10M.serve_steady"]}
                  for n, u, b, src in (
                      ("steady.tick_ms", "ms", "lower", "host_clock"),
                      ("steady.hop_device_ms", "ms", "lower", "device_trace"),
                      ("steady.hop_roofline_pct", "%", "higher",
                       "device_trace"),
                      ("steady.idle_pct", "%", "lower", "device_trace"))],
}


def spec_with_parked() -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in PARKED.items():
        spec[key] += entries
    return spec


def tiny(workload: str) -> dict:
    """``run.load(workload)`` with the configuration and traffic shrunk."""
    from bench import run
    loaded = copy.deepcopy(run.load(workload, ROOT, spec_with_parked()))
    cfg = loaded["config"]
    cfg["stats"].update(TINY_STATS)
    cfg["build"].update(TINY_BUILD)
    cfg["serve"].update(TINY_SERVE)
    cfg["query_pool"] = 256
    for key, value in TINY_TRAFFIC.items():
        if key in loaded["traffic"]:
            loaded["traffic"][key] = value
    return loaded

