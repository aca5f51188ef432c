"""Each driver runs a whole cell through the harness's functions, at a
tiny size on the CPU, and comes out correct; the command itself refuses
the CPU."""
import io
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import run
from bench.tests.tiny import ROOT, tiny

CELLS = ["ml10M.build", "AM.build", "ml10M.serve_steady", "ml10M.serve_batch"]


def execute(loaded, seed=2**33 + 17, seconds=1.0, trace=False):
    err = io.StringIO()
    result = run.execute(loaded, seed, seconds, trace, jax.devices()[:1],
                         err=err)
    return result, err.getvalue()


@pytest.mark.parametrize("cell", CELLS)
def test_driver_runs_a_tiny_cell_correctly(cell):
    loaded = tiny(cell)
    result, err = execute(loaded)
    assert result["correct"], (result["checks"], err)
    assert result["attempted"] > 0 and result["failed"] == 0
    names = {m["name"] for m in loaded["end_to_end"]}
    assert set(result["metrics"]) == names
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    json.dumps(result)


def test_traced_run_reports_host_span_metrics():
    loaded = tiny("ml10M.serve_batch")
    result, _ = execute(loaded, trace=True)
    # No TPU plane on the CPU: device metrics are left out, host spans stay.
    assert "batch.wave_ms" in result["metrics"]
    assert "batch.descent_device_ms" not in result["metrics"]
    assert result["correct"]


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.environ.get(
                   "JAX_COMPILATION_CACHE_DIR", ""))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ml10M.build",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_compiles_inside_a_window_are_counted():
    with run.Compiles() as compiles:
        jax.jit(lambda x: x * 3 + 1)(2.0)
    assert compiles.count >= 1
