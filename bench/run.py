"""Run one benchmark cell and print its result as one JSON line.

    python bench/run.py --workload ml10M.build --seed 7 --seconds 10 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
it needs is found by name: the configuration file the entry's config
names, ``bench/traffic/<traffic>.json``, the load generator
``bench/drivers/<driver>.py`` that the traffic file names, the limits of
``correct`` in ``bench/limits/<cell>.json``, and one reader
``bench/layer_metrics/<metric>.py`` per per-layer metric.

A run sets the cell up (data from ``--seed``, the build, warm-up of every
shape the window uses; all of it ``setup_s``), measures for
``--seconds``, then checks the window's output against the plain
reference (``bench/checks.py``). With ``--trace 0`` the result carries
the cell's end-to-end metrics; with ``--trace 1`` the window runs under
the profiler and the result carries the per-layer metrics, ``busy_s``,
``window_s`` and a breakdown. The numbers compared for ``correct`` come
last, on standard error and under ``checks`` in the result.

The run refuses anything but TPU devices, and fewer than the cell asks
for, with a non-zero exit and no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


class NoDevice(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load(workload: str, root: Path = ROOT, spec: dict | None = None) -> dict:
    """Everything a cell names, read from the benchmark's data files
    (``spec``: the contents of ``BENCHMARK.json``, read if not given)."""
    spec = spec or _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / "bench"
    traffic = _json(bench / "traffic" / f"{cell['traffic']}.json")
    limits_path = bench / "limits" / f"{workload}.json"

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell,
            "config": _json(root / configs[cell["config"]]["file"]),
            "traffic": traffic,
            "limits": _json(limits_path)["limits"] if limits_path.exists()
            else {},
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)],
            "bench": bench}


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def layer_reader(bench: Path, name: str):
    """The ``read`` function of ``<bench>/layer_metrics/<name>.py``."""
    path = bench / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(chips: int):
    """The devices a run uses; raises NoDevice unless they are TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"bench/run.py needs a TPU; JAX found "
                       f"{devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def _device_record(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


class View:
    """What a per-layer reader sees of a traced run."""

    def __init__(self, spans, win, trace, work, device_kind):
        self.spans = spans
        self.counts = win["counts"]
        self.window_s = win["window_s"]
        self.trace = trace            # bench.trace.Reduced, or None
        self.work = work
        self.device_kind = device_kind


def settle() -> None:
    """End of set-up: collect, then move every object alive now (the
    runtime, the index, the data) out of the collector's reach, so that a
    full collection in the window scans only what the window allocates."""
    gc.collect()
    gc.freeze()


class GcPauses:
    """Collector pauses while active, for a note on stderr."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t = 0.0

    def _cb(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def note(self) -> str:
        longest = 1e3 * max(self.pauses, default=0.0)
        return (f"full collections in the window: {len(self.pauses)}, "
                f"longest {longest:.3f} ms")


class Compiles:
    """Programs JAX compiles, or loads from its cache, while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _cb(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._cb)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._cb)


def _traces() -> int:
    """The program's own jit-trace counters (repro.sched.trace)."""
    from repro.sched import trace as program_trace
    return sum(program_trace.counts().values())


def execute(loaded: dict, seed: int, seconds: float, trace: bool, devs,
            t_start: float = T_START, err=sys.stderr):
    """Set up, measure and check one cell; returns the result dict."""
    from bench import spans as spans_mod
    from bench import system
    from bench import trace as trace_mod

    spans = spans_mod.Spans(annotate=trace)
    ctx = system.Ctx(config=loaded["config"], traffic=loaded["traffic"],
                     seed=seed, spans=spans)
    drv = driver(loaded["traffic"]["driver"])
    st = drv.setup(ctx)
    settle()
    setup_s = time.perf_counter() - t_start
    spans.clear()
    traces0 = _traces()
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with (spans.span("bench.window"), GcPauses() as pauses,
              Compiles() as compiles):
            win = drv.measure(st, seconds)
    finally:
        if trace:
            import jax
            jax.profiler.stop_trace()
    traced = _traces() - traces0
    device = _device_record(devs)
    reduced = None
    if trace:
        try:
            reduced = trace_mod.reduce(trace_mod.find_xplane(log_dir))
        except (FileNotFoundError, ValueError) as e:
            print(f"[bench] trace not reduced: {e}", file=err)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    for note in win["notes"] + [pauses.note()]:
        print(f"[bench] {note}", file=err)
    print(f"[bench] inside the window: {compiles.count} compiles, "
          f"{traced} jit traces; setup {setup_s:.3f} s", file=err)
    t_check = time.perf_counter()
    chk = drv.check(st, win)
    for note in chk["notes"]:
        print(f"[bench] {note}", file=err)
    print(f"[bench] reference check took {time.perf_counter() - t_check:.3f}"
          f" s", file=err)
    limits = loaded["limits"]
    checks = {name: {"value": value, "limit": limits.get(name)}
              for name, value in chk["numbers"].items()}
    correct = (win["attempted"] > 0 and bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values()))
    metrics = {}
    if trace:
        work = drv.work(st) if hasattr(drv, "work") else {}
        view = View(spans, win, reduced, work, device["kind"])
        for m in loaded["per_layer"]:
            value = layer_reader(loaded["bench"], m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
    else:
        values = dict(win["metrics"], setup_s=setup_s, recall=chk["recall"])
        for m in loaded["end_to_end"]:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    result = {"correct": correct, "attempted": int(win["attempted"]),
              "failed": int(win["failed"]), "metrics": metrics,
              "device": device}
    if reduced is not None:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    return result


def _fmt(c: dict) -> str:
    ok = c["limit"] is not None and c["value"] <= c["limit"]
    return f"{c['value']!r} limit {c['limit']!r} {'ok' if ok else 'FAIL'}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    loaded = load(args.workload)
    devs = devices(int(loaded["cell"]["chips"]))
    import jax
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = execute(loaded, args.seed, args.seconds, bool(args.trace), devs)
    for name, c in result["checks"].items():
        print(f"[check] {name} {_fmt(c)}", file=sys.stderr)
    sys.stderr.flush()
    if not all(math.isfinite(m["value"]) for m in result["metrics"].values()):
        print("[bench] a metric is not finite", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
