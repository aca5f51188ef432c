"""Readings that the limits of ``correct`` are set from.

    python bench/readings.py --workload ml10M.build --seeds 11 12 13 \
        --control 3 --faults --seconds 2 --out readings.jsonl

In one process, for each seed: the cell's set-up, a short window at the
cell's own load, and the numbers of ``correct`` for the program; for the
first ``--control`` seeds also for the control (the reference in
bfloat16 in the program's place); with ``--faults``, for the first three
seeds, each fault of ``bench/faults.py`` (or those named) planted under
the timed path.
One JSON line per reading goes to ``--out`` and to standard output.
Like a run, it refuses anything but TPU devices.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def readings(loaded, seeds, seconds, n_control, with_faults, emit):
    from bench import faults, spans, system

    kind = loaded["traffic"]["driver"]
    drv = run.driver(kind)
    names = faults.BUILD if kind == "build" else faults.SERVE
    if with_faults:
        names = [n for n in names if n in with_faults]
    for i, seed in enumerate(seeds):
        ctx = system.Ctx(config=loaded["config"], traffic=loaded["traffic"],
                         seed=seed, spans=spans.Spans())
        t0 = time.perf_counter()
        st = drv.setup(ctx)
        run.settle()
        win = drv.measure(st, seconds)
        chk = drv.check(st, win)
        emit({"seed": seed, "side": "program", "numbers": chk["numbers"],
              "recall": chk["recall"], "metrics": win["metrics"],
              "seconds": time.perf_counter() - t0})
        if i < n_control:
            chk = drv.check(st, win, control=True)
            emit({"seed": seed, "side": "control", "numbers": chk["numbers"]})
        if with_faults is not None and i < 3:
            for name in names:
                fault = (faults.build_fault(name) if kind == "build" else
                         faults.serve_fault(name,
                                            loaded["traffic"]["batching"]))
                with fault:
                    win = drv.measure(st, seconds)
                chk = drv.check(st, win)
                emit({"seed": seed, "side": f"fault:{name}",
                      "numbers": chk["numbers"]})
        gc.unfreeze()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", nargs="*", metavar="NAME",
                    help="plant faults of bench/faults.py: the names "
                    "given, or every one the cell can have")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    loaded = run.load(args.workload)
    run.devices(int(loaded["cell"]["chips"]))
    import jax
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as f:
        def emit(rec):
            line = json.dumps(dict(rec, workload=args.workload))
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
        readings(loaded, args.seeds, args.seconds, args.control,
                 args.faults, emit)


if __name__ == "__main__":
    main()
