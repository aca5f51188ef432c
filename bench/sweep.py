"""Find the knee of an open-loop serving cell: the highest offered rate
the system sustains.

    python bench/sweep.py --workload ml10M.serve_steady --seed 5 \
        --rates 600 800 1000 1200 --seconds 8

One set-up, then one open-loop window per rate, in the order given. For
each rate it prints the rate served inside the window, how long the
backlog took to drain after it, and the 95th-percentile latency. A rate
is sustained when the window serves at least 98% of what it offered and
the backlog drains within one tenth of a second. The cell's traffic file
holds a fixed rate found this way; the benchmark itself never searches.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    loaded = run.load(args.workload)
    run.devices(int(loaded["cell"]["chips"]))
    import jax
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import spans, system
    drv = run.driver(loaded["traffic"]["driver"])
    ctx = system.Ctx(config=loaded["config"],
                     traffic=copy.deepcopy(loaded["traffic"]),
                     seed=args.seed, spans=spans.Spans())
    st = drv.setup(ctx)
    run.settle()
    knee = None
    for rate in args.rates:
        ctx.traffic["rate_qps"] = rate
        win = drv.measure(st, args.seconds)
        served = win["in_window"] / args.seconds
        ok = served >= 0.98 * rate and win["drain_s"] <= 0.1
        if ok:
            knee = rate if knee is None else max(knee, rate)
        print(json.dumps({"rate_qps": rate, "served_in_window_qps": served,
                          "drain_s": win["drain_s"],
                          "query_p95_ms": win["metrics"]["query_p95_ms"],
                          "sustained": ok}), flush=True)
        st["engine"].done.clear()
    print(json.dumps({"knee_qps": knee}), flush=True)


if __name__ == "__main__":
    main()
