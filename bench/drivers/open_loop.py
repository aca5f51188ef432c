"""Open loop: requests arrive on a fixed schedule, whatever the server does.

The arrival arithmetic is that of ``benchmarks/query_bench.open_loop``:
a request's latency counts from the time it was due, not from when the
loop got round to enqueueing it, so a stall shows on every request
behind it. The schedule is Poisson at ``rate_qps``, conditioned on its
count: exactly ``round(rate_qps · seconds)`` arrivals, uniform over the
window, so every seed offers the same work in another order. Each
request's profile is drawn uniformly from the held-out pool.

After the window the loop drains for at most ``drain_s``. A request due
in the window that was shed, failed, or is not done by then counts as
missing, at infinite latency, in ``query_p95_ms``.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench.drivers import _serve


def setup(ctx):
    from repro.query.engine import QueryRequest

    st = _serve.setup(ctx)
    eng = st["engine"]
    rng = np.random.default_rng([ctx.seed, 3])
    warm = rng.integers(0, st["n_pool"], int(ctx.traffic["warmup_queries"]))
    for i, j in enumerate(warm):
        eng.submit(QueryRequest(rid=-1 - i, profile=_serve.pool_profile(st, j)))
    eng.run()   # compiles, or loads, the admit / hop programs
    eng.done.clear()
    return st


def schedule(seed: int, rate: float, seconds: float, n_pool: int):
    """(due times in seconds from the window's start, pool rows)."""
    rng = np.random.default_rng([seed, 5])
    n = max(1, int(round(rate * seconds)))
    return np.sort(rng.uniform(0.0, seconds, n)), rng.integers(0, n_pool, n)


def p95(latencies: np.ndarray) -> float:
    """Nearest-rank 95th percentile (missing requests are +inf)."""
    s = np.sort(latencies)
    return float(s[max(0, math.ceil(0.95 * len(s)) - 1)])


def measure(st: dict, seconds: float) -> dict:
    from repro.query.engine import QueryRequest

    ctx, eng = st["ctx"], st["engine"]
    tr = ctx.traffic
    due, rows = schedule(ctx.seed, float(tr["rate_qps"]), seconds,
                         st["n_pool"])
    reqs = [QueryRequest(rid=i, profile=_serve.pool_profile(st, j))
            for i, j in enumerate(rows)]
    late = np.zeros(len(reqs))
    clock, span = time.perf_counter, ctx.spans.span
    steps0 = eng.n_ticks
    t0 = clock()
    i, steps = 0, 0
    while True:
        now = clock() - t0
        while i < len(reqs) and due[i] <= now:
            reqs[i].t_submit = t0 + due[i]
            late[i] = now - due[i]
            eng.queue.append(reqs[i])
            i += 1
        if eng.busy():
            with span("steady.step"):
                eng.step()
            steps += 1
        elif i < len(reqs):
            time.sleep(max(min(due[i] - now, 0.002), 0.0))
        else:
            break
        if now > seconds + float(tr["drain_s"]):
            break
    t_end = clock() - t0
    lat = np.array([r.t_done - r.t_submit if r.status == "done"
                    and r.t_done > 0 else np.inf for r in reqs])
    done = np.isfinite(lat)
    ids = np.stack([r.ids if r.ids is not None else
                    np.full(eng.qc.k, -1, np.int32) for r in reqs])
    sims = np.stack([r.sims if r.sims is not None else
                     np.full(eng.qc.k, -np.inf, np.float32) for r in reqs])
    return {"window_s": seconds, "attempted": len(reqs),
            "failed": int((~done).sum()),
            "metrics": {"query_p95_ms": 1e3 * p95(lat)},
            "counts": {"steps": steps, "hop_ticks": eng.n_ticks - steps0,
                       "served": int(done.sum()),
                       "hop_queries": eng.plan.descent_stats["hop_queries"]},
            "notes": [f"{len(reqs)} requests due at {tr['rate_qps']} q/s; "
                      f"served {int(done.sum())}; drained at {t_end:.3f} s; "
                      f"{steps} steps; p50 "
                      f"{1e3 * float(np.median(lat)):.3f} ms; generator "
                      f"late by p95 {1e3 * p95(late):.3f} ms, max "
                      f"{1e3 * float(late.max()):.3f} ms"],
            "drain_s": t_end - seconds,
            "in_window": int(sum(1 for r in reqs if r.status == "done"
                                 and r.t_done - t0 <= seconds)),
            "rows": rows[done], "ids": ids[done], "sims": sims[done],
            "unanswered": int((~done).sum())}


def check(st, win, control=False):
    return _serve.check(st, win["rows"], win["ids"], win["sims"],
                        win["unanswered"], control)


def work(st):
    return _serve.hop_work(st)
