"""Closed loop of waves: ``max_wave`` queries at a time, back to back.

Each wave goes through ``QueryEngine.query_batch``: host routing of the
whole wave, then one compiled descent program. The next wave starts
when the previous one has returned. The window closes at the end of the
last wave started; ``queries_per_s`` is the queries answered over it.
Profiles are drawn uniformly from the held-out pool.
"""
from __future__ import annotations

import time

import numpy as np

from bench.drivers import _serve


def setup(ctx):
    st = _serve.setup(ctx)
    eng = st["engine"]
    wave = eng.qc.max_wave
    rng = np.random.default_rng([ctx.seed, 3])
    for _ in range(int(ctx.traffic["warmup_waves"])):
        rows = rng.integers(0, st["n_pool"], wave)
        eng.query_batch([_serve.pool_profile(st, j) for j in rows])
    return st


def measure(st: dict, seconds: float) -> dict:
    ctx, eng = st["ctx"], st["engine"]
    wave = eng.qc.max_wave
    rng = np.random.default_rng([ctx.seed, 7])
    span = ctx.spans.span
    rows_all, ids_all, sims_all = [], [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        rows = rng.integers(0, st["n_pool"], wave)
        profiles = [_serve.pool_profile(st, j) for j in rows]
        with span("batch.wave"):
            ids, sims = eng.query_batch(profiles)
        rows_all.append(rows)
        ids_all.append(ids)
        sims_all.append(sims)
    window = time.perf_counter() - t0
    n = len(rows_all) * wave
    return {"window_s": window, "attempted": n, "failed": 0,
            "metrics": {"queries_per_s": n / window},
            "counts": {"waves": len(rows_all), "queries": n},
            "notes": [f"{len(rows_all)} waves of {wave} in {window:.3f} s"],
            "rows": np.concatenate(rows_all), "ids": np.concatenate(ids_all),
            "sims": np.concatenate(sims_all)}


def check(st, win, control=False):
    return _serve.check(st, win["rows"], win["ids"], win["sims"], 0, control)


def work(st):
    return _serve.hop_work(st)
