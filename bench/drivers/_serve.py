"""Set-up shared by the serving drivers: population, index, engine."""
from __future__ import annotations

import numpy as np

from bench import checks, data, system


def setup(ctx: system.Ctx) -> dict:
    from repro.query.engine import QueryEngine
    from repro.query.index import build_index

    cfg = ctx.config
    n_pool = int(cfg["query_pool"])
    pop = data.generate(cfg["stats"], ctx.seed, n_pool=n_pool)
    n_index = pop.n - n_pool
    ds = system.dataset(pop, n_index, cfg["name"])
    index = build_index(ds, system.c2_params(cfg))
    engine = QueryEngine(index, system.query_config(
        cfg, ctx.traffic["batching"]))
    return {"ctx": ctx, "pop": pop, "n_index": n_index, "n_pool": n_pool,
            "index": index, "engine": engine}


def pool_profile(st: dict, j: int) -> np.ndarray:
    return st["pop"].profile(st["n_index"] + int(j))


def check(st: dict, pool_rows, ids, sims, unanswered: int,
          control: bool) -> dict:
    """Check one seeded sample of the served requests, and score recall
    on another, larger one."""
    ctx = st["ctx"]
    n = len(pool_rows)
    pick = system.sample(ctx.seed, 13, n, ctx.traffic["check_requests"])
    rec = system.sample(ctx.seed, 17, n, ctx.traffic["recall_requests"])
    rows, ids, sims = (np.asarray(a) for a in (pool_rows, ids, sims))
    return checks.check_answers(
        ctx, st["pop"], st["n_index"], (rows[pick], ids[pick], sims[pick]),
        (rows[rec], ids[rec]), unanswered, control)


def hop_work(st: dict) -> dict:
    """Shapes that fix the bytes of a descent hop (bench/work.py)."""
    ix, spec = st["index"], st["engine"].plan.spec
    return {"beam": max(spec.beam, spec.k), "k_graph": ix.graph_ids.shape[1],
            "r_max": ix.rev_ids.shape[1], "words": ix.words.shape[1],
            "hops": spec.hops}
