"""Builds of the whole C² graph, back to back.

Each build runs the path ``knn_build`` runs, called step by step so that
a span sits at each boundary: GoldFinger fingerprints, the FRH cluster
plan, local KNN (host gather, device group programs, host scatter), the
merge, and index packaging (reverse adjacency, cluster tables). The
window closes at the end of the last build started; ``build_s`` is the
window over the builds completed.
"""
from __future__ import annotations

import time

import numpy as np

from bench import checks, data, system


def setup(ctx: system.Ctx) -> dict:
    cfg = ctx.config
    pop = data.generate(cfg["stats"], ctx.seed, n_pool=0)
    st = {"ctx": ctx, "pop": pop,
          "ds": system.dataset(pop, pop.n, cfg["name"]),
          "params": system.c2_params(cfg)}
    build_once(st)  # compiles, or loads from the cache, every group shape
    return st


def build_once(st: dict):
    from repro.core import clustering, local_knn, merge
    from repro.query import index as index_mod
    from repro.sketch import goldfinger

    span = st["ctx"].spans.span
    ds, p = st["ds"], st["params"]
    with span("build.fingerprint"):
        gf = goldfinger.fingerprint_dataset(ds, n_bits=p.n_bits, seed=p.seed)
    with span("build.cluster"):
        plan = clustering.build_plan(ds, p)
    with span("build.local_knn"):
        ids, sims = local_knn.local_knn(plan, gf, p)
    with span("build.merge"):
        graph = merge.merge_partial(ids, sims, p.k)
    with span("build.index"):
        index = index_mod.build_index(ds, p, gf=gf, plan=plan, graph=graph)
    return graph, plan, index


def measure(st: dict, seconds: float) -> dict:
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        graph, plan, index = build_once(st)
        n += 1
    window = time.perf_counter() - t0
    sizes = plan.sizes
    return {"window_s": window, "attempted": n, "failed": 0,
            "metrics": {"build_s": window / n},
            "counts": {"builds": n},
            "notes": [f"{n} builds in {window:.3f} s; {plan.n_clusters} "
                      f"clusters, largest {int(sizes.max())}, "
                      f"{int((sizes >= st['params'].bf_threshold).sum())} "
                      f"on the Hyrec branch"],
            "graph": (np.asarray(graph.ids), np.asarray(graph.sims)),
            "index": index}


def check(st: dict, win: dict, control: bool = False) -> dict:
    """The window's last merged graph and the index packaged from it."""
    return checks.check_graph(st["ctx"], st["pop"], win["graph"], control,
                              index=win["index"])
