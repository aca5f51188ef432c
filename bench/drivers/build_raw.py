"""Builds of the whole C² graph on exact Jaccard (Table V's raw data),
back to back.

The steps and span names of ``build``; the fingerprint step makes the
rows the configuration asks for through ``sketch_dataset``: incidence
rows of the whole item universe, so the group programs score them as an
int8 bit-plane matmul. ``check`` holds the last merged graph and the
whole index to ``bench/reference_raw.py``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import checks, data, reference_raw, system
from repro.sketch.goldfinger import sketch_dataset


def setup(ctx: system.Ctx) -> dict:
    cfg = ctx.config
    pop = data.generate(cfg["stats"], ctx.seed, n_pool=0)
    st = {"ctx": ctx, "pop": pop,
          "ds": system.dataset(pop, pop.n, cfg["name"]),
          "params": system.c2_params(cfg)}
    _, _, index = build_once(st)  # compiles every group shape
    st["words"] = int(index.words.shape[1])
    return st


def build_once(st: dict):
    from repro.core import clustering, local_knn, merge
    from repro.query import index as index_mod

    span = st["ctx"].spans.span
    ds, p = st["ds"], st["params"]
    with span("build.fingerprint"):
        gf = sketch_dataset(ds, p)
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
    return {"window_s": window, "attempted": n, "failed": 0,
            "metrics": {"build_s": window / n},
            "counts": {"builds": n},
            "notes": [f"{n} builds in {window:.3f} s; {plan.n_clusters} "
                      f"clusters, largest {int(plan.sizes.max())}; rows of "
                      f"{st['words']} words"],
            "graph": (np.asarray(graph.ids), np.asarray(graph.sims)),
            "index": index}


def work(st: dict) -> dict:
    """Shapes the group programs' roofline is counted from."""
    return {"words": st["words"], "t": st["params"].t,
            "n_users": st["pop"].n}


def check(st: dict, win: dict, control: bool = False) -> dict:
    """The window's last merged graph on a seeded sample of its users,
    and the index packaged from it, whole, against the reference of
    exact Jaccard over the reference's clusters (or, with ``control``,
    that reference in bfloat16 in the program's place)."""
    ctx, pop, graph = st["ctx"], st["pop"], win["graph"]
    b = ctx.config["build"]
    k, n = b["k"], pop.n
    users = system.sample(ctx.seed, 11, n, ctx.traffic["check_users"])
    words, card = reference_raw.incidence(pop.items, pop.offsets, pop.n_items)
    labels, paths = reference_raw.cluster_tables(pop.items, pop.offsets, b)
    cands = reference_raw.comembers(labels, users)
    ref_sims, _, every = reference_raw.graph_rows(users, cands, words, card,
                                                  k)
    if control:
        g_sims, g_ids, _ = reference_raw.graph_rows(
            users, cands, words, card, k, dtype=reference_raw.BF16)
    else:
        g_ids = graph[0][users].astype(np.int64)
        g_sims = graph[1][users].astype(np.float32)
    bad, sim_gap, rank_gap = 0, 0.0, 0.0
    for r, u in enumerate(users):
        cand = cands[r][0]
        ids, sims = g_ids[r], g_sims[r]
        bad += checks._row_faults(ids, sims, n, self_id=u)
        valid = (ids >= 0) & (ids < n)
        pos = np.minimum(np.searchsorted(cand, ids[valid]),
                         max(len(cand) - 1, 0))
        member = (cand[pos] == ids[valid]) if len(cand) else \
            np.zeros(int(valid.sum()), bool)
        bad += int(np.sum(~member)) + abs(int(valid.sum())
                                          - min(k, len(cand)))
        if member.any():
            gap = np.abs(sims[valid][member] - every[r][pos[member]])
            sim_gap = max(sim_gap, float(gap.max()))
        both_empty = ~np.isfinite(sims) & ~np.isfinite(ref_sims[r])
        diff = np.where(both_empty, 0.0,
                        np.abs(np.nan_to_num(sims, neginf=-1e9)
                               - np.nan_to_num(ref_sims[r], neginf=-1e9)))
        rank_gap = max(rank_gap, float(diff.max()))
    inv = reference_raw.Inverted(pop.items, pop.offsets)
    rec_users = system.sample(ctx.seed, 17, n, ctx.traffic["recall_users"])
    rec = [reference_raw.tie_aware_recall(graph[0][u],
                                          inv.jaccard(pop.profile(u)), k,
                                          exclude=u)
           for u in rec_users]
    numbers = {"bad_edges": bad, "sim_gap": sim_gap, "rank_gap": rank_gap}
    numbers.update(checks.check_index(win["index"], graph, words, card,
                                      labels, paths,
                                      reference_raw.hash_seeds(b)))
    return {"numbers": numbers, "recall": 100.0 * float(np.mean(rec)),
            "notes": [f"checked {len(users)} users against exact Jaccard; "
                      f"recall over {len(rec_users)}"]}
