"""What decides ``correct``: the program's output against the reference.

Every number here is a count or a gap, lower is better, and each is
held to the limit in ``bench/limits/<cell>.json``. With ``control`` the
reference computed in bfloat16 stands in the program's place; it has to
fail at least one limit.
"""
from __future__ import annotations

import numpy as np

from bench import reference, system


def _row_faults(ids, sims, n, self_id=None):
    """Edges that break the graph's form: an id out of range, the row's
    own id, a repeat, a PAD with a finite sim or an id with none, and
    sims out of descending order."""
    valid = ids >= 0
    bad = int(np.sum(ids >= n))
    bad += int(np.sum(valid & ~np.isfinite(sims)))
    bad += int(np.sum(~valid & np.isfinite(sims)))
    if self_id is not None:
        bad += int(np.sum(ids == self_id))
    v = ids[valid]
    bad += len(v) - len(np.unique(v))
    bad += int(np.sum(np.diff(sims[np.isfinite(sims)]) > 0))
    return bad


def _reverse_faults(graph_ids, rev_ids) -> int:
    """Entries of the reverse adjacency that break it: an id that is not
    an in-neighbour of its row (``v`` in row ``u`` needs the edge
    ``v → u``), a repeat, and per row the distance of its count from
    ``min(in-degree, r_max)``."""
    n, r_max = rev_ids.shape
    src = np.repeat(np.arange(n, dtype=np.int64), graph_ids.shape[1])
    dst = graph_ids.reshape(-1).astype(np.int64)
    ok = (dst >= 0) & (dst < n)
    edges = np.unique(src[ok] * n + dst[ok])
    indeg = np.bincount(edges % n, minlength=n)
    rev = rev_ids.astype(np.int64)
    valid = rev >= 0
    bad = int(np.sum(rev >= n))
    valid &= rev < n
    row = np.broadcast_to(np.arange(n, dtype=np.int64)[:, None], rev.shape)
    keys = rev[valid] * n + row[valid]
    bad += int(np.sum(~np.isin(keys, edges)))
    bad += len(keys) - len(np.unique(keys))
    bad += int(np.abs(valid.sum(axis=1) - np.minimum(indeg, r_max)).sum())
    return bad


def _cluster_faults(index, labels, paths, seeds) -> int:
    """Cluster tables that differ from the reference's clustering: per
    configuration, users whose membership differs, clusters split or
    merged against the reference's, clusters whose split path differs,
    a user listed twice, and hash seeds that differ."""
    bad = int(np.sum(np.asarray(index.hash_seeds, np.int64) != seeds))
    if len(index.hash_seeds) != len(seeds):
        return bad + abs(len(index.hash_seeds) - len(seeds))
    t, n = labels.shape
    offsets = np.asarray(index.cluster_offsets, np.int64)
    sizes = np.diff(offsets)
    members = np.asarray(index.cluster_members, np.int64)[offsets[0]:
                                                          offsets[-1]]
    config = np.asarray(index.cluster_config, np.int64)
    cid = np.repeat(np.arange(len(sizes)), sizes)
    m_cfg = config[cid]
    out_of_range = (members < 0) | (members >= n) | (m_cfg < 0) | (m_cfg >= t)
    bad += int(out_of_range.sum())
    members, cid, m_cfg = (a[~out_of_range] for a in (members, cid, m_cfg))
    prog_paths = np.asarray(index.cluster_paths, np.int64)
    for i in range(t):
        sel = m_cfg == i
        users, ci = members[sel], cid[sel]
        bad += len(users) - len(np.unique(users))
        mine = np.full(n, -1, np.int64)
        mine[users] = ci
        ref = labels[i]
        bad += int(np.sum((ref >= 0) != (mine >= 0)))
        both = (ref >= 0) & (mine >= 0)
        pairs = np.unique(ref[both] * len(sizes) + mine[both])
        bad += 2 * len(pairs) - len(np.unique(ref[both])) \
            - len(np.unique(mine[both]))
        # Each cluster's path against its first member's reference label's.
        first = np.unique(ci, return_index=True)
        c_ids, u_first = first[0], users[first[1]]
        lab = ref[u_first]
        has = lab >= 0
        want = paths[i][lab[has]]
        got = prog_paths[c_ids[has]]
        width = min(want.shape[1], got.shape[1])
        bad += int(np.any(want[:, :width] != got[:, :width], axis=1).sum())
        bad += int(np.any(got[:, width:] != int(reference.NO_HASH),
                          axis=1).sum())
    return bad


def _index_row_faults(index, graph, words, card) -> int:
    """Rows whose graph copy, GoldFinger words or cardinality in the
    index differ from the merged graph and the reference fingerprints."""
    n = len(card)
    ids = np.asarray(index.graph_ids)
    sims = np.asarray(index.graph_sims)
    if ids.shape != np.asarray(graph[0]).shape:
        return n
    bad = ~np.all(ids == np.asarray(graph[0]), axis=1)
    bad |= ~np.all(sims.view(np.uint32)
                   == np.asarray(graph[1], np.float32).view(np.uint32),
                   axis=1)
    w = np.ascontiguousarray(index.words, np.uint32).view(np.uint64)
    if w.shape != words.shape:
        return n
    bad |= ~np.all(w == words, axis=1)
    bad |= np.asarray(index.card, np.int64) != card
    return int(bad.sum())


def check_index(index, graph, words, card, labels, paths, seeds) -> dict:
    """The packaged index against the merged graph it was given and the
    reference's fingerprints and clustering: every row, every cluster."""
    return {"index_row_faults": _index_row_faults(index, graph, words, card),
            "rev_faults": _reverse_faults(np.asarray(graph[0]),
                                          np.asarray(index.rev_ids)),
            "cluster_faults": _cluster_faults(index, labels, paths, seeds)}


def check_graph(ctx: system.Ctx, pop, graph, control: bool = False,
                index=None) -> dict:
    """The merged C² graph on a seeded sample of its users and, given
    ``index``, the index packaged from it, whole."""
    b = ctx.config["build"]
    k, n = b["k"], pop.n
    users = system.sample(ctx.seed, 11, n, ctx.traffic["check_users"])
    words, card = reference.fingerprints(pop.items, pop.offsets,
                                         b["n_bits"], b["seed"])
    labels, paths = reference.cluster_tables(pop.items, pop.offsets, b)
    cands = reference.comembers(labels, users)
    ref_sims, ref_ids, every = reference.graph_rows(users, cands, words,
                                                    card, k)
    if control:
        g_sims, g_ids, _ = reference.graph_rows(users, cands, words, card, k,
                                                dtype=reference.BF16)
    else:
        g_ids = np.asarray(graph[0])[users].astype(np.int64)
        g_sims = np.asarray(graph[1])[users].astype(np.float32)
    bad, sim_gap, rank_gap = 0, 0.0, 0.0
    for r, u in enumerate(users):
        cand = cands[r][0]
        ids, sims = g_ids[r], g_sims[r]
        bad += _row_faults(ids, sims, n, self_id=u)
        valid = (ids >= 0) & (ids < n)
        pos = np.minimum(np.searchsorted(cand, ids[valid]),
                         max(len(cand) - 1, 0))
        member = (cand[pos] == ids[valid]) if len(cand) else \
            np.zeros(int(valid.sum()), bool)
        bad += int(np.sum(~member))
        bad += abs(int(valid.sum()) - min(k, len(cand)))
        if member.any():
            gap = np.abs(sims[valid][member] - every[r][pos[member]])
            sim_gap = max(sim_gap, float(gap.max()))
        both_empty = ~np.isfinite(sims) & ~np.isfinite(ref_sims[r])
        diff = np.where(both_empty, 0.0,
                        np.abs(np.nan_to_num(sims, neginf=-1e9)
                               - np.nan_to_num(ref_sims[r], neginf=-1e9)))
        rank_gap = max(rank_gap, float(diff.max()))
    hyrec = sum(c[1] >= b["rho"] * k * k for c in cands)
    inv = reference.Inverted(pop.items, pop.offsets)
    rec_users = system.sample(ctx.seed, 17, n, ctx.traffic["recall_users"])
    rec = [reference.tie_aware_recall(np.asarray(graph[0][u]),
                                      inv.jaccard(pop.profile(u)), k,
                                      exclude=u)
           for u in rec_users]
    numbers = {"bad_edges": bad, "sim_gap": sim_gap, "rank_gap": rank_gap}
    if index is not None:
        numbers.update(check_index(index, graph, words, card, labels, paths,
                                   reference.hash_seeds(b)))
    return {"numbers": numbers,
            "recall": 100.0 * float(np.mean(rec)),
            "notes": [f"checked {len(users)} users, {hyrec} of them in a "
                      f"cluster on the Hyrec branch; recall over "
                      f"{len(rec_users)}"]}


def check_answers(ctx: system.Ctx, pop, n_index: int, checked, scored,
                  unanswered: int, control: bool = False) -> dict:
    """Served answers against the reference: ``checked`` (pool rows,
    ids, sims of a sample of requests) against brute force over the
    index's users by GoldFinger; ``scored`` (pool rows, ids of another
    sample) for recall against exact Jaccard."""
    pool_rows, ids, sims = checked
    s = ctx.config["serve"]
    b = ctx.config["build"]
    k = s["k"]
    index_pop = pop.rows(0, n_index)
    words, card = reference.fingerprints(index_pop.items, index_pop.offsets,
                                         b["n_bits"], b["seed"])
    queries = [pop.profile(n_index + int(j)) for j in pool_rows]
    q_items = np.concatenate(queries)
    q_off = np.r_[0, np.cumsum([len(q) for q in queries])]
    qw, qc = reference.fingerprints(q_items, q_off, b["n_bits"], b["seed"])
    ref_top, _ = reference.gf_topk_all(qw, qc, words, card, k)
    if control:
        sims, ids = reference.gf_topk_all(qw, qc, words, card, k,
                                          dtype=reference.BF16)
    ids = np.asarray(ids).astype(np.int64)
    sims = np.asarray(sims).astype(np.float32)
    bad, sim_gap, miss = 0, 0.0, 0
    for r in range(len(queries)):
        bad += _row_faults(ids[r], sims[r], n_index)
        valid = (ids[r] >= 0) & (ids[r] < n_index)
        v = ids[r][valid]
        true = reference.gf_sim(reference.gf_inter(qw[r][None, :], words[v]),
                                qc[r], card[v]).astype(np.float32)
        if len(v):
            sim_gap = max(sim_gap, float(np.abs(sims[r][valid] - true).max()))
        miss += k - int(np.sum(true >= ref_top[r, k - 1]))
    inv = reference.Inverted(index_pop.items, index_pop.offsets)
    rec = [reference.tie_aware_recall(
        np.asarray(row_ids), inv.jaccard(pop.profile(n_index + int(j))), k)
        for j, row_ids in zip(*scored)]
    return {"numbers": {"unanswered": unanswered, "bad_ids": bad,
                        "sim_gap": sim_gap,
                        "miss_share": miss / (k * max(len(queries), 1))},
            "recall": 100.0 * float(np.mean(rec)) if rec else 0.0,
            "notes": [f"checked {len(queries)} served requests; recall "
                      f"over {len(rec)}"]}
