"""Step 2 of C²: per-cluster partial KNN graphs (paper Alg. 2).

The paper hands each cluster to a thread and switches between brute force
(|C| < ρk²) and Hyrec. The TPU-native version batches clusters of similar
size into padded capacity groups and vmaps one fused similarity+top-k over
each group — every cluster in a group is processed by the same program, so
there is no divergence and no synchronization.

Capacity groups are powers of two ≥ 32, so padding waste is < 2× and each
group compiles once per (capacity, k). The group program scores GoldFinger
sketches (W = 32 words at 1,024 bits) with the VPU popcount and incidence
rows (W = |I|/32, 328 words for MovieLens-10M) as an int8 bit-plane matmul
on the MXU; PERF.md §3 lists the spans and counters each metric reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustering import ClusterPlan
from repro.core.params import C2Params
from repro.sched import trace
from repro.sketch.goldfinger import GoldFinger, jaccard_pairwise_auto
from repro.types import NEG_INF, PAD_ID


def capacity_of(size: int, minimum: int = 32) -> int:
    c = minimum
    while c < size:
        c *= 2
    return c


@functools.partial(jax.jit, static_argnames=("k",))
def _group_knn(words, card, member_ids, k: int):
    """Brute-force KNN inside each padded cluster of one capacity group.

    words: uint32[m, cap, W]; card: int32[m, cap];
    member_ids: int32[m, cap] global user ids (PAD_ID padded).
    Returns (nbr_ids int32[m, cap, k] global ids, sims float32[m, cap, k]).
    """

    def one_cluster(w, c, ids):
        # Width-dispatched estimator: VPU popcount for GoldFinger-width
        # sketches, MXU bit-plane matmul for raw-incidence widths —
        # identical results, different compute layout.
        sims = jaccard_pairwise_auto(w, c, w, c)  # [cap, cap]
        valid = ids != PAD_ID
        cap = ids.shape[0]
        eye = jnp.eye(cap, dtype=bool)
        mask = valid[None, :] & valid[:, None] & ~eye
        sims = jnp.where(mask, sims, NEG_INF)
        top_sims, pos = jax.lax.top_k(sims, k)
        nbr = jnp.where(top_sims == NEG_INF, PAD_ID, ids[pos])
        return nbr, top_sims

    return jax.vmap(one_cluster)(words, card, member_ids)


def _pallas_group_knn(words, card, member_ids, k: int):
    """Same contract as :func:`_group_knn`, through the Pallas kernel."""
    from repro.kernels.goldfinger_knn import ops as gk_ops

    return gk_ops.cluster_knn(words, card, member_ids, k)


def _hyrec_cluster(members: np.ndarray, gf: GoldFinger, k: int,
                   max_iters: int):
    """Alg. 2's greedy branch: Hyrec restricted to one (huge) cluster."""
    from repro.knn.greedy import hyrec  # local import: avoids cycle

    sub = GoldFinger(words=np.asarray(gf.words)[members],
                     card=np.asarray(gf.card)[members])
    graph, _ = hyrec(sub, k=min(k, len(members) - 1), max_iters=max_iters)
    # Map local indices back to global user ids.
    nbr = np.where(graph.ids == PAD_ID, PAD_ID,
                   members[np.where(graph.ids == PAD_ID, 0, graph.ids)])
    sims = graph.sims
    if nbr.shape[1] < k:  # pad narrow neighborhoods up to k
        pad = k - nbr.shape[1]
        nbr = np.pad(nbr, ((0, 0), (0, pad)), constant_values=PAD_ID)
        sims = np.pad(sims, ((0, 0), (0, pad)), constant_values=NEG_INF)
    return nbr.astype(np.int32), sims.astype(np.float32)


def local_knn(plan: ClusterPlan, gf: GoldFinger, params: C2Params):
    """Compute partial KNNs for every cluster; scatter per configuration.

    Implements Alg. 2's hybrid: clusters with |C| < ρk² go through the
    batched brute-force path (the common case — the paper picks N < ρk²
    deliberately); larger ones run Hyrec restricted to the cluster.

    Returns (ids int32[t, n, k], sims float32[t, n, k]) — for each hash
    configuration, each user's neighbors within its cluster (PAD_ID where
    the cluster was smaller than k+1 or the user was unclustered).
    """
    t, n, k = plan.t, plan.n_users, params.k
    with trace.span("repro.local_knn"):
        out_ids = np.full((t, n, k), PAD_ID, dtype=np.int32)
        out_sims = np.full((t, n, k), NEG_INF, dtype=np.float32)

        sizes = plan.sizes
        # Alg. 2 switch: brute force iff |C| < ρk².
        greedy_idx = np.flatnonzero(sizes >= params.bf_threshold)
        if len(greedy_idx):
            with trace.span("repro.local_knn.hyrec"):
                for ci in greedy_idx:
                    cfg = plan.config_of[ci]
                    users = plan.members[ci]
                    nbr, sims = _hyrec_cluster(users, gf, k,
                                               max_iters=params.rho)
                    out_ids[cfg, users] = nbr
                    out_sims[cfg, users] = sims

        brute = np.ones(len(sizes), dtype=bool)
        brute[greedy_idx] = False
        caps = np.array([capacity_of(int(s)) for s in sizes], dtype=np.int64)
        caps = np.where(brute, caps, -1)  # exclude greedy clusters below
        words_h = np.asarray(gf.words)
        card_h = np.asarray(gf.card)
        W = words_h.shape[1]

        # Bound per-group batch memory: sims [m, cap, cap] f32 AND the
        # gathered fingerprints [m, cap, W] (wide in raw-incidence mode).
        sim_budget = 256 << 20  # 256 MB

        for cap in np.unique(caps):
            if cap < 0:
                continue
            idx = np.flatnonzero(caps == cap)
            m_max = max(1, int(sim_budget // max(cap * cap * 4,
                                                 cap * W * 4 * 4)))
            for s in range(0, len(idx), m_max):
                batch = idx[s:s + m_max]
                # Pad the cluster count to a power of two so each
                # (capacity, m) group shape compiles once, not once per
                # batch remainder.
                m = capacity_of(len(batch), minimum=1)
                useful = 0  # ordered pairs of distinct co-members
                with trace.span("repro.local_knn.gather"):
                    mem = np.full((m, cap), PAD_ID, dtype=np.int32)
                    for j, ci in enumerate(batch):
                        size = int(sizes[ci])
                        mem[j, :size] = plan.members[ci]
                        useful += size * (size - 1)
                    gmem = np.where(mem == PAD_ID, 0, mem)
                    w = words_h[gmem].reshape(m, cap, W)
                    c = np.where(mem == PAD_ID, 0, card_h[gmem])
                fn = _pallas_group_knn if params.use_pallas else _group_knn
                with trace.span("repro.local_knn.device"):
                    nbr, sims = fn(jnp.asarray(w), jnp.asarray(c),
                                   jnp.asarray(mem), k)
                    nbr = np.asarray(nbr)[: len(batch)]
                    sims = np.asarray(sims)[: len(batch)]
                # Scatter back per configuration (each user appears in
                # exactly one cluster per configuration).
                with trace.span("repro.local_knn.scatter"):
                    for j, ci in enumerate(batch):
                        cfg = plan.config_of[ci]
                        users = plan.members[ci]
                        out_ids[cfg, users] = nbr[j, : len(users)]
                        out_sims[cfg, users] = sims[j, : len(users)]
                trace.add("repro.local_knn.pairs_useful", useful)
                trace.add("repro.local_knn.pairs_computed",
                          m * int(cap) * (int(cap) - 1))
                trace.add("repro.local_knn.rows_gathered", m * int(cap))
    return out_ids, out_sims
