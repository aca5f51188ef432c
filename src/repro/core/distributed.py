"""Distributed C² Step 2: shard_map over the mesh's data axis.

The paper's thread pool + synchronized priority queue becomes a *static*
LPT (longest-processing-time) bin-packing of clusters onto devices —
identical straggler protection (cluster cost is capped by N, the paper's
own knob) with zero runtime synchronization. Inside the shard_map there
are NO collectives: each device computes the partial KNNs of its bin,
exactly the paper's "computed independently, without any synchronization"
property, realized as SPMD (DESIGN.md §3).

The merge (Step 3) is the reduce phase: partial results return to host
sharded by device and are merged per hash configuration.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustering import ClusterPlan
from repro.core.local_knn import _group_knn, capacity_of
from repro.core.params import C2Params
from repro.sketch.goldfinger import GoldFinger
from repro.types import NEG_INF, PAD_ID


def lpt_assign(costs: np.ndarray, n_bins: int) -> np.ndarray:
    """Longest-processing-time assignment: returns bin id per item."""
    order = np.argsort(-costs, kind="stable")
    loads = np.zeros(n_bins, dtype=np.float64)
    assign = np.zeros(len(costs), dtype=np.int64)
    for i in order:
        b = int(np.argmin(loads))
        assign[i] = b
        loads[b] += costs[i]
    return assign


def lpt_loads(costs: np.ndarray, assign: np.ndarray,
              n_bins: int) -> np.ndarray:
    """Per-bin load of an assignment (shared by build + serving shards)."""
    loads = np.zeros(n_bins, dtype=np.float64)
    np.add.at(loads, assign, np.asarray(costs, dtype=np.float64))
    return loads


@dataclasses.dataclass
class DistPlan:
    """Static per-capacity-group member tensors: [n_dev, m_max, cap]."""

    groups: list[np.ndarray]
    caps: list[int]
    cluster_of: list[np.ndarray]  # (dev, slot) → cluster index (−1 pad)
    imbalance: float              # max/mean device load


def build_dist_plan(plan: ClusterPlan, n_dev: int) -> DistPlan:
    sizes = plan.sizes
    costs = sizes.astype(np.float64) ** 2  # brute force is O(|C|²)
    assign = lpt_assign(costs, n_dev)
    loads = lpt_loads(costs, assign, n_dev)
    imbalance = float(loads.max() / max(loads.mean(), 1e-9))

    caps_all = np.array([capacity_of(int(s)) for s in sizes])
    groups, caps, cluster_of = [], [], []
    for cap in np.unique(caps_all):
        idx = np.flatnonzero(caps_all == cap)
        m_max = max(int(np.max(np.bincount(assign[idx], minlength=n_dev))), 1)
        mem = np.full((n_dev, m_max, cap), PAD_ID, dtype=np.int32)
        cof = np.full((n_dev, m_max), -1, dtype=np.int64)
        slot = np.zeros(n_dev, dtype=np.int64)
        for ci in idx:
            d = assign[ci]
            s = slot[d]
            mem[d, s, : sizes[ci]] = plan.members[ci]
            cof[d, s] = ci
            slot[d] += 1
        groups.append(mem)
        caps.append(int(cap))
        cluster_of.append(cof)
    return DistPlan(groups=groups, caps=caps, cluster_of=cluster_of,
                    imbalance=imbalance)


def distributed_local_knn(plan: ClusterPlan, gf: GoldFinger,
                          params: C2Params, mesh,
                          data_axis: str = "data"):
    """Step 2 on a mesh: each device brute-forces its LPT bin of clusters.

    Returns (ids, sims) int32/float32 [t, n, k] as local_knn does.
    """
    from jax.sharding import PartitionSpec as P

    n_dev = int(mesh.shape[data_axis])
    dp = build_dist_plan(plan, n_dev)
    words = jnp.asarray(np.asarray(gf.words))
    card = jnp.asarray(np.asarray(gf.card))
    k = params.k

    def device_fn(*mems):
        # mems: per capacity group [1, m_max, cap] member ids (local bin).
        outs = []
        for mem in mems:
            mem = mem[0]
            gmem = jnp.where(mem == PAD_ID, 0, mem)
            w = words[gmem]                       # gather from replicated
            c = jnp.where(mem == PAD_ID, 0, card[gmem])
            nbr, sims = _group_knn(w, c, mem, k)
            outs.append((nbr[None], sims[None]))
        return tuple(outs)

    in_specs = tuple(P(data_axis, None, None) for _ in dp.groups)
    out_specs = tuple((P(data_axis, None, None, None),
                       P(data_axis, None, None, None))
                      for _ in dp.groups)
    results = jax.shard_map(device_fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)(
        *[jnp.asarray(g) for g in dp.groups])

    t, n = plan.t, plan.n_users
    out_ids = np.full((t, n, k), PAD_ID, dtype=np.int32)
    out_sims = np.full((t, n, k), NEG_INF, dtype=np.float32)
    for (nbr, sims), mem, cof in zip(results, dp.groups, dp.cluster_of):
        nbr = np.asarray(nbr)
        sims = np.asarray(sims)
        for d in range(mem.shape[0]):
            for s in range(mem.shape[1]):
                ci = cof[d, s]
                if ci < 0:
                    continue
                users = plan.members[ci]
                cfg = plan.config_of[ci]
                out_ids[cfg, users] = nbr[d, s, : len(users)]
                out_sims[cfg, users] = sims[d, s, : len(users)]
    return out_ids, out_sims, dp


def distributed_c2(ds, params: C2Params, mesh, gf: GoldFinger | None = None,
                   data_axis: str = "data"):
    """Full distributed pipeline: host plan → mesh Step 2 → merge."""
    from repro.core.clustering import build_plan
    from repro.core.merge import merge_partial
    from repro.sketch.goldfinger import sketch_dataset

    if gf is None:
        gf = sketch_dataset(ds, params)
    plan = build_plan(ds, params)
    ids, sims, dp = distributed_local_knn(plan, gf, params, mesh, data_axis)
    graph = merge_partial(ids, sims, params.k)
    stats = {
        "n_clusters": plan.n_clusters,
        "n_sims": plan.brute_force_sims(),
        "lpt_imbalance": dp.imbalance,
        "n_devices": int(mesh.shape[data_axis]),
    }
    return graph, stats
