"""Cluster-and-Conquer end-to-end pipeline (paper §II-C).

Step 1 cluster (FastRandomHash + recursive split) → Step 2 per-cluster
partial KNNs → Step 3 merge. Returns the approximate KNN graph plus a
stats record (similarity counts, cluster histogram). Each step times
itself through ``repro.sched.trace`` spans.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.clustering import ClusterPlan, build_plan
from repro.core.local_knn import local_knn
from repro.core.merge import merge_partial
from repro.core.params import C2Params
from repro.sketch.goldfinger import GoldFinger, sketch_dataset
from repro.types import Dataset, KNNGraph


@dataclasses.dataclass
class C2Stats:
    n_clusters: int
    n_sims: int            # Σ |C|(|C|−1)/2 — Step 2 similarity budget
    max_cluster: int
    cluster_sizes: np.ndarray


def cluster_and_conquer(
    ds: Dataset,
    params: C2Params | None = None,
    gf: GoldFinger | None = None,
) -> tuple[KNNGraph, C2Stats]:
    params = params or C2Params()

    if gf is None:
        gf = sketch_dataset(ds, params)
    plan: ClusterPlan = build_plan(ds, params)
    ids, sims = local_knn(plan, gf, params)
    graph = merge_partial(ids, sims, params.k)

    sizes = plan.sizes
    stats = C2Stats(
        n_clusters=plan.n_clusters,
        n_sims=plan.brute_force_sims(),
        max_cluster=int(sizes.max()) if len(sizes) else 0,
        cluster_sizes=sizes,
    )
    return graph, stats
