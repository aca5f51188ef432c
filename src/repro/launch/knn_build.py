"""Distributed KNN-graph construction driver (the paper's system as a
service on the trainer's mesh), with per-hash-configuration checkpointing
— the map-reduce fault-tolerance the paper sketches in §VIII: each
configuration's partial KNN graph is an independent map task; a restart
skips completed configurations.

    PYTHONPATH=src python -m repro.launch.knn_build --dataset ml1M \
        --scale 0.2 --k 10 --ckpt-dir /tmp/knn_ck
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.clustering import build_plan
from repro.core.local_knn import local_knn
from repro.core.merge import merge_partial
from repro.core.params import C2Params, params_for
from repro.data.synthetic import make_dataset
from repro.sketch.goldfinger import sketch_dataset
from repro.types import NEG_INF, PAD_ID


def build(ds, params: C2Params, ckpt_dir: str | None = None,
          mesh=None, verbose: bool = True, gf=None):
    if gf is None:
        gf = sketch_dataset(ds, params)
    plan = build_plan(ds, params)
    t, n, k = params.t, ds.n_users, params.k
    ids = np.full((t, n, k), PAD_ID, dtype=np.int32)
    sims = np.full((t, n, k), NEG_INF, dtype=np.float32)

    done = set()
    cdir = Path(ckpt_dir) if ckpt_dir else None
    if cdir and cdir.exists():
        for f in cdir.glob("config_*.npz"):
            i = int(f.stem.split("_")[1])
            z = np.load(f)
            ids[i], sims[i] = z["ids"], z["sims"]
            done.add(i)
        if done and verbose:
            print(f"[knn] resuming: configs {sorted(done)} already done")

    from repro.core.clustering import ClusterPlan

    for i in range(t):
        if i in done:
            continue
        t0 = time.time()
        # Restrict the plan to configuration i (independent map task).
        sub_members = [m for m, c in zip(plan.members, plan.config_of)
                       if c == i]
        sub = ClusterPlan(
            members=sub_members,
            config_of=np.zeros(len(sub_members), dtype=np.int32),
            n_users=n, t=1)
        if mesh is not None:
            from repro.core.distributed import distributed_local_knn
            i1, s1, _ = distributed_local_knn(sub, gf, params, mesh)
        else:
            i1, s1 = local_knn(sub, gf, params)
        ids[i], sims[i] = i1[0], s1[0]
        if cdir:
            cdir.mkdir(parents=True, exist_ok=True)
            tmp = cdir / f".tmp_config_{i:03d}.npz"
            np.savez(tmp, ids=ids[i], sims=sims[i])
            tmp.rename(cdir / f"config_{i:03d}.npz")
        if verbose:
            print(f"[knn] config {i}: {time.time() - t0:.2f}s")
    graph = merge_partial(ids, sims, k)
    return graph, plan


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="ml1M")
    ap.add_argument("--scale", type=float, default=0.2)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--fail-after-config", type=int, default=None)
    ap.add_argument("--index-out", default=None,
                    help="save a servable KNNIndex (.npz) for knn_serve")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    ds = make_dataset(args.dataset, scale=args.scale, seed=args.seed)
    params = params_for(args.dataset, k=args.k)
    if args.fail_after_config is not None:
        # Simulate a failure: run only the first m configs then exit.
        import dataclasses

        build(ds, dataclasses.replace(params, t=args.fail_after_config),
              ckpt_dir=args.ckpt_dir)
        print("[knn] simulated failure after "
              f"{args.fail_after_config} configs")
        raise SystemExit(42)
    t0 = time.time()
    gf = sketch_dataset(ds, params)
    graph, plan = build(ds, params, ckpt_dir=args.ckpt_dir, gf=gf)
    print(f"[knn] built KNN graph for {ds.n_users} users in "
          f"{time.time() - t0:.2f}s "
          f"({plan.n_clusters} clusters, {plan.brute_force_sims()} sims)")
    print(f"[knn] avg_sim = {graph.avg_sim():.4f}")
    if args.index_out:
        from repro.query.index import build_index

        index = build_index(ds, params, graph=graph, plan=plan, gf=gf)
        index.save(args.index_out)
        print(f"[knn] servable index saved to {args.index_out} "
              f"(serve with: python -m repro.launch.knn_serve "
              f"--index {args.index_out})")


if __name__ == "__main__":
    main()
