"""Kernel micro-benchmarks (CPU wall time; Pallas paths run in interpret
mode here — their TPU performance is characterized structurally in
§Roofline, these tables establish correctness-path overheads and the
popcount-vs-MXU layout tradeoff on real data).

Two sections:

* **all-pairs** — the three GoldFinger-similarity paths on a KNN tile
  (jnp popcount ref, jnp MXU bit-plane, fused goldfinger_knn kernel).
* **descent** — the serving hot path, per beam width: the unfused jnp
  hop (score every ``beam·(kg+kr)`` lane, dedup after, wide top-k) vs
  the fused descent_score kernel in BOTH placements — blocked-VMEM
  tables and HBM-resident tables with per-chunk candidate-row DMA —
  with the kernel's scored-lane counts showing how much estimator work
  dedup-before-scoring removes and the DMA path's byte columns showing
  the HBM traffic the suppressed-lane skip avoids.

    PYTHONPATH=src python -m benchmarks.kernel_bench [--smoke]

``--smoke`` shrinks both sections for CI and fails loudly (exit 1) if
the fused descent hop (either placement) drifts from the jnp oracle by
a single bit, stops reducing scored work, moves no DMA / saves no
bytes on the dedup-heavy workload, or re-misses the shape-keyed
autotuner cache on a repeated shape.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.compile_cache import use_compile_cache
from repro.data.synthetic import make_dataset
from repro.kernels.goldfinger_knn import ops as gk_ops
from repro.kernels.goldfinger_knn import ref as gk_ref
from repro.sketch.goldfinger import fingerprint_dataset


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run(n: int = 1024, k: int = 10):
    ds = make_dataset("ml1M", scale=max(n / 6038, 0.01), seed=5)
    gf = fingerprint_dataset(ds)
    n = min(n, gf.n)
    w = jnp.asarray(gf.words[:n])
    c = jnp.asarray(gf.card[:n])
    ids = jnp.arange(n, dtype=jnp.int32)

    ref_j = jax.jit(lambda *a: gk_ref.knn_ref(*a, k=k))
    t_ref = _time(ref_j, w, c, ids, w, c, ids)

    from repro.sketch.goldfinger import jaccard_pairwise_mxu

    def mxu_knn(w, c, ids):
        sims = jaccard_pairwise_mxu(w, c, w, c)
        sims = jnp.where(ids[None, :] == ids[:, None], -jnp.inf, sims)
        return jax.lax.top_k(sims, k)

    t_mxu = _time(jax.jit(mxu_knn), w, c, ids)
    t_pal = _time(lambda *a: gk_ops.knn(*a, k=k), w, c, ids, w, c, ids)

    rows = [
        {"path": "jnp_popcount_ref", "n": n, "time_s": t_ref,
         "us_per_pair": 1e6 * t_ref / (n * n)},
        {"path": "jnp_mxu_bitplane", "n": n, "time_s": t_mxu,
         "us_per_pair": 1e6 * t_mxu / (n * n)},
        {"path": "pallas_interpret", "n": n, "time_s": t_pal,
         "us_per_pair": 1e6 * t_pal / (n * n)},
    ]
    for r in rows:
        print(f"[kernel] {r['path']:18s} n={n}: {r['time_s']*1e3:8.1f} ms "
              f"({r['us_per_pair']:.4f} µs/pair)")
    return emit(rows, "kernel_bench")


def run_descent(scale: float = 0.1, n_queries: int = 128,
                beams=(8, 16, 32), k: int = 10, seed: int = 5):
    """Descent-hop rows: jnp vs fused per beam width + scored-lane stats.

    Returns the rows; raises AssertionError on any jnp/fused bit drift
    (the smoke gate turns that into a CI failure).
    """
    from repro.core.params import params_for
    from repro.kernels.descent_score import ops as ds_ops
    from repro.kernels.descent_score import ref as ds_ref
    from repro.kernels.descent_score.descent_score import dma_row_words
    from repro.query.index import build_index
    from repro.query.router import routed_queries
    from repro.query.search import descent_init

    ds = make_dataset("synth", scale=scale, seed=seed)
    index = build_index(ds, params_for("synth", k=k,
                                       b=max(64, ds.n_users // 16),
                                       max_cluster=max(48,
                                                       int(0.06 * ds.n_users))))
    qds = make_dataset("synth", scale=scale, seed=seed + 1)
    profiles = [qds.profile(u) for u in range(min(n_queries, qds.n_users))]
    qw, qc, seeds = (jnp.asarray(x)
                     for x in routed_queries(index, profiles, 16))
    g, r = jnp.asarray(index.graph_ids), jnp.asarray(index.rev_ids)
    w, c = jnp.asarray(index.words), jnp.asarray(index.card)
    kg, kr = g.shape[1], r.shape[1]

    jnp_hop = jax.jit(ds_ref.descent_hop_ref)
    W = w.shape[1]
    rows = []
    for beam in beams:
        bi, bs = descent_init(w, c, qw, qc, seeds, beam=beam)
        bi, bs = jax.block_until_ready((bi, bs))
        t_jnp = _time(jnp_hop, g, r, w, c, qw, qc, bi, bs)
        t_pal = _time(lambda *a: ds_ops.descent_hop(*a),
                      g, r, w, c, qw, qc, bi, bs)
        t_dma = _time(lambda *a: ds_ops.descent_hop(*a, dma=True),
                      g, r, w, c, qw, qc, bi, bs)
        ri, rs = jnp_hop(g, r, w, c, qw, qc, bi, bs)
        ki, ks, nsc, _, _ = ds_ops.descent_hop(
            g, r, w, c, qw, qc, bi, bs, with_counts=True)
        di, dsim, dnsc, dmab, saved = ds_ops.descent_hop(
            g, r, w, c, qw, qc, bi, bs, dma=True, with_counts=True)
        np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(ks), np.asarray(rs))
        np.testing.assert_array_equal(np.asarray(di), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(dsim), np.asarray(rs))
        # DMA accounting must agree with the scored-lane counter: the
        # kernel fetches exactly the surviving lanes' packed rows.
        np.testing.assert_array_equal(np.asarray(dmab),
                                      np.asarray(dnsc) * 4 * dma_row_words(W))
        total = beam * (kg + kr)
        scored = float(np.asarray(nsc).mean())
        q_dma = float(np.asarray(dmab).mean())
        q_saved = float(np.asarray(saved).mean())
        rows.append({
            "beam": beam, "n": index.n, "n_queries": len(profiles),
            "candidates_per_hop": total,
            "scored_per_hop_mean": round(scored, 1),
            "scored_fraction": round(scored / total, 3),
            "jnp_hop_ms": round(t_jnp * 1e3, 2),
            "fused_interpret_ms": round(t_pal * 1e3, 2),
            "fused_dma_interpret_ms": round(t_dma * 1e3, 2),
            "dma_kb_per_query": round(q_dma / 1e3, 2),
            "dma_saved_kb_per_query": round(q_saved / 1e3, 2),
            "dma_saved_fraction": round(q_saved / (q_dma + q_saved), 3),
        })
    for row in rows:
        print(f"[descent] beam={row['beam']:3d}: scored "
              f"{row['scored_per_hop_mean']:7.1f}/{row['candidates_per_hop']}"
              f" lanes ({row['scored_fraction']:.0%}) | jnp "
              f"{row['jnp_hop_ms']:.1f} ms, fused(interpret) "
              f"{row['fused_interpret_ms']:.1f} ms, fused-dma(interpret) "
              f"{row['fused_dma_interpret_ms']:.1f} ms | dma "
              f"{row['dma_kb_per_query']:.1f} KB/q, skipped "
              f"{row['dma_saved_kb_per_query']:.1f} KB/q "
              f"({row['dma_saved_fraction']:.0%})")
    return emit(rows, "kernel_bench_descent")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run; exit 1 on fused-hop drift")
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        from repro.kernels.descent_score import tune

        run(n=256)
        tune.clear()
        try:
            rows = run_descent(scale=0.05, n_queries=48, beams=(8, 16))
        except AssertionError as e:
            print(f"[kernel_bench] FAIL fused descent hop drifted from "
                  f"the jnp oracle: {e}", file=sys.stderr)
            sys.exit(1)
        if not all(row["scored_fraction"] < 1.0 for row in rows):
            print("[kernel_bench] FAIL dedup-before-scoring removed no "
                  "work", file=sys.stderr)
            sys.exit(1)
        if not all(row["dma_saved_kb_per_query"] > 0 for row in rows):
            print("[kernel_bench] FAIL suppressed-lane DMA skip saved "
                  "no bytes on a dedup-heavy workload", file=sys.stderr)
            sys.exit(1)
        # Shape-keyed autotuner: the first dma hop per beam width is a
        # cache miss, every repeat (timing reps + counted rerun) a hit.
        if tune.stats["misses"] != 2 or tune.stats["hits"] < 2:
            print(f"[kernel_bench] FAIL autotuner cache re-missed on a "
                  f"repeated shape: {tune.stats}", file=sys.stderr)
            sys.exit(1)
        print(f"[kernel_bench] smoke OK (tune cache {tune.stats})")
        return
    run()
    run_descent()


if __name__ == "__main__":
    main()
