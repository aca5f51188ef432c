"""Drive the C² build-and-serve path once on a TPU, at MovieLens-10M scale.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip sharded-serving phase

The deployment is the paper's Table I MovieLens-10M shape at its full
user count (69,816 users, 10,472 items, mean profile 84.3), built with
the paper's §IV-C parameters (k=30, b=4096, t=8, N=2000, 1024-bit
GoldFinger) and served with the serving CLI's defaults (k=10, beam 32,
3 hops, 32 slots) to 256 unseen query profiles. It goes through the
library's own entry points: ``make_dataset`` → ``build_index`` →
``QueryEngine``.

One chip, four phases, each printing its wall times and the device
bytes in use:

  (a) build the index;
  (b) serve the queries in wave and in continuous mode with the jnp
      scorer — bitwise equal, recall@10 against the exact KNN at least
      ``RECALL_FLOOR``;
  (c) serve them through the fused DMA hop, compiled — bitwise equal
      to (b);
  (d) run one real 2048-row capacity group of the build through the
      compiled cluster-KNN kernel — bitwise equal to the jnp group KNN.

``--chips 4`` builds the same index and runs only the sharded phase:
four shards served on the four-device mesh, bitwise equal to the same
plan vmapped on one device, with recall beside the single placement's.

Every check raises; nothing is caught, so any failure exits non-zero.
The script refuses to run unless JAX's devices are TPUs, and its last
line is the JSON device record. It starts no other process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.clustering import ClusterPlan, build_plan  # noqa: E402
from repro.core.local_knn import capacity_of, local_knn  # noqa: E402
from repro.core.params import params_for  # noqa: E402
from repro.data.synthetic import PAPER_DATASETS, make_dataset  # noqa: E402
from repro.eval.metrics import knn_recall  # noqa: E402
from repro.kernels import config  # noqa: E402
from repro.query.engine import (QueryConfig, QueryEngine,  # noqa: E402
                                QueryRequest)
from repro.query.index import build_index  # noqa: E402
from repro.query.router import routed_queries  # noqa: E402
from repro.query.search import exact_knn  # noqa: E402
from repro.query.sharded import ShardedDescent  # noqa: E402
from repro.sketch.goldfinger import sketch_dataset  # noqa: E402

DATASET = "ml10M"
SEED = 0
N_QUERIES = 256
GROUP_CAP = 2048
# Clusters the build batches per 2048-row group: its 256 MB budget of
# f32 sims over 2048² · 4 bytes each (core/local_knn.py).
GROUP_CLUSTERS = 16
# Recall@10 of the jnp wave in a CPU run of the same build and queries
# (same seed and parameters), less 0.01.
RECALL_FLOOR = 0.3767


def _mem() -> str:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    use = sum(s.get("bytes_in_use", 0) for s in stats)
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    return f"device bytes in use {use:,} (peak {peak:,})"


def _check(ok: bool, what) -> None:
    """Fail the smoke (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise AssertionError(what)


def _say(phase: str, msg: str) -> None:
    print(f"[smoke] {phase}: {msg} | {_mem()}", flush=True)


def build():
    """Phase (a): the dataset and the index, timed step by step."""
    t0 = time.perf_counter()
    ds = make_dataset(DATASET, scale=1.0, seed=SEED)
    t1 = time.perf_counter()
    params = params_for(DATASET)
    gf = sketch_dataset(ds, params)
    plan = build_plan(ds, params)
    t2 = time.perf_counter()
    index = build_index(ds, params, gf=gf, plan=plan)
    t3 = time.perf_counter()
    sizes = plan.sizes
    n_hyrec = int((sizes >= params.bf_threshold).sum())
    _say("a build",
         f"{ds.n_users} users, {ds.n_items} items, k={params.k} b="
         f"{params.b} t={params.t} N={params.max_cluster} "
         f"{params.n_bits}-bit | {plan.n_clusters} clusters (largest "
         f"{int(sizes.max())}, {n_hyrec} on the Hyrec branch) | data "
         f"{t1 - t0:.1f}s, fingerprint+cluster {t2 - t1:.1f}s, local KNN"
         f"+merge {t3 - t2:.1f}s")
    return ds, params, gf, plan, index


def query_profiles():
    """256 unseen profiles from the same generator (seed + 1)."""
    spec = PAPER_DATASETS[DATASET]
    qds = make_dataset(DATASET, scale=N_QUERIES / spec.n_users,
                       seed=SEED + 1)
    return [qds.profile(u) for u in range(N_QUERIES)]


def exact_ids(index, profiles, k: int) -> np.ndarray:
    qw, qc, _ = routed_queries(index, profiles)
    ids, _ = exact_knn(index.words, index.card, qw, qc, k,
                       tomb=index.tombstone)
    return ids


def serve(index, qc: QueryConfig, profiles):
    """Serve the profiles twice through one engine (cold, then warm);
    both passes must agree bitwise. Returns (engine, ids, sims, times)."""
    engine = QueryEngine(index, qc)
    results, times = [], []
    for _ in range(2):
        engine.done.clear()
        for rid, p in enumerate(profiles):
            engine.submit(QueryRequest(rid=rid, profile=p))
        t0 = time.perf_counter()
        engine.run()
        times.append(time.perf_counter() - t0)
        by_rid = {r.rid: r for r in engine.done}
        results.append((np.stack([by_rid[i].ids for i in range(len(profiles))]),
                        np.stack([by_rid[i].sims
                                  for i in range(len(profiles))])))
    for a, b in zip(results[0], results[1]):
        np.testing.assert_array_equal(a, b)
    return engine, results[0][0], results[0][1], times


def _times(times) -> str:
    return f"cold {times[0]:.2f}s, warm {times[1]:.3f}s"


def phase_jnp(index, profiles, exact):
    """Phase (b): the jnp scorer, wave vs continuous, and recall."""
    _, w_ids, w_sims, w_t = serve(index, QueryConfig(), profiles)
    _, c_ids, c_sims, c_t = serve(index, QueryConfig(continuous=True),
                                  profiles)
    np.testing.assert_array_equal(c_ids, w_ids)
    np.testing.assert_array_equal(c_sims, w_sims)
    recall = knn_recall(w_ids, exact)
    _say("b jnp", f"{len(profiles)} queries | wave {_times(w_t)} | "
         f"continuous {_times(c_t)} | wave == continuous bitwise | "
         f"recall@10 {recall:.4f} (floor {RECALL_FLOOR})")
    _check(recall >= RECALL_FLOOR, (recall, RECALL_FLOOR))
    return w_ids, w_sims, recall


def phase_dma(index, profiles, ref_ids, ref_sims):
    """Phase (c): the fused DMA hop, bitwise vs phase (b)."""
    engine, ids, sims, t = serve(
        index, QueryConfig(kernel=True, dma=True), profiles)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(sims, ref_sims)
    d = engine.plan.descent_stats
    _say("c dma", f"wave {_times(t)} | == jnp bitwise | "
         f"{d['scored_lanes']} lanes scored, {d['dma_bytes']:,} bytes "
         f"DMA'd, {d['bytes_saved']:,} skipped")


def phase_group_kernel(params, gf, plan):
    """Phase (d): one real 2048-row capacity group, kernel vs jnp."""
    caps = np.array([capacity_of(int(s)) for s in plan.sizes])
    batch = np.flatnonzero(caps == GROUP_CAP)[:GROUP_CLUSTERS]
    group = ClusterPlan(members=[plan.members[ci] for ci in batch],
                        config_of=plan.config_of[batch],
                        n_users=plan.n_users, t=plan.t)
    t0 = time.perf_counter()
    ref = local_knn(group, gf, params)
    t1 = time.perf_counter()
    out = local_knn(group, gf, dataclasses.replace(params, use_pallas=True))
    t2 = time.perf_counter()
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a, b)
    _say("d cluster-KNN kernel",
         f"{len(batch)} clusters at capacity {GROUP_CAP} | jnp "
         f"{t1 - t0:.2f}s, kernel {t2 - t1:.2f}s (both cold) | == jnp "
         f"bitwise")


def phase_mesh(index, profiles, exact, n_shards: int = 4):
    """Sharded serving on the mesh vs the same plan vmapped on one
    device, with recall beside the single placement's."""
    qc = QueryConfig(shards=n_shards)
    engine, ids, sims, t = serve(index, qc, profiles)
    sd = engine.sharded_state()
    _check(sd.mesh is not None, "sharded serving fell back to vmap")
    _check(len(sd.devices) == n_shards, sd.devices)
    vmap_sd = ShardedDescent(index, n_shards, plan=sd.plan, use_mesh=False,
                             oversample=qc.shard_oversample)
    _check(vmap_sd.mesh is None and len(vmap_sd.devices) == 1,
           vmap_sd.devices)
    qw, qcard, seeds = routed_queries(index, profiles, qc.seeds_per_config)
    kw = dict(k=qc.k, beam=max(qc.beam, qc.k), hops=qc.hops)
    m_ids, m_sims = (np.asarray(x) for x in sd.descend(qw, qcard, seeds,
                                                       **kw))
    t0 = time.perf_counter()
    v_ids, v_sims = (np.asarray(x) for x in vmap_sd.descend(
        qw, qcard, seeds, **kw))
    t_vmap = time.perf_counter() - t0
    np.testing.assert_array_equal(m_ids, v_ids)
    np.testing.assert_array_equal(m_sims, v_sims)
    np.testing.assert_array_equal(ids, m_ids)
    np.testing.assert_array_equal(sims, m_sims)
    _, s_ids, _, s_t = serve(index, QueryConfig(), profiles)
    _say(f"{n_shards}-shard mesh",
         f"resident rows {[len(r) for r in sd.plan.residents]} on "
         f"{len(sd.devices)} devices | mesh wave {_times(t)}, one-device "
         f"vmap {t_vmap:.2f}s (cold) | mesh == vmap bitwise | recall@10 "
         f"{knn_recall(ids, exact):.4f} sharded, "
         f"{knn_recall(s_ids, exact):.4f} single ({_times(s_t)})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases (a)-(d); 4: the sharded mesh phase")
    args = ap.parse_args(argv)
    use_compile_cache()

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[smoke] device: {device}", flush=True)
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{device['platform']!r} devices")
    if device["count"] < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} "
                         f"devices; JAX found {device['count']}")
    # Every kernel resolves its mode through this one rule.
    _check(config.interpret_mode() is False, "kernels would interpret")

    t0 = time.perf_counter()
    _, params, gf, plan, index = build()
    profiles = query_profiles()
    t1 = time.perf_counter()
    exact = exact_ids(index, profiles, QueryConfig().k)
    _say("exact KNN", f"{len(profiles)} queries x {index.n} rows in "
         f"{time.perf_counter() - t1:.2f}s (cold)")
    if args.chips == 4:
        phase_mesh(index, profiles, exact)
    else:
        ids, sims, _ = phase_jnp(index, profiles, exact)
        phase_dma(index, profiles, ids, sims)
        phase_group_kernel(params, gf, plan)
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
