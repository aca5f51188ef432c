"""Online KNN query serving CLI: build (or load) an index, serve a wave
of unseen query profiles, report QPS / latency / recall vs brute force.

    PYTHONPATH=src python -m repro.launch.knn_serve --dataset synth \
        --scale 0.2 --queries 256

Pass ``--index path.npz`` to serve a previously built artifact
(``launch/knn_build --index-out``), ``--insert M`` to also exercise
online insertion before the query wave, and any combination of the
three plan axes (``repro/query/plan.py`` — the flags compose freely
and invalid values fail loudly instead of silently dropping a flag):

* ``--shards S`` — placement: LPT cluster shards (shard_map when a
  device per shard exists, vmapped on one device otherwise — see
  repro/query/sharded.py; inserts delta-reshard instead of rebuilding);
* ``--continuous`` — batching: stream requests through the slot-based
  scheduler (``repro/sched/``) instead of closed waves — same results,
  but admission happens mid-descent; composes with ``--shards`` (per-
  shard slot arrays, cross-shard merge at slot release);
* ``--kernel`` — scorer: the fused Pallas descent-scoring hop
  (``repro/kernels/descent_score``; identical results, candidates
  deduped before the estimator runs). Add ``--dma`` for the
  HBM-resident placement: tables stay in HBM and only surviving
  candidate lanes' fingerprint rows are DMA'd into VMEM per scoring
  chunk (double-buffered; identical results again) — the per-query
  byte traffic and the traffic the suppressed-lane skip avoided are
  reported on a ``[serve] descent:`` line.

Lifecycle flags (``repro/lifecycle/``): ``--churn M`` deletes M users
and profile-updates M more online before the query wave (both picked
id-strided over the live rows, so reruns are deterministic), ``--ttl``
expires rows untouched for that many scheduler ticks, and
``--repair-every`` re-links delete-damaged rows on that tick cadence.

SLO flags (``repro/sched/scheduler.py`` + ``repro/query/cache.py``):
``--admission slo`` ranks pending requests by (priority class,
deadline) and sheds expired/overflow work explicitly (``--max-pending``
bounds the queue; shed requests complete with a ``rejected`` marker),
``--priority-split F`` marks the first F fraction of the wave
high-priority (class 0, the rest class 1), ``--deadline-ms D`` stamps
every request with a D-millisecond deadline, ``--adaptive P`` frees a
continuous slot once its top-k prefix has held P hops, and
``--cache N`` serves exact-fingerprint repeats from an N-entry result
cache invalidated by index-mutation journals.

Re-balance flags (``repro/query/rebalance.py``, shards > 1 only):
``--rebalance-every N`` measures shard imbalance every N scheduler
steps and blue/green-swaps to a freshly derived plan when it exceeds
``--rebalance-threshold`` (merge-based subgraph rebuild, in-flight
beams remapped, result cache flushed); ``--resident-configs M``
restricts shard residency to clusters of the first M hash
configurations (tiered residency: ~t/M per-shard memory for a small
recall cost; routing still sees every cluster).

Fault-tolerance flags (``repro/faults/``): ``--fault-plan SPEC``
schedules deterministic faults at the plan-step boundary
(``kill:S@T``, ``fail:S@T+D``, ``slow:S@T+D:MS``, ``crash@T`` —
separated by ``;``); killed shards are masked out and served around
(degraded recall reported), then rebuilt from survivors and swapped
back in. ``--store DIR --snapshot-every N`` persists periodic index
snapshots plus a write-ahead journal of every mutation;
``--recover DIR`` skips the build entirely and restores the engine —
bitwise — from the last snapshot + WAL replay.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.params import params_for
from repro.data.synthetic import make_dataset
from repro.faults.plan import EngineCrash
from repro.query.engine import QueryConfig, QueryEngine, QueryRequest
from repro.query.index import KNNIndex, build_index


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synth")
    ap.add_argument("--scale", type=float, default=0.2)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--beam", type=int, default=32)
    ap.add_argument("--hops", type=int, default=3)
    ap.add_argument("--max-wave", type=int, default=256)
    ap.add_argument("--continuous", action="store_true",
                    help="slot-based continuous batching (streaming "
                         "admission) instead of closed waves")
    ap.add_argument("--slots", type=int, default=32,
                    help="in-flight slot capacity in continuous mode")
    ap.add_argument("--shards", type=int, default=1,
                    help="serve across this many LPT cluster shards")
    ap.add_argument("--kernel", action="store_true",
                    help="fused Pallas descent-scoring hop "
                         "(kernels/descent_score; identical results)")
    ap.add_argument("--dma", action="store_true",
                    help="with --kernel: HBM-resident tables + per-"
                         "chunk candidate-row DMA (suppressed lanes "
                         "skipped at the DMA level; identical results, "
                         "reports bytes moved/saved)")
    ap.add_argument("--insert", type=int, default=0,
                    help="insert this many users online before querying")
    ap.add_argument("--churn", type=int, default=0,
                    help="delete this many users AND profile-update as "
                         "many more online before querying")
    ap.add_argument("--ttl", type=int, default=0,
                    help="expire rows untouched for this many scheduler "
                         "ticks (0 = never)")
    ap.add_argument("--repair-every", type=int, default=0,
                    help="re-link churn-damaged rows every this many "
                         "scheduler ticks (0 = off)")
    ap.add_argument("--admission", default="fifo", choices=["fifo", "slo"],
                    help="admission policy: fifo (arrival order) or slo "
                         "(priority class + earliest deadline, explicit "
                         "shedding)")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="slo: bound on the pending queue; overflow is "
                         "shed with a rejected marker (0 = unbounded)")
    ap.add_argument("--priority-split", type=float, default=0.0,
                    help="fraction of the wave submitted as high "
                         "priority (class 0); the rest is best-effort "
                         "class 1 (0 = every request class 0)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline in ms from submission; "
                         "expired pending requests are shed under "
                         "--admission slo (0 = no deadline)")
    ap.add_argument("--adaptive", type=int, default=0,
                    help="continuous: free a slot once its top-k prefix "
                         "held this many hops (0 = run to budget)")
    ap.add_argument("--cache", type=int, default=0,
                    help="fingerprint result-cache capacity, journal-"
                         "invalidated on index mutation (0 = off)")
    ap.add_argument("--rebalance-every", type=int, default=0,
                    help="measure shard imbalance every this many "
                         "scheduler steps; blue/green-swap the plan "
                         "past the threshold (0 = off; needs --shards)")
    ap.add_argument("--rebalance-threshold", type=float, default=1.25,
                    help="measured imbalance (max/mean resident cluster "
                         "mass) that triggers a re-balance swap")
    ap.add_argument("--resident-configs", type=int, default=0,
                    help="tiered residency: only clusters of the first "
                         "M hash configurations contribute shard "
                         "residents (0 = all t; needs --shards)")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic fault schedule: kill:S@T, "
                         "fail:S@T+D, slow:S@T+D:MS, crash@T "
                         "(';'-separated; steps count scheduler steps)")
    ap.add_argument("--store", default=None,
                    help="crash-store directory: snapshots + write-"
                         "ahead journal of every index mutation")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot cadence in scheduler steps (journal "
                         "compaction; 0 = snapshot only at startup)")
    ap.add_argument("--recover", default=None,
                    help="recover the engine from this crash-store "
                         "directory (skips the build; last snapshot + "
                         "WAL replay, bitwise)")
    ap.add_argument("--index", default=None, help="load a saved index")
    ap.add_argument("--save-index", default=None, help="save the built index")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    faults = None
    if args.fault_plan:
        from repro.faults import FaultInjector, FaultPlan
        faults = FaultInjector(FaultPlan.parse(args.fault_plan))
        print(f"[serve] fault plan: {faults.plan.describe()}")
    store = None
    if args.store:
        from repro.faults import CrashStore
        store = CrashStore(args.store, every=args.snapshot_every)

    qc = QueryConfig(
        k=args.k, beam=args.beam, hops=args.hops, max_wave=args.max_wave,
        shards=args.shards, continuous=args.continuous, slots=args.slots,
        kernel=args.kernel, dma=args.dma,
        ttl=args.ttl, repair_every=args.repair_every,
        admission=args.admission, max_pending=args.max_pending,
        adaptive=args.adaptive, cache=args.cache,
        resident_configs=args.resident_configs,
        rebalance_every=args.rebalance_every,
        rebalance_threshold=args.rebalance_threshold)

    if args.recover:
        engine = QueryEngine.recover(args.recover, qc, faults=faults,
                                     store=store)
        index = engine.index
        print(f"[serve] recovered from {args.recover}: {index.n} users, "
              f"{index.n_clusters} clusters, version {index.version}")
        return _serve(args, engine, index)

    if args.index:
        index = KNNIndex.load(args.index)
        print(f"[serve] loaded index: {index.n} users, k={index.k}, "
              f"t={index.t}, {index.n_clusters} clusters")
    else:
        ds = make_dataset(args.dataset, scale=args.scale, seed=args.seed)
        params = params_for(args.dataset, k=args.k,
                            b=max(64, ds.n_users // 16),
                            max_cluster=max(48, int(0.06 * ds.n_users)))
        t0 = time.perf_counter()
        index = build_index(ds, params)
        print(f"[serve] built index: {ds.n_users} users, k={params.k} "
              f"({time.perf_counter() - t0:.2f}s, "
              f"{index.n_clusters} clusters)")
    if args.save_index:
        index.save(args.save_index)
        print(f"[serve] index saved to {args.save_index}")

    engine = QueryEngine(index, qc, faults=faults, store=store)
    return _serve(args, engine, index)


def _serve(args, engine, index):
    print(f"[serve] plan: {engine.plan.describe()}")

    # Unseen profiles from the same distribution (different seed).
    qds = make_dataset(args.dataset, scale=args.scale, seed=args.seed + 1)
    n_q = min(args.queries, qds.n_users)
    profiles = [qds.profile(u) for u in range(n_q)]

    for m in range(args.insert):
        engine.insert(qds.profile(qds.n_users - 1 - m))
    if args.insert:
        print(f"[serve] inserted {args.insert} users online "
              f"(index now {index.n} users)")

    if args.churn:
        # Id-strided picks over the live rows: deterministic across
        # reruns, and the delete/update sets never overlap.
        alive = index.alive_ids()
        take = np.linspace(0, len(alive) - 1,
                           num=min(2 * args.churn, len(alive)),
                           dtype=np.int64)
        victims = alive[take]
        for u in victims[0::2]:
            engine.remove_user(int(u))
        for m, u in enumerate(victims[1::2]):
            engine.update_user(int(u), qds.profile(m % qds.n_users))
        if args.repair_every:
            engine.lifecycle.repair()  # serve the wave on a healed graph
        print(f"[serve] churned: {len(victims[0::2])} deletes, "
              f"{len(victims[1::2])} updates "
              f"(index now {index.n_live} live rows) | "
              f"lifecycle {engine.lifecycle.stats()}")

    sd = engine.sharded_state()  # after inserts: the waves reuse this state
    if sd is not None:
        mb = [round(b / 1e6, 2) for b in sd.resident_bytes()]
        print(f"[serve] sharded: {sd.n_shards} shards, resident rows "
              f"{[len(r) for r in sd.plan.residents]} "
              f"({mb} MB"
              + (f", configs {sd.plan.resident_configs}/{index.t}"
                 if sd.plan.resident_configs else "")
              + f"), imbalance {sd.plan.imbalance:.2f}, "
              f"{'mesh' if sd.mesh is not None else 'vmap'} execution")

    if not profiles:
        print("[serve] no queries requested")
        return {"requests": 0}, 0.0

    # Warm-up wave compiles the descent program; the timed run reuses it.
    engine.submit(QueryRequest(rid=-1, profile=profiles[0]))
    engine.run()
    engine.done.clear()

    n_high = (int(round(args.priority_split * len(profiles)))
              if args.priority_split > 0 else len(profiles))
    for rid, p in enumerate(profiles):
        deadline = (time.perf_counter() + args.deadline_ms / 1e3
                    if args.deadline_ms > 0 else None)
        engine.submit(QueryRequest(
            rid=rid, profile=p,
            priority=0 if rid < n_high else 1, deadline=deadline))
    try:
        stats = engine.run()
    except EngineCrash as e:
        # The injected crash lands between scheduler steps: every
        # mutation is journaled, in-flight requests are lost (clients
        # retry). Report what was durable and exit like a real death.
        print(f"[serve] CRASHED: {e}")
        if engine.store is not None:
            print(f"[serve] recover with: --recover {args.store}  "
                  f"(store: {engine.store.stats()})")
        return {"requests": 0, "crashed": True}, 0.0
    recall = engine.recall_vs_brute_force()
    unit = "ticks" if args.continuous else "waves"
    print(f"[serve] {stats['requests']} queries in {stats['waves']} {unit} "
          f"({stats['mode']}) | "
          f"QPS {stats['qps']:.0f} | "
          f"p50 {stats['p50_latency_s'] * 1e3:.1f}ms | "
          f"p95 {stats['p95_latency_s'] * 1e3:.1f}ms | "
          f"recall@{args.k} vs brute force {recall:.3f}")
    if "descent" in stats:
        d = stats["descent"]
        n_served = max(stats["served"], 1)
        line = (f"[serve] descent: {d['scored_lanes']} lanes scored "
                f"({d['scored_lanes'] / n_served:.0f}/query)")
        if d["dma_bytes"]:
            moved, saved = d["dma_bytes"], d["bytes_saved"]
            line += (f" | dma {moved / 1e6:.2f} MB moved "
                     f"({moved / n_served / 1e3:.1f} KB/query), "
                     f"{saved / 1e6:.2f} MB skipped "
                     f"({saved / (moved + saved):.0%} of gather traffic)")
        print(line)
    if args.admission == "slo":
        print(f"[serve] slo: served {stats['served']}, "
              f"shed {stats['shed']} "
              f"(priority split {n_high}/{len(profiles) - n_high}, "
              f"deadline {args.deadline_ms:.0f}ms)")
    if "cache" in stats:
        c = stats["cache"]
        print(f"[serve] cache: {c['hits']} hits / "
              f"{c['hits'] + c['misses']} lookups "
              f"(rate {c['hit_rate']:.2f}), {c['entries']}/{c['capacity']} "
              f"entries, {c['flushes']} flushes")
    if "faults" in stats:
        f = stats["faults"]
        degraded = [r for r in engine.done if getattr(r, "degraded", False)]
        deg_recall = (engine.recall_vs_brute_force(degraded)
                      if degraded else None)
        print(f"[serve] faults: {f.get('shards_down', 0)} shards down, "
              f"{f.get('deaths', 0)} deaths, "
              f"{f.get('retries', 0)} retries, "
              f"{f.get('backoff_steps', 0)} backoff steps, "
              f"{f.get('failovers', 0)} failovers | "
              f"{len(degraded)} served degraded"
              + (f" (degraded recall@{args.k} {deg_recall:.3f})"
                 if deg_recall is not None else ""))
    if "store" in stats:
        s = stats["store"]
        print(f"[serve] store: {s['snapshots']} snapshots, "
              f"{s['wal_records']} WAL records since last "
              f"(cadence {s['every']})")
    return stats, recall


if __name__ == "__main__":
    main()
