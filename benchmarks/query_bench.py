"""Query-serving benchmark: QPS, latency percentiles, recall@k vs brute
force, for cold (compile included) and warm waves, in single-device and
sharded modes — each also through the fused Pallas descent-scoring
kernel (``*_kernel`` rows, plus a ``single_dma`` row for its
HBM-resident DMA placement, and a ``descent_scoring`` block reporting
scored-lane counts per hop vs the unfused ``beam·(kg+kr)`` alongside
the DMA path's bytes-moved / bytes-saved-per-query columns) — plus
online-insert throughput.

    PYTHONPATH=src python benchmarks/query_bench.py [--dataset synth]
        [--scale 0.2] [--queries 256] [--shards 2] [--out BENCH_query.json]

``--devices N`` (default: the shard count) emulates N XLA host devices —
the multi-core serving configuration, one shard per device via
shard_map; ``--devices 0`` forces the single-device vmap fallback.
``--continuous`` adds the slot-scheduler comparison: closed-loop
continuous rows plus a Poisson-arrival *open-loop* run (requests are
submitted at their arrival times, not all at once) reporting p50/p95
under load for wave vs continuous serving — the tail-latency case
continuous batching exists for — both single-device AND under the
sharded placement (the ``sharded_N_continuous`` block: per-shard slot
arrays with a release-time cross-shard merge, same Poisson protocol).
``--overload`` adds the SLO-serving rows: a 0.85/0.95/1.2-offered-load
sweep under slo admission (priority classes + deadlines, explicit
shedding, bounded pending queue) against a FIFO baseline whose queue
collapses at 1.2x, the adaptive-hop-budget comparison (free a slot once
its top-k prefix stabilizes vs run to budget), and the
journal-invalidated result cache on a repeated-query stream with
interleaved churn (gated bitwise against cache-off).
``--rebalance`` adds the background re-balance rows: frozen-extend vs
rebalanced imbalance trajectories under skewed insert growth, the
forced blue/green swap checks (merge rebuild bitwise vs from-scratch,
cache flush, recall across the swap), and the tiered-residency sweep
(``resident_configs`` subset size vs recall vs per-shard resident
bytes).
``--faults`` adds the fault-tolerance rows: kill 1 of N shards
mid-open-loop (every request still answered, degraded answers stamped
and their recall priced, health-machine walk to a failover rebuild,
post-recovery wave bitwise vs pre-failure) and a crash between
scheduler steps recovered from snapshot + write-ahead-log replay,
gated bitwise — tensors and answers — against a never-crashed mirror.
``--smoke`` shrinks the workload for CI: it still exercises build,
every serving plan, and insertion, and fails loudly (exit 1) if the
sharded mode regresses against single-device beyond the allowed
margins (with ``--continuous``: if streaming admission loses results,
recall parity with waves, or — sharded × continuous — bitwise
closed-loop equality with the sharded wave).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

# The device count must be pinned before jax initializes (same pattern
# as launch/dryrun.py), so peek at argv before the heavy imports.
_pre = argparse.ArgumentParser(add_help=False)
_pre.add_argument("--devices", type=int, default=None)
_pre.add_argument("--shards", type=int, default=2)
_pre_args, _ = _pre.parse_known_args()
_n_dev = (_pre_args.devices if _pre_args.devices is not None
          else _pre_args.shards)
if _n_dev and _n_dev > 1:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_n_dev}")

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.params import params_for
from repro.data.synthetic import make_dataset
from repro.query.engine import QueryConfig, QueryEngine, QueryRequest
from repro.query.index import build_index


def _serve_waves(engine: QueryEngine, profiles, k: int) -> dict:
    """One cold + one warm wave through ``engine``; per-wave stats."""
    out = {}
    for tag in ("cold", "warm"):
        for rid, p in enumerate(profiles):
            engine.submit(QueryRequest(rid=rid, profile=p))
        stats = engine.run()
        recall = engine.recall_vs_brute_force(engine.done[-len(profiles):])
        out[tag] = {
            "qps": round(stats["qps"], 1),
            "p50_latency_ms": round(stats["p50_latency_s"] * 1e3, 2),
            "p95_latency_ms": round(stats["p95_latency_s"] * 1e3, 2),
            f"recall_at_{k}": round(recall, 4),
        }
    return out


def _warm_wave_capacities(engine: QueryEngine, profiles, hop_set=(None,)):
    """Compile the wave program for every pow-2 wave capacity × hop
    budget the open-loop run can hit (waves are padded to capacity
    buckets), so a mid-run compile doesn't pollute the latency
    measurement."""
    for hops in hop_set:
        n = 1
        while True:
            engine.query_batch(profiles[: min(n, len(profiles))],
                               hops=hops)
            if n >= len(profiles):  # final call warms the top bucket
                break
            n *= 2


def _latency_row(reqs) -> dict:
    """p50/p95/max over SERVED requests (rejected ones carry no service
    latency — their submit→shed interval is queueing, not service)."""
    lats = np.array([r.latency for r in reqs
                     if r.status == "done" and r.latency is not None])
    if not len(lats):
        return {"p50_latency_ms": None, "p95_latency_ms": None,
                "max_latency_ms": None}
    return {
        "p50_latency_ms": round(float(np.percentile(lats, 50)) * 1e3, 2),
        "p95_latency_ms": round(float(np.percentile(lats, 95)) * 1e3, 2),
        "max_latency_ms": round(float(lats.max()) * 1e3, 2),
    }


def median_row(rows: list) -> dict:
    """Representative open-loop row: the rep whose p95 is the median.

    Taking per-key medians independently across reps stitches together
    a row no rep actually measured — the median p50 can come from one
    rep and the median p95 from another, breaking p50 <= p95 coherence
    and detaching achieved_qps from the latencies that run paid for it.
    The tail is the quantity under test, so pick the rep whose p95 is
    the median and report that rep's ENTIRE row, keeping every rep's
    p95 alongside so the spread stays visible.
    """
    p95s = [np.inf if r["p95_latency_ms"] is None else r["p95_latency_ms"]
            for r in rows]
    pick = rows[int(np.argsort(p95s, kind="stable")[(len(p95s) - 1) // 2])]
    out = {key: pick[key] for key in ("rate_qps", "achieved_qps",
                                      "p50_latency_ms", "p95_latency_ms",
                                      "max_latency_ms")}
    out["p95_latency_ms_reps"] = [r["p95_latency_ms"] for r in rows]
    return out


def open_loop(engine: QueryEngine, profiles, rate_qps: float,
              budgets=None, seed: int = 0, stall_s: float = 60.0,
              priorities=None, deadline_ms: float = 0.0,
              clock=None) -> dict:
    """Poisson-arrival open-loop serving through ``engine.step()``.

    Requests are submitted at their arrival times (exponential
    inter-arrivals at ``rate_qps``) while the engine serves — so a
    request's latency includes the queueing it actually experiences
    behind in-flight work, which is where wave and continuous modes
    diverge. ``budgets`` (optional int[n]) gives each request its own
    hop budget: wave mode convoys a wave to its deepest member, while
    continuous mode frees each slot at its own budget. ``priorities``
    (optional int[n]) assigns SLO classes and ``deadline_ms`` stamps
    each request with a deadline that many ms after its arrival — both
    only matter to engines configured with slo admission.

    SHED requests count as completions (they come back with a
    ``rejected`` marker): an overloaded slo engine shedding its way
    through the backlog is making progress, not stalling. The stall
    guard therefore watches completions of EITHER kind — it fires only
    when the engine stops completing work for ``stall_s`` seconds,
    which is a serving bug, never a load response.

    ``clock`` (optional, default ``time.perf_counter``) makes the loop
    time-source injectable: pass a ``repro.sched.ManualClock`` and the
    run advances virtual time only through the idle-sleep path (the
    clock's ``sleep`` doubles as ``advance``), so tests drive the whole
    open loop without a single real ``time.sleep``.
    """
    clock = clock or time.perf_counter
    sleep = getattr(clock, "sleep", time.sleep)
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_qps,
                                         size=len(profiles)))
    reqs = [QueryRequest(
                rid=i, profile=p,
                hops=None if budgets is None else int(budgets[i]),
                priority=0 if priorities is None else int(priorities[i]))
            for i, p in enumerate(profiles)]
    n_done0 = len(engine.done)
    sched = engine.plan.scheduler
    n_steps = 0
    max_depth = 0
    t0 = clock()
    t_progress = t0
    i = 0
    while len(engine.done) - n_done0 < len(reqs):
        now = clock() - t0
        while i < len(reqs) and arrivals[i] <= now:
            req = reqs[i]
            # Latency counts from the ARRIVAL time, not from when the
            # driver got around to enqueueing it — a request that landed
            # while a long wave was in flight has been waiting since its
            # arrival, and that queueing is the quantity under test.
            req.t_submit = t0 + arrivals[i]
            if deadline_ms > 0:
                req.deadline = req.t_submit + deadline_ms / 1e3
            engine.queue.append(req)
            i += 1
        depth = len(engine.queue) + (len(sched.pending) if sched else 0)
        max_depth = max(max_depth, depth)
        if engine.busy():
            if engine.step():
                t_progress = clock()
            n_steps += 1
        elif i < len(reqs):  # idle: sleep to the next arrival
            t_progress = clock()
            sleep(max(min(arrivals[i] - now, 0.01), 0.0))
        if clock() - t_progress > stall_s:
            part = engine.done[n_done0:]
            n_srv = sum(1 for r in part if r.status == "done")
            n_shd = sum(1 for r in part if r.rejected)
            raise RuntimeError(
                f"open_loop stalled: engine stopped completing work — "
                f"{len(part)}/{len(reqs)} complete ({n_srv} served, "
                f"{n_shd} shed) and no completion of either kind for "
                f"{stall_s:.0f}s. Shedding counts as progress here, so "
                f"this is a serving bug, not admission-control load "
                f"response.")
    dt = max(clock() - t0, 1e-9)
    finished = engine.done[n_done0:]
    served = [r for r in finished if r.status == "done"]
    n_shed = len(finished) - len(served)
    row = {
        "rate_qps": round(rate_qps, 1),
        "achieved_qps": round(len(served) / dt, 1),
        "steps": n_steps,
        "served": len(served),
        "shed": n_shed,
        "max_queue_depth": int(max_depth),
        **_latency_row(finished),
    }
    if priorities is not None:
        classes = {}
        for cls in sorted(set(int(c) for c in priorities)):
            part = [r for r in finished if r.priority == cls]
            classes[str(cls)] = {
                "n": len(part),
                "served": sum(1 for r in part if r.status == "done"),
                "shed": sum(1 for r in part if r.rejected),
                **_latency_row(part),
            }
        row["classes"] = classes
    return row


def run_continuous(index, profiles, k: int, beam: int, hops: int,
                   slots: int, load: float = 0.85, deep_frac: float = 0.2,
                   seed: int = 0, shards: int = 1,
                   oversample: float = 1.25) -> dict:
    """Wave vs continuous under identical Poisson load + closed-loop rows.

    The open-loop workload is heterogeneous — ``deep_frac`` of the
    requests carry a 2× hop budget (refinement queries, the "slow
    descent" of the PR motivation). Wave batching convoys every wave
    containing a deep request to the deep budget; continuous serving
    frees each slot at its own budget, which is where the tail-latency
    gap comes from. ``shards > 1`` runs BOTH modes under the sharded
    placement (the sharded × continuous plan composition): batching is
    results-transparent for a fixed placement, so the closed-loop
    parity check below must hold bitwise — and the smoke gate fails if
    it drifts by even one bit.
    """
    place = dict(shards=shards, shard_oversample=oversample)
    cont = QueryEngine(index, QueryConfig(k=k, beam=beam, hops=hops,
                                          continuous=True, slots=slots,
                                          **place))
    closed = _serve_waves(cont, profiles, k)

    # A sustained arrival stream (2× the profile set) and a few
    # repetitions: a single short burst is a convoy lottery — backlog
    # needs time to build before the wave-mode tail shows.
    deep_hops = 2 * hops
    stream = profiles * 2
    reps = 3
    rng = np.random.default_rng(seed + 1)
    budgets = np.where(rng.random(len(stream)) < deep_frac,
                       deep_hops, hops)

    # Calibrate offered load against the wave engine's warm closed-loop
    # throughput on this mixed workload (one drain = one deep-budget
    # wave), then run below the knee so neither mode saturates outright.
    wave_ol = QueryEngine(index, QueryConfig(k=k, beam=beam, hops=hops,
                                             max_wave=len(stream),
                                             **place))
    _warm_wave_capacities(wave_ol, stream, hop_set=(hops, deep_hops))
    # Closed-loop parity vs wave on the SAME placement: batching must be
    # results-transparent, i.e. bitwise-equal (ids AND sims) per request.
    for rid, p in enumerate(profiles):
        wave_ol.submit(QueryRequest(rid=rid, profile=p))
    wave_ol.run()
    wave_closed_recall = wave_ol.recall_vs_brute_force()
    w_by = {r.rid: r for r in wave_ol.done}
    c_by = {r.rid: r for r in cont.done[-len(profiles):]}
    bitwise = all(np.array_equal(w_by[rid].ids, c_by[rid].ids)
                  and np.array_equal(w_by[rid].sims, c_by[rid].sims)
                  for rid in c_by)
    wave_ol.done.clear()
    for rid, p in enumerate(stream):
        wave_ol.submit(QueryRequest(rid=rid, profile=p,
                                    hops=int(budgets[rid])))
    mixed_qps = wave_ol.run()["qps"]
    wave_ol.done.clear()
    rate = max(load * mixed_qps, 1.0)

    cont_ol = QueryEngine(index, QueryConfig(k=k, beam=beam, hops=hops,
                                             continuous=True, slots=slots,
                                             **place))
    for rid, p in enumerate(stream[: 2 * slots]):
        cont_ol.submit(QueryRequest(rid=-1 - rid, profile=p))  # warm ticks
    cont_ol.run()
    cont_ol.done.clear()

    runs = {"wave": [], "continuous": []}
    for rep in range(reps):
        runs["wave"].append(open_loop(wave_ol, stream, rate,
                                      budgets=budgets, seed=seed + rep))
        runs["continuous"].append(open_loop(cont_ol, stream, rate,
                                            budgets=budgets,
                                            seed=seed + rep))

    open_rows = {mode: median_row(rows) for mode, rows in runs.items()}
    wave_recall = wave_ol.recall_vs_brute_force()
    cont_recall = cont_ol.recall_vs_brute_force()
    return {
        "slots": slots,
        "shards": shards,
        "plan": cont.plan.describe(),
        "closed_loop": closed,
        "closed_loop_vs_wave": {
            "bitwise_equal": bitwise,
            "recall_delta": round(
                closed["warm"][f"recall_at_{k}"] - wave_closed_recall, 4),
        },
        "open_loop_workload": {
            "deep_frac": deep_frac,
            "hops": hops,
            "deep_hops": deep_hops,
            "load": load,
            "arrivals_per_rep": len(stream),
            "reps": reps,
            "mixed_wave_closed_loop_qps": round(mixed_qps, 1),
        },
        "open_loop": open_rows,
        "open_loop_recall": {
            "wave": round(wave_recall, 4),
            "continuous": round(cont_recall, 4),
            "delta": round(cont_recall - wave_recall, 4),
        },
        "p95_improvement": round(
            open_rows["wave"]["p95_latency_ms"]
            / max(open_rows["continuous"]["p95_latency_ms"], 1e-9), 3),
    }


def run_churn(index0, profiles, k: int, beam: int, hops: int,
              insert_pool, seed: int = 0, turnover: float = 0.2,
              rounds: int = 4, shards: int = 1) -> dict:
    """Sustained-churn recall trajectory, repair on vs off.

    Each round deletes ``turnover/rounds`` of the live rows and inserts
    replacements (true turnover: the live count is conserved), then
    serves the same fixed query wave through the scheduler loop — so
    lifecycle maintenance fires exactly as it would in production
    (between steps). The two arms see IDENTICAL mutation streams; the
    only difference is the repair cadence. Repair-off decays as deletes
    punch PAD holes into survivors' rows; repair-on re-links the
    churn-touched cohort and should hold recall near the no-churn
    baseline.
    """
    import copy

    m_round = max(1, int(turnover * index0.n_live / rounds))
    arms = {}
    baseline = None
    for arm, repair_every in (("repair_on", 1), ("repair_off", 0)):
        ix = copy.deepcopy(index0)
        eng = QueryEngine(ix, QueryConfig(
            k=k, beam=beam, hops=hops, max_wave=len(profiles),
            shards=shards, refresh_every=10**9,
            repair_every=repair_every))
        rng = np.random.default_rng(seed + 7)  # same stream both arms
        pool = iter(insert_pool)

        def wave_recall(eng=eng):
            for rid, p in enumerate(profiles):
                eng.submit(QueryRequest(rid=rid, profile=p))
            eng.run()
            return eng.recall_vs_brute_force(eng.done[-len(profiles):])

        if baseline is None:  # no-churn reference (arm-independent)
            baseline = round(wave_recall(), 4)
        else:
            wave_recall()  # warm this arm's programs identically
        trajectory = []
        for _ in range(rounds):
            alive = eng.index.alive_ids()
            for u in rng.choice(alive, size=min(m_round, len(alive) - 1),
                                replace=False):
                eng.remove_user(int(u))
            for _i in range(m_round):
                eng.insert(next(pool))
            trajectory.append(round(wave_recall(), 4))
        arms[arm] = {
            "recall_trajectory": trajectory,
            "final_recall": trajectory[-1],
            "lifecycle": eng.lifecycle.stats(),
        }
    return {
        "turnover": turnover,
        "rounds": rounds,
        "deletes_per_round": m_round,
        "no_churn_recall": baseline,
        **arms,
        "repair_recovery": round(
            arms["repair_on"]["final_recall"]
            - arms["repair_off"]["final_recall"], 4),
        "repair_vs_baseline": round(
            arms["repair_on"]["final_recall"] - baseline, 4),
    }


def run_overload(index, profiles, k: int, beam: int, hops: int,
                 slots: int, seed: int = 0, high_frac: float = 0.3,
                 loads=(0.85, 0.95, 1.2)) -> dict:
    """SLO admission under increasing offered load, vs a FIFO baseline.

    The workload mixes ``high_frac`` high-priority (class 0) requests
    into a best-effort (class 1) stream, every request carrying a
    deadline. Offered load is calibrated against the engine's own
    closed-loop throughput; at 1.2× the engine CANNOT serve everything,
    and the two policies diverge: slo admission serves class 0 first
    and sheds expired/overflow class-1 work explicitly (bounded queue,
    high-priority p95 held near its uncontended value), while FIFO
    accepts everything in arrival order (queue collapse: depth and tail
    latency grow with the backlog, every class degrades together).
    """
    # A long stream: overload is an ACCUMULATION phenomenon (a 20%
    # deficit needs arrivals to pile into a backlog), so the absolute
    # excess — and the shed counts — scale with stream length. The
    # overloaded 1.2x rows run a 2x-longer stream for the same reason:
    # FIFO's queue growth is linear in time, and the collapse contrast
    # needs horizon to integrate over.
    stream = profiles * 4
    peak_stream = profiles * 8
    rng = np.random.default_rng(seed + 3)
    priorities = (rng.random(len(peak_stream)) >= high_frac) \
        .astype(np.int64)

    # Capacity: the engine's sustainable service rate, measured by a
    # saturating open-loop probe — arrivals offered at 3x the closed-
    # loop estimate keep every slot full for the whole stream, so the
    # probe's achieved rate IS the demonstrated capacity. (The closed-
    # loop qps alone underestimates it: its ramp and drain tail run
    # with idle slots, and tick time itself shifts with occupancy.)
    cal = QueryEngine(index, QueryConfig(k=k, beam=beam, hops=hops,
                                         continuous=True, slots=slots))
    for rid, p in enumerate(stream[: 2 * slots]):
        cal.submit(QueryRequest(rid=-1 - rid, profile=p))
    cal.run()
    cal.done.clear()
    for rid, p in enumerate(stream):
        cal.submit(QueryRequest(rid=rid, profile=p))
    est = cal.run()["qps"]
    capacity = open_loop(cal, stream, 3.0 * max(est, 1.0),
                         seed=seed)["achieved_qps"]

    # Deadline: a tenth of the ideal full-stream duration — several
    # uncontended service times, binding for work queued behind a
    # sustained overload. max_pending is the hard bound, set well below
    # the backlog a 20% deficit accumulates over this stream so the
    # 1.2x row MUST shed (and the pending queue can never grow past the
    # bound, unlike FIFO's).
    deadline_ms = 0.1 * len(stream) / max(capacity, 1e-9) * 1e3
    max_pending = max(slots // 2, len(stream) // 24)

    slo_eng = QueryEngine(index, QueryConfig(
        k=k, beam=beam, hops=hops, continuous=True, slots=slots,
        admission="slo", max_pending=max_pending))
    for rid, p in enumerate(stream[: 2 * slots]):
        slo_eng.submit(QueryRequest(rid=-1 - rid, profile=p))
    slo_eng.run()
    slo_eng.done.clear()

    slo_rows = {}
    hp_recall = {}
    for load in loads:
        work = peak_stream if load > 1.0 else stream
        n0 = len(slo_eng.done)
        slo_rows[str(load)] = open_loop(
            slo_eng, work, max(load * capacity, 1.0), seed=seed,
            priorities=priorities[: len(work)], deadline_ms=deadline_ms)
        hp = [r for r in slo_eng.done[n0:]
              if r.priority == 0 and r.ids is not None]
        hp_recall[str(load)] = round(
            slo_eng.recall_vs_brute_force(hp), 4) if hp else None

    # FIFO baseline at the overloaded point: the calibration engine IS
    # a warm fifo continuous engine, so reuse it. Deadlines are stamped
    # but fifo admission ignores them — nothing sheds, the queue absorbs
    # the full excess.
    fifo_row = open_loop(cal, peak_stream,
                         max(loads[-1] * capacity, 1.0), seed=seed,
                         priorities=priorities, deadline_ms=deadline_ms)

    def hp_p95(row):
        return row["classes"]["0"]["p95_latency_ms"]

    base, peak = slo_rows[str(loads[0])], slo_rows[str(loads[-1])]
    return {
        "slots": slots,
        "capacity_qps": round(capacity, 1),
        "high_frac": high_frac,
        "deadline_ms": round(deadline_ms, 1),
        "max_pending": max_pending,
        "arrivals": len(stream),
        "arrivals_at_peak": len(peak_stream),
        "slo": slo_rows,
        "high_priority_recall": hp_recall,
        f"fifo_{loads[-1]}": fifo_row,
        # Degradation of the protected class across the load sweep, and
        # the queue-collapse contrast at the overloaded point.
        "hp_p95_degradation": (
            round(hp_p95(peak) / max(hp_p95(base), 1e-9), 3)
            if hp_p95(peak) is not None and hp_p95(base) else None),
        "queue_collapse": {
            "slo_max_queue_depth": peak["max_queue_depth"],
            "fifo_max_queue_depth": fifo_row["max_queue_depth"],
            "depth_ratio": round(
                fifo_row["max_queue_depth"]
                / max(peak["max_queue_depth"], 1), 2),
            "slo_shed": peak["shed"],
            "fifo_shed": fifo_row["shed"],
        },
    }


def run_adaptive(index, profiles, k: int, beam: int, hops: int,
                 slots: int, seed: int = 0, patience: int = 1) -> dict:
    """Adaptive hop budgets: free a slot once its top-k prefix held
    ``patience`` hops, vs running every request to a fixed 2× budget.

    The deep budget is the refinement regime (the continuous-batching
    motivation); most descents converge well before it. The fixed arm
    burns the full budget anyway, the adaptive arm frees the slot when
    the result has stopped moving — fewer ticks for the same stream,
    measured as QPS against the recall it gives up (the exact-fixed-
    point early exit already comes free; patience trades the last
    epsilon of prefix churn for throughput).
    """
    deep = 2 * hops
    rows = {}
    for name, pat in (("fixed", 0), ("adaptive", patience)):
        eng = QueryEngine(index, QueryConfig(
            k=k, beam=beam, hops=deep, continuous=True, slots=slots,
            adaptive=pat))
        for rid, p in enumerate(profiles[: 2 * slots]):
            eng.submit(QueryRequest(rid=-1 - rid, profile=p))
        eng.run()
        eng.done.clear()
        ticks0 = eng.n_ticks
        for rid, p in enumerate(profiles):
            eng.submit(QueryRequest(rid=rid, profile=p))
        stats = eng.run()
        rows[name] = {
            "qps": round(stats["qps"], 1),
            "ticks": eng.n_ticks - ticks0,
            "p95_latency_ms": round(stats["p95_latency_s"] * 1e3, 2),
            f"recall_at_{k}": round(eng.recall_vs_brute_force(
                eng.done[-len(profiles):]), 4),
        }
    rk = f"recall_at_{k}"
    return {
        "slots": slots,
        "hop_budget": deep,
        "patience": patience,
        **rows,
        "qps_gain": round(rows["adaptive"]["qps"]
                          / max(rows["fixed"]["qps"], 1e-9), 3),
        "ticks_saved": rows["fixed"]["ticks"] - rows["adaptive"]["ticks"],
        "recall_delta": round(rows["adaptive"][rk] - rows["fixed"][rk], 4),
    }


def run_cache(index0, profiles, k: int, beam: int, hops: int,
              insert_pool, seed: int = 0, repeat_factor: int = 4,
              n_mutations: int = 6, capacity: int = 256) -> dict:
    """Result cache on a repeated-query stream with interleaved churn.

    The stream draws ``repeat_factor`` passes over a hot profile subset
    (the recommendation front-door shape the cache exists for), with a
    delete + insert between passes — each mutation flushes the cache via
    the journal rule. Cache-on and cache-off run the IDENTICAL request
    and mutation schedule on private index deepcopies; the gate is
    bitwise equality of every (ids, sims) pair, with the hit rate and
    flush count as the payoff/cost measurements.
    """
    import copy

    rng = np.random.default_rng(seed + 9)
    hot = profiles[: max(8, len(profiles) // 4)]
    # First pass covers every hot profile (populating the cache), later
    # passes redraw from the hot set — the repeated-query front-door
    # shape the cache exists for.
    order = np.concatenate([
        np.arange(len(hot)),
        rng.integers(0, len(hot), size=(repeat_factor - 1) * len(hot))])
    wave = max(4, len(hot) // 2)
    n_waves = int(np.ceil(len(order) / wave))
    # Mutations at evenly spaced wave boundaries — each flushes the
    # cache (journal rule), so they are capped to leave the cache at
    # least one re-warm wave between flushes or the hit rate would
    # measure the mutation cadence, not the cache.
    n_mut = min(n_mutations, max(1, n_waves // 2 - 1))
    mut_at = {round((m + 1) * n_waves / (n_mut + 1))
              for m in range(n_mut)}

    arms = {}
    results = {}
    for arm, cap in (("cache_off", 0), ("cache_on", capacity)):
        ix = copy.deepcopy(index0)
        eng = QueryEngine(ix, QueryConfig(
            k=k, beam=beam, hops=hops, max_wave=wave,
            refresh_every=10**9, cache=cap))
        mut_rng = np.random.default_rng(seed + 11)  # same stream per arm
        pool = iter(insert_pool)
        rid = 0
        t0 = time.perf_counter()
        for wi in range(n_waves):
            if wi in mut_at:
                alive = ix.alive_ids()
                eng.remove_user(int(alive[mut_rng.integers(len(alive))]))
                eng.insert(next(pool))
            for qi in order[wi * wave:(wi + 1) * wave]:
                eng.submit(QueryRequest(rid=rid, profile=hot[int(qi)]))
                rid += 1
            eng.run()
        dt = max(time.perf_counter() - t0, 1e-9)
        results[arm] = {r.rid: (np.asarray(r.ids), np.asarray(r.sims))
                        for r in eng.done}
        arms[arm] = {"qps": round(len(order) / dt, 1)}
        if eng.plan.cache is not None:
            arms[arm]["cache"] = eng.plan.cache.stats()
    bitwise = (set(results["cache_on"]) == set(results["cache_off"])
               and all(np.array_equal(results["cache_on"][r][0],
                                      results["cache_off"][r][0])
                       and np.array_equal(results["cache_on"][r][1],
                                          results["cache_off"][r][1])
                       for r in results["cache_off"]))
    return {
        "hot_profiles": len(hot),
        "requests": len(order),
        "waves": n_waves,
        "mutations": n_mut,
        "capacity": capacity,
        **arms,
        "bitwise_equal": bitwise,
        "hit_rate": arms["cache_on"]["cache"]["hit_rate"],
        "qps_gain": round(arms["cache_on"]["qps"]
                          / max(arms["cache_off"]["qps"], 1e-9), 3),
    }


def run_rebalance(index0, ds, profiles, k: int, beam: int, hops: int,
                  shards: int, seed: int = 0, rounds: int = 4,
                  growth: float = 0.25, threshold: float = 1.25) -> dict:
    """Frozen-extend vs background re-balance under skewed insert growth,
    plus the forced-swap mechanism checks.

    The insert stream clones profiles of the users whose cluster
    memberships are most CONCENTRATED on shard 0 under the initial plan
    — the adversarial drift for a frozen partition. (An insert registers
    into its deepest matching cluster of EVERY hash configuration, so
    cloning an arbitrary resident spreads its mass over all the shards
    its t clusters live on and the skew averages away; cloning the
    shard-0-concentrated cohort lands most of each insert's mass on
    shard-0 clusters.) The frozen ``extend_plan`` arm's measured
    imbalance then climbs round over round while the rebalanced arm's
    re-derived LPT packing pulls it back toward 1. Both arms see the
    IDENTICAL mutation stream (same seed); the only difference is
    ``rebalance_every``. The mechanism block then
    forces one blue/green swap on a grown copy and checks the swap
    invariants the serving path relies on: merge-based rebuild
    bitwise-equal to a from-scratch ``plan_shards`` build, result cache
    flushed exactly once, recall preserved across the swap, post-swap
    imbalance back under the threshold.
    """
    import copy

    from repro.query.rebalance import measured_imbalance
    from repro.query.sharded import ShardedDescent, plan_shards

    base = plan_shards(index0, shards)
    mass = np.zeros((index0.n, shards))
    for ci in range(index0.n_clusters):
        mem = index0.cluster_users(ci)
        mem = mem[(mem >= 0) & (mem < index0.n)]
        mass[mem, base.cluster_shard[ci]] += 1.0
    frac0 = mass[:, 0] / np.maximum(mass.sum(axis=1), 1.0)
    donors = np.argsort(-frac0, kind="stable")[: max(32, index0.n // 8)]

    def wave(eng):
        for rid, p in enumerate(profiles):
            eng.submit(QueryRequest(rid=rid, profile=p))
        eng.run()
        return eng.recall_vs_brute_force(eng.done[-len(profiles):])

    arms = {}
    for arm in ("frozen", "rebalanced"):
        ix = copy.deepcopy(index0)
        kw = dict(k=k, beam=beam, hops=hops, max_wave=len(profiles),
                  shards=shards, refresh_every=10**9)
        if arm == "rebalanced":
            kw.update(rebalance_every=1, rebalance_threshold=threshold)
        eng = QueryEngine(ix, QueryConfig(**kw))
        rng = np.random.default_rng(seed + 13)  # same stream both arms
        imbs = []
        recall = 0.0
        for _ in range(rounds):
            n_ins = max(1, int(growth * eng.index.n_live))
            for u in rng.choice(donors, size=n_ins, replace=True):
                eng.insert(ds.profile(int(u)))
            recall = wave(eng)
            sd = eng.plan.sharded_state()
            imbs.append(round(measured_imbalance(eng.index, sd.plan), 4))
        row = {"imbalance_trajectory": imbs,
               "final_imbalance": imbs[-1],
               f"recall_at_{k}": round(recall, 4)}
        if arm == "rebalanced":
            row["rebalance"] = eng.rebalance.stats()
            ref = QueryEngine(eng.index, QueryConfig(
                k=k, beam=beam, hops=hops, max_wave=len(profiles)))
            single = wave(ref)
            row["single_shard_recall"] = round(single, 4)
            row["recall_delta_vs_single"] = round(recall - single, 4)
        arms[arm] = row

    # Mechanism block: one round of growth, then a FORCED swap (so the
    # checks run even at smoke scale, where natural drift may stay
    # under the threshold) with the result cache enabled.
    ix = copy.deepcopy(index0)
    eng = QueryEngine(ix, QueryConfig(
        k=k, beam=beam, hops=hops, max_wave=len(profiles), shards=shards,
        refresh_every=10**9, cache=256, rebalance_every=10**9,
        rebalance_threshold=threshold))
    rng = np.random.default_rng(seed + 13)
    for u in rng.choice(donors, size=max(1, int(growth * ix.n_live)),
                        replace=True):
        eng.insert(ds.profile(int(u)))
    pre_recall = wave(eng)
    pre_imb = measured_imbalance(ix, eng.plan.sharded_state().plan)
    flushes0 = eng.plan.cache.flushes
    post_imb = eng.rebalance.swap()
    cache_flushed = eng.plan.cache.flushes == flushes0 + 1
    sd = eng.plan.sharded_state()
    scratch = ShardedDescent(ix, shards, plan=sd.plan, use_mesh=False)
    merge_equal = (np.array_equal(sd._g2l, scratch._g2l)
                   and all(np.array_equal(np.asarray(a), np.asarray(b))
                           for a, b in zip(sd._dev, scratch._dev)))
    post_recall = wave(eng)
    return {
        "rounds": rounds,
        "growth_per_round": growth,
        "threshold": threshold,
        "donor_pool": int(len(donors)),
        "frozen": arms["frozen"],
        "rebalanced": arms["rebalanced"],
        "frozen_exceeds_threshold":
            arms["frozen"]["final_imbalance"] > threshold,
        "forced_swap": {
            "pre_swap_imbalance": round(pre_imb, 4),
            "post_swap_imbalance": round(post_imb, 4),
            "recall_pre_swap": round(pre_recall, 4),
            "recall_post_swap": round(post_recall, 4),
            "recall_delta": round(post_recall - pre_recall, 4),
            "cache_flushed": bool(cache_flushed),
            "merge_bitwise_equal": bool(merge_equal),
            "merge": eng.rebalance.merge_stats,
        },
    }


def run_residency_sweep(index, profiles, k: int, beam: int, hops: int,
                        shards: int, oversample: float = 1.25) -> dict:
    """Tiered residency: restrict shard residency to the first ``m`` of
    the ``t`` hash configurations and price the memory saving in recall.

    Routing still sees every cluster (``cluster_shard`` covers all of
    them); only RESIDENCY — which users' rows sit on a shard — shrinks
    to the clusters of the first ``m`` configurations, with the
    uncovered users striped across shards so every row stays hosted
    somewhere. ``m = 0`` is full residency (the baseline row).
    """
    t = index.t
    ms = sorted({0, max(2, t // 4), t // 2, max(1, 3 * t // 4)})
    rows = []
    for m in ms:
        eng = QueryEngine(index, QueryConfig(
            k=k, beam=beam, hops=hops, max_wave=len(profiles),
            shards=shards, shard_oversample=oversample,
            resident_configs=m))
        for rid, p in enumerate(profiles):
            eng.submit(QueryRequest(rid=rid, profile=p))
        eng.run()
        recall = eng.recall_vs_brute_force(eng.done[-len(profiles):])
        sd = eng.plan.sharded_state()
        rb = sd.resident_bytes()
        rows.append({
            "resident_configs": m or t,
            "full_residency": m == 0,
            f"recall_at_{k}": round(recall, 4),
            "residents_per_shard": [len(r) for r in sd.plan.residents],
            "resident_bytes_per_shard": rb,
            "max_resident_bytes": int(max(rb)),
        })
    full = rows[0]  # m = 0 sorts first
    for r in rows:
        r["bytes_vs_full"] = round(
            r["max_resident_bytes"] / max(full["max_resident_bytes"], 1), 3)
        r["recall_delta_vs_full"] = round(
            r[f"recall_at_{k}"] - full[f"recall_at_{k}"], 4)
    return {"t": t, "shards": shards, "rows": rows}


def run_faults(index0, profiles, k: int, beam: int, hops: int,
               insert_pool, seed: int = 0, shards: int = 2) -> dict:
    """Fault-tolerance rows, both CI-gated.

    (a) kill 1 of ``shards`` mid-open-loop: the surviving fleet must
    keep answering EVERY request (degraded answers stamped, their
    recall priced against brute force), walk the dead shard through
    the health machine (suspect -> backoff re-probes -> dead), rebuild
    it from survivors + index via the merge path, blue/green-swap the
    plan back in, and then serve a wave BITWISE equal to the
    pre-failure wave — fail-and-recover must be invisible after the
    fact (nothing mutated the index, so any drift is a failover bug).

    (b) crash between scheduler steps mid-mutation-stream: recovery
    from the latest snapshot + write-ahead-log replay must land an
    engine whose index tensors AND served answers are bitwise what a
    never-crashed mirror (driven through the identical mutations,
    including the step the crash pre-empted) holds.
    """
    import copy
    import shutil
    import tempfile

    from repro.faults import (CrashStore, EngineCrash, FaultInjector,
                              FaultPlan, HealthConfig)
    from repro.query.index import _ROWS
    from repro.sched import ManualClock

    def wave(eng, ps):
        base = len(eng.done)
        for rid, p in enumerate(ps):
            eng.submit(QueryRequest(rid=rid, profile=p))
        eng.run()
        part = eng.done[base:]
        return ({r.rid: (np.asarray(r.ids), np.asarray(r.sims))
                 for r in part},
                round(eng.recall_vs_brute_force(part), 4))

    def same(a, b):
        return set(a) == set(b) and all(
            np.array_equal(a[r][0], b[r][0])
            and np.array_equal(a[r][1], b[r][1]) for r in a)

    # -- (a) kill/failover under an open-loop stream ------------------
    # The injector starts DISARMED so the pre-failure wave measures the
    # healthy fleet; arm() restarts its step count, so the kill lands
    # on the 3rd serving step of the open loop — mid-stream.
    inj = FaultInjector(FaultPlan.parse("kill:1@2"), armed=False,
                        health=HealthConfig(max_retries=2, backoff_cap=2,
                                            recover_after=6))
    eng = QueryEngine(copy.deepcopy(index0), QueryConfig(
        k=k, beam=beam, hops=hops, shards=shards, continuous=True,
        slots=8, max_wave=len(profiles)), faults=inj)
    pre, pre_recall = wave(eng, profiles)
    inj.arm()
    n_done0 = len(eng.done)
    row = open_loop(eng, profiles, rate_qps=64.0, seed=seed + 21,
                    stall_s=120.0)
    finished = eng.done[n_done0:]
    deg = [r for r in finished if r.status == "done" and r.degraded]
    # Idle steps walk the health machine the rest of the way to the
    # failover swap if the open loop drained before it fired.
    idle = 0
    while (eng.degraded or eng.failover.n_failovers == 0) and idle < 200:
        eng.step()
        idle += 1
    post, post_recall = wave(eng, profiles)
    kill_row = {
        "submitted": len(profiles),
        "served": row["served"],
        "shed": row["shed"],
        "degraded_served": len(deg),
        "degraded_recall": (round(eng.recall_vs_brute_force(deg), 4)
                            if deg else None),
        "failovers": int(eng.failover.n_failovers),
        "recovery_steps": eng.failover.recovery_steps,
        "idle_steps_to_recover": idle,
        "health": list(eng.failover.health.state),
        "recall_pre_failure": pre_recall,
        "recall_post_recovery": post_recall,
        "post_recovery_bitwise": bool(same(pre, post)),
        "open_loop": {key: row[key] for key in
                      ("achieved_qps", "p50_latency_ms", "p95_latency_ms",
                       "max_queue_depth")},
        "injector": eng.faults.stats(),
    }

    # -- (b) crash + snapshot/WAL recovery ----------------------------
    tmp = tempfile.mkdtemp(prefix="query_bench_faults_")
    qc = QueryConfig(k=k, beam=beam, hops=hops, shards=shards,
                     max_wave=16, refresh_every=6)
    store = CrashStore(tmp, every=3)
    ceng = QueryEngine(copy.deepcopy(index0), qc, clock=ManualClock(),
                       faults=FaultInjector(FaultPlan.parse("crash@5")),
                       store=store)
    mirror = QueryEngine(copy.deepcopy(index0), qc, clock=ManualClock())
    crashed = False
    for t in range(10):
        for e in (ceng, mirror):
            e.insert(insert_pool[t])
            if t % 3 == 2:
                e.remove_user(10 * t)
        try:
            ceng.step()
        except EngineCrash:
            crashed = True
            break
        mirror.step()
    if crashed:
        mirror.step()  # the mirror runs the step the crash pre-empted
    wal_at_crash = int(store.wal.n_records)
    rec_eng = QueryEngine.recover(tmp, qc, clock=ManualClock())
    rows_ok = all(np.array_equal(getattr(rec_eng.index, name),
                                 getattr(mirror.index, name))
                  for name in _ROWS)
    probe = profiles[:16]
    a, recall_rec = wave(rec_eng, probe)
    b, _ = wave(mirror, probe)
    crash_row = {
        "crashed": bool(crashed),
        "crash_step": 5,
        "snapshot_every": 3,
        "snapshots": int(store.n_snapshots),
        "wal_records_at_crash": wal_at_crash,
        "rows_bitwise": bool(rows_ok),
        "answers_bitwise": bool(same(a, b)),
        "recovered_version": int(rec_eng.index.version),
        "recall_after_recovery": recall_rec,
    }
    shutil.rmtree(tmp, ignore_errors=True)
    return {"shards": shards, "kill_failover": kill_row,
            "crash_recovery": crash_row}


def descent_scoring_stats(index, profiles, k: int, beam: int, hops: int,
                          seeds_per_config: int = 16) -> dict:
    """Per-hop scored-candidate counts through the fused kernel on the
    same routed wave the serving rows answer: how many estimator lanes
    survive dedup-before-scoring vs the unfused ``beam·(kg+kr)``, and —
    through the HBM-resident DMA placement of the same hop — how many
    fingerprint bytes actually move vs how many the suppressed-lane
    skip leaves in HBM. The DMA hop's (ids, sims) are asserted bitwise
    against the VMEM hop's along the way."""
    import jax.numpy as jnp

    from repro.kernels.descent_score import ops as ds_ops
    from repro.query.router import routed_queries
    from repro.query.search import descent_init

    qw, qc, seeds = (jnp.asarray(x) for x in
                     routed_queries(index, profiles, seeds_per_config))
    g, r = jnp.asarray(index.graph_ids), jnp.asarray(index.rev_ids)
    w, c = jnp.asarray(index.words), jnp.asarray(index.card)
    beam = max(beam, k)
    bi, bs = descent_init(w, c, qw, qc, seeds, beam=beam)
    di, dsm = bi, bs
    per_hop, dma_per_hop, saved_per_hop = [], [], []
    for _ in range(hops):
        bi, bs, nsc, _, _ = ds_ops.descent_hop(
            g, r, w, c, qw, qc, bi, bs, with_counts=True)
        di, dsm, dnsc, dmab, saved = ds_ops.descent_hop(
            g, r, w, c, qw, qc, di, dsm, dma=True, with_counts=True)
        np.testing.assert_array_equal(np.asarray(di), np.asarray(bi))
        np.testing.assert_array_equal(np.asarray(dsm), np.asarray(bs))
        np.testing.assert_array_equal(np.asarray(dnsc), np.asarray(nsc))
        per_hop.append(float(np.asarray(nsc).mean()))
        dma_per_hop.append(float(np.asarray(dmab).mean()))
        saved_per_hop.append(float(np.asarray(saved).mean()))
    total = beam * (g.shape[1] + r.shape[1])
    dma_b, saved_b = float(np.sum(dma_per_hop)), float(np.sum(saved_per_hop))
    return {
        "candidates_per_hop": total,
        "scored_per_hop_mean": [round(x, 1) for x in per_hop],
        "scored_fraction": round(float(np.mean(per_hop)) / total, 3),
        "dma_kb_per_query_per_hop": [round(x / 1e3, 2)
                                     for x in dma_per_hop],
        "dma_kb_per_query": round(dma_b / 1e3, 2),
        "dma_saved_kb_per_query": round(saved_b / 1e3, 2),
        "dma_saved_fraction": round(saved_b / max(dma_b + saved_b, 1.0),
                                    3),
    }


def run(dataset: str = "synth", scale: float = 0.2, n_queries: int = 256,
        k: int = 10, beam: int = 32, hops: int = 3, seed: int = 0,
        shards: int = 2, oversample: float = 1.25,
        continuous: bool = False, slots: int = 32,
        churn: bool = False, overload: bool = False,
        rebalance: bool = False, faults: bool = False) -> dict:
    if shards < 2:
        raise SystemExit("query_bench compares sharded vs single-device "
                         "serving; --shards must be >= 2")
    ds = make_dataset(dataset, scale=scale, seed=seed)
    params = params_for(dataset, k=k, b=max(64, ds.n_users // 16),
                        max_cluster=max(48, int(0.06 * ds.n_users)))
    t0 = time.perf_counter()
    index = build_index(ds, params)
    t_build = time.perf_counter() - t0

    qds = make_dataset(dataset, scale=scale, seed=seed + 1)
    n_q = min(n_queries, qds.n_users)
    profiles = [qds.profile(u) for u in range(n_q)]

    single = QueryEngine(index, QueryConfig(k=k, beam=beam, hops=hops,
                                            max_wave=n_queries))
    sharded = QueryEngine(index, QueryConfig(k=k, beam=beam, hops=hops,
                                             max_wave=n_queries,
                                             shards=shards,
                                             shard_oversample=oversample))
    # Fused descent-scoring kernel rows, same index and query set — the
    # acceptance bar is recall parity to ±0.000 (the kernel is bitwise
    # transparent), so these rows isolate pure serving-path overheads.
    single_kernel = QueryEngine(index, QueryConfig(
        k=k, beam=beam, hops=hops, max_wave=n_queries, kernel=True))
    sharded_kernel = QueryEngine(index, QueryConfig(
        k=k, beam=beam, hops=hops, max_wave=n_queries, shards=shards,
        shard_oversample=oversample, kernel=True))
    # The same fused hop with HBM-resident tables + per-chunk candidate
    # DMA ("pallas_dma" scorer) — still bitwise, now with byte
    # accounting for the suppressed-lane skip.
    single_dma = QueryEngine(index, QueryConfig(
        k=k, beam=beam, hops=hops, max_wave=n_queries, kernel=True,
        dma=True))
    modes = {
        "single": _serve_waves(single, profiles, k),
        f"sharded_{shards}": _serve_waves(sharded, profiles, k),
        "single_kernel": _serve_waves(single_kernel, profiles, k),
        f"sharded_{shards}_kernel": _serve_waves(sharded_kernel, profiles, k),
        "single_dma": _serve_waves(single_dma, profiles, k),
    }
    scoring = descent_scoring_stats(index, profiles, k, beam, hops)
    served_dma = single_dma.plan.descent_stats
    scoring["serving_dma_bytes_per_query"] = round(
        served_dma["dma_bytes"] / max(served_dma["hop_queries"], 1), 1)
    scoring["serving_bytes_saved_per_query"] = round(
        served_dma["bytes_saved"] / max(served_dma["hop_queries"], 1), 1)
    sd = sharded.sharded_state()
    sharded_exec = "mesh" if sd is not None and sd.mesh is not None else "vmap"

    # Continuous-batching rows BEFORE the insert benchmark mutates the
    # shared index, so wave and continuous are measured on the same
    # index state and their recall numbers are directly comparable.
    cont = None
    cont_sharded = None
    if continuous:
        cont = run_continuous(index, profiles, k, beam, hops, slots,
                              seed=seed)
        # The sharded × continuous plan composition: same Poisson
        # open-loop protocol, per-shard slot arrays + release-time
        # cross-shard merge, gated bitwise against the sharded wave.
        cont_sharded = run_continuous(index, profiles, k, beam, hops,
                                      slots, seed=seed, shards=shards,
                                      oversample=oversample)

    # SLO-serving rows (overload sweep, adaptive budgets, result cache)
    # BEFORE the insert benchmark for the same same-index-state reason;
    # the cache arms mutate private deepcopies only.
    overload_rec = None
    adaptive_rec = None
    cache_rec = None
    if overload:
        overload_rec = run_overload(index, profiles, k, beam, hops,
                                    slots, seed=seed)
        adaptive_rec = run_adaptive(index, profiles, k, beam, hops,
                                    slots, seed=seed)
        cache_ds = make_dataset(dataset, scale=scale, seed=seed + 3)
        cache_pool = [cache_ds.profile(u)
                      for u in range(min(16, cache_ds.n_users))]
        cache_rec = run_cache(index, profiles, k, beam, hops, cache_pool,
                              seed=seed)

    # Sustained-churn trajectory BEFORE the insert benchmark, on private
    # deepcopies — the serving rows above and the churn arms must not
    # see each other's mutations.
    churn_rec = None
    if churn:
        # Replacement users come from an INDEPENDENT draw (seed+2) so the
        # inserts don't shadow the query distribution — the trajectory
        # should isolate graph damage, not ground-truth drift.
        ins_ds = make_dataset(dataset, scale=scale, seed=seed + 2)
        need = min(int(0.2 * index.n_live) + 8, ins_ds.n_users)
        pool = [ins_ds.profile(u) for u in range(need)]
        churn_rec = run_churn(index, profiles, k, beam, hops, pool,
                              seed=seed)

    # Re-balance arms run on private deepcopies; the residency sweep
    # reads the shared index, so both run BEFORE the insert benchmark.
    rebalance_rec = None
    residency_rec = None
    if rebalance:
        rebalance_rec = run_rebalance(index, ds, profiles, k, beam, hops,
                                      shards, seed=seed)
        residency_rec = run_residency_sweep(index, profiles, k, beam,
                                            hops, shards,
                                            oversample=oversample)

    # Fault-tolerance arms run on private deepcopies (and the crash arm
    # in a throwaway store dir), so they too run BEFORE the insert
    # benchmark mutates the shared index.
    faults_rec = None
    if faults:
        f_ds = make_dataset(dataset, scale=scale, seed=seed + 4)
        f_pool = [f_ds.profile(u) for u in range(min(12, f_ds.n_users))]
        faults_rec = run_faults(index, profiles, k, beam, hops, f_pool,
                                seed=seed, shards=shards)

    # Online insertion through the amortized-growth path (single engine;
    # the index is shared, so the sharded engine reshards lazily).
    t0 = time.perf_counter()
    n_ins = min(64, qds.n_users - n_q)
    for m in range(n_ins):
        single.insert(qds.profile(n_q + m))
    t_ins = time.perf_counter() - t0

    sh = modes[f"sharded_{shards}"]["warm"]
    sg = modes["single"]["warm"]
    return {
        "dataset": ds.name,
        "n_users": ds.n_users,
        "n_queries": n_q,
        "k": k,
        "beam": beam,
        "hops": hops,
        "shards": shards,
        "shard_oversample": oversample,
        "sharded_execution": sharded_exec,
        "n_devices": jax.device_count(),
        "t_build_s": round(t_build, 2),
        "modes": modes,
        "inserts": n_ins,
        "inserts_per_s": round(n_ins / max(t_ins, 1e-9), 1),
        "cohort_refreshes": single.n_refreshes,
        "index_capacity": index.capacity,
        "descent_scoring": scoring,
        "kernel_vs_jnp": {
            "recall_delta": round(
                modes["single_kernel"]["warm"][f"recall_at_{k}"]
                - modes["single"]["warm"][f"recall_at_{k}"], 4),
            "sharded_recall_delta": round(
                modes[f"sharded_{shards}_kernel"]["warm"][f"recall_at_{k}"]
                - modes[f"sharded_{shards}"]["warm"][f"recall_at_{k}"], 4),
            "dma_recall_delta": round(
                modes["single_dma"]["warm"][f"recall_at_{k}"]
                - modes["single"]["warm"][f"recall_at_{k}"], 4),
        },
        "sharded_vs_single": {
            "qps_ratio": round(sh["qps"] / max(sg["qps"], 1e-9), 3),
            "recall_delta": round(sh[f"recall_at_{k}"]
                                  - sg[f"recall_at_{k}"], 4),
        },
        **({"continuous": cont} if cont is not None else {}),
        **({f"sharded_{shards}_continuous": cont_sharded}
           if cont_sharded is not None else {}),
        **({"churn": churn_rec} if churn_rec is not None else {}),
        **({"overload": overload_rec} if overload_rec is not None else {}),
        **({"adaptive": adaptive_rec} if adaptive_rec is not None else {}),
        **({"cache": cache_rec} if cache_rec is not None else {}),
        **({"rebalance": rebalance_rec} if rebalance_rec is not None
           else {}),
        **({"residency_sweep": residency_rec} if residency_rec is not None
           else {}),
        **({"faults": faults_rec} if faults_rec is not None else {}),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synth")
    ap.add_argument("--scale", type=float, default=0.2)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--beam", type=int, default=32)
    ap.add_argument("--hops", type=int, default=3)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--oversample", type=float, default=1.25,
                    help="sharded fleet frontier vs single-device beam")
    ap.add_argument("--devices", type=int, default=None,
                    help="emulated host devices (default: --shards; 0=off)")
    ap.add_argument("--continuous", action="store_true",
                    help="add wave-vs-continuous closed/open-loop rows")
    ap.add_argument("--slots", type=int, default=32,
                    help="continuous-mode in-flight slot capacity")
    ap.add_argument("--churn", action="store_true",
                    help="add sustained-churn recall-trajectory rows "
                         "(repair on vs off under 20%% turnover)")
    ap.add_argument("--overload", action="store_true",
                    help="add SLO-serving rows: 0.85/0.95/1.2-load "
                         "overload sweep (slo vs fifo), adaptive hop "
                         "budgets, and the journal-invalidated result "
                         "cache")
    ap.add_argument("--rebalance", action="store_true",
                    help="add background re-balance rows: frozen-extend "
                         "vs rebalanced imbalance under skewed insert "
                         "growth, forced blue/green swap checks, and "
                         "the tiered-residency sweep")
    ap.add_argument("--faults", action="store_true",
                    help="add fault-tolerance rows: kill 1 shard mid-"
                         "open-loop (keeps answering, degraded recall "
                         "priced, failover rebuild, post-recovery "
                         "bitwise) and crash + snapshot/WAL-replay "
                         "bitwise recovery")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run; exit 1 on sharded regression")
    ap.add_argument("--out", default="BENCH_query.json")
    args = ap.parse_args()
    use_compile_cache()

    if args.smoke:
        args.scale, args.queries = min(args.scale, 0.1), min(args.queries, 64)
        args.slots = min(args.slots, 16)
    rec = run(args.dataset, args.scale, args.queries, args.k, args.beam,
              args.hops, shards=args.shards, oversample=args.oversample,
              continuous=args.continuous, slots=args.slots,
              churn=args.churn, overload=args.overload,
              rebalance=args.rebalance, faults=args.faults)
    Path(args.out).write_text(json.dumps(rec, indent=2))
    print(json.dumps(rec, indent=2))
    print(f"[query_bench] wrote {args.out}")

    if args.smoke:
        ratio = rec["sharded_vs_single"]["qps_ratio"]
        delta = rec["sharded_vs_single"]["recall_delta"]
        # CI floor: sharded must not collapse (generous margins — CI
        # machines are noisy; the committed BENCH_query.json carries the
        # quiet-machine numbers).
        if ratio < 0.5 or delta < -0.05:
            print(f"[query_bench] FAIL sharded regression: qps_ratio="
                  f"{ratio} recall_delta={delta}", file=sys.stderr)
            sys.exit(1)
        print(f"[query_bench] smoke OK: qps_ratio={ratio} "
              f"recall_delta={delta}")
        # The fused kernel is bitwise transparent: recall must match the
        # jnp rows EXACTLY (±0.000), and dedup-before-scoring must have
        # removed estimator work.
        kd = rec["kernel_vs_jnp"]
        frac = rec["descent_scoring"]["scored_fraction"]
        if (kd["recall_delta"] != 0.0 or kd["sharded_recall_delta"] != 0.0
                or kd["dma_recall_delta"] != 0.0):
            print(f"[query_bench] FAIL kernel recall drift: {kd}",
                  file=sys.stderr)
            sys.exit(1)
        if not frac < 1.0:
            print(f"[query_bench] FAIL kernel scored no fewer lanes: "
                  f"{rec['descent_scoring']}", file=sys.stderr)
            sys.exit(1)
        if not (rec["descent_scoring"]["dma_saved_kb_per_query"] > 0
                and rec["descent_scoring"]["serving_bytes_saved_per_query"]
                > 0):
            print(f"[query_bench] FAIL DMA suppressed-lane skip saved no "
                  f"bytes: {rec['descent_scoring']}", file=sys.stderr)
            sys.exit(1)
        print(f"[query_bench] kernel smoke OK: recall_delta=0.0 "
              f"scored_fraction={frac} dma_saved_fraction="
              f"{rec['descent_scoring']['dma_saved_fraction']}")
        if args.continuous:
            # Streaming admission must keep result quality: recall parity
            # with waves (identical descent ⇒ tight margin even on noisy
            # CI) and full completion of the open-loop run.
            cd = rec["continuous"]["open_loop_recall"]["delta"]
            if abs(cd) > 0.005:
                print(f"[query_bench] FAIL continuous recall drift: "
                      f"delta={cd}", file=sys.stderr)
                sys.exit(1)
            print(f"[query_bench] continuous smoke OK: recall_delta={cd} "
                  f"p95_improvement="
                  f"{rec['continuous']['p95_improvement']}")
            # Sharded × continuous composition: batching is results-
            # transparent under a fixed placement, so closed-loop results
            # must equal the sharded wave BITWISE (recall delta ±0.000).
            sc = rec[f"sharded_{args.shards}_continuous"]
            scw = sc["closed_loop_vs_wave"]
            if not scw["bitwise_equal"] or scw["recall_delta"] != 0.0:
                print(f"[query_bench] FAIL sharded-continuous drift vs "
                      f"sharded wave: {scw}", file=sys.stderr)
                sys.exit(1)
            scd = sc["open_loop_recall"]["delta"]
            if abs(scd) > 0.005:
                print(f"[query_bench] FAIL sharded-continuous open-loop "
                      f"recall drift: delta={scd}", file=sys.stderr)
                sys.exit(1)
            print(f"[query_bench] sharded-continuous smoke OK: "
                  f"closed-loop bitwise, open-loop recall_delta={scd}")
        if args.overload:
            # Overload-degradation gate: at 1.2× capacity the slo policy
            # must (a) shed explicitly, (b) keep the pending queue
            # bounded while FIFO's collapses, and (c) hold the protected
            # class's p95 near its uncontended value (generous CI margin
            # on the ratio; the committed BENCH_query.json carries the
            # quiet-machine <= 2x number).
            ov = rec["overload"]
            peak = ov["slo"]["1.2"]
            if peak["shed"] == 0:
                print(f"[query_bench] FAIL overload: slo shed nothing at "
                      f"1.2x capacity: {peak}", file=sys.stderr)
                sys.exit(1)
            if peak["max_queue_depth"] > ov["max_pending"] + args.slots:
                print(f"[query_bench] FAIL overload: slo queue exceeded "
                      f"its bound: {peak['max_queue_depth']} > "
                      f"{ov['max_pending']}", file=sys.stderr)
                sys.exit(1)
            # FIFO collapse criterion: its queue must grow past the
            # bound slo admission enforces (the depth_ratio in the
            # committed BENCH_query.json shows the full contrast; the
            # smoke gate uses the bound because absolute depths are
            # noise-prone at CI scale).
            if (ov["queue_collapse"]["fifo_max_queue_depth"]
                    <= ov["max_pending"]):
                print(f"[query_bench] FAIL overload: fifo queue stayed "
                      f"within the slo bound ({ov['max_pending']}): "
                      f"{ov['queue_collapse']}", file=sys.stderr)
                sys.exit(1)
            deg = ov["hp_p95_degradation"]
            if deg is None or deg > 4.0:
                print(f"[query_bench] FAIL overload: high-priority p95 "
                      f"degraded {deg}x from 0.85 to 1.2 load",
                      file=sys.stderr)
                sys.exit(1)
            print(f"[query_bench] overload smoke OK: shed={peak['shed']} "
                  f"hp_p95_degradation={deg} "
                  f"depth_ratio={ov['queue_collapse']['depth_ratio']}")
            # Adaptive budgets must actually save hops without giving up
            # meaningful recall (tight -0.005 on the committed bench;
            # smoke allows noise).
            ad = rec["adaptive"]
            if ad["ticks_saved"] <= 0 or ad["recall_delta"] < -0.02:
                print(f"[query_bench] FAIL adaptive budgets: "
                      f"ticks_saved={ad['ticks_saved']} "
                      f"recall_delta={ad['recall_delta']}",
                      file=sys.stderr)
                sys.exit(1)
            print(f"[query_bench] adaptive smoke OK: "
                  f"ticks_saved={ad['ticks_saved']} "
                  f"qps_gain={ad['qps_gain']} "
                  f"recall_delta={ad['recall_delta']}")
            # The cache is only correct if it is invisible: bitwise
            # equality against cache-off across interleaved mutations,
            # AND it must actually hit on the repeated stream.
            ca = rec["cache"]
            if not ca["bitwise_equal"] or ca["hit_rate"] <= 0.0:
                print(f"[query_bench] FAIL cache: bitwise_equal="
                      f"{ca['bitwise_equal']} hit_rate={ca['hit_rate']}",
                      file=sys.stderr)
                sys.exit(1)
            print(f"[query_bench] cache smoke OK: bitwise, "
                  f"hit_rate={ca['hit_rate']} qps_gain={ca['qps_gain']}")
        if args.churn:
            # Under sustained turnover the repair pass must hold recall
            # near the no-churn baseline while repair-off is the decayed
            # arm (CI margins are generous; the committed
            # BENCH_query.json carries the quiet-machine trajectory).
            ch = rec["churn"]
            if ch["repair_vs_baseline"] < -0.03:
                print(f"[query_bench] FAIL churn repair did not hold "
                      f"recall: {ch['repair_vs_baseline']} vs baseline "
                      f"{ch['no_churn_recall']}", file=sys.stderr)
                sys.exit(1)
            # At smoke scale the two arms sit within noise of each other;
            # the gate only trips when repair actively HURTS recall.
            if ch["repair_recovery"] < -0.01:
                print(f"[query_bench] FAIL repair-on recall below "
                      f"repair-off: {ch['repair_recovery']}",
                      file=sys.stderr)
                sys.exit(1)
            print(f"[query_bench] churn smoke OK: repair_vs_baseline="
                  f"{ch['repair_vs_baseline']} recovery="
                  f"{ch['repair_recovery']}")
        if args.rebalance:
            # Blue/green swap gate: the forced swap must restore balance,
            # keep recall (placement moves individual results, so the
            # margin is the same ±0.005 the continuous rows get), flush
            # the result cache (journals cannot see a swap), and the
            # merge-based rebuild must equal a from-scratch build
            # BITWISE — the symmetric-merge + audit-patch guarantee.
            rb = rec["rebalance"]
            fs = rb["forced_swap"]
            if fs["post_swap_imbalance"] > 1.25:
                print(f"[query_bench] FAIL rebalance: post-swap imbalance "
                      f"{fs['post_swap_imbalance']} > 1.25", file=sys.stderr)
                sys.exit(1)
            # A swap changes placement — the one axis that may move
            # individual results — so the recall check is granular: at
            # the 64-query smoke scale one flipped result slot is
            # 0.0016, and the committed full-scale BENCH_query.json
            # carries the tight ±0.005 number.
            if abs(fs["recall_delta"]) > 0.02:
                print(f"[query_bench] FAIL rebalance: recall moved "
                      f"{fs['recall_delta']} across the swap",
                      file=sys.stderr)
                sys.exit(1)
            if not fs["cache_flushed"]:
                print("[query_bench] FAIL rebalance: swap did not flush "
                      "the result cache", file=sys.stderr)
                sys.exit(1)
            if not fs["merge_bitwise_equal"]:
                print("[query_bench] FAIL rebalance: merge-based rebuild "
                      "!= from-scratch plan_shards build", file=sys.stderr)
                sys.exit(1)
            # The rebalanced arm must end at or under the threshold (the
            # re-balancer's contract), and never land above the frozen
            # arm it exists to beat.
            fin = rb["rebalanced"]["final_imbalance"]
            if fin > rb["threshold"] + 0.01 \
                    or fin > rb["frozen"]["final_imbalance"] + 1e-9:
                print(f"[query_bench] FAIL rebalance: rebalanced arm "
                      f"imbalance {fin} vs frozen "
                      f"{rb['frozen']['final_imbalance']} (threshold "
                      f"{rb['threshold']})", file=sys.stderr)
                sys.exit(1)
            if rb["rebalanced"]["recall_delta_vs_single"] < -0.05:
                print(f"[query_bench] FAIL rebalance: recall fell "
                      f"{rb['rebalanced']['recall_delta_vs_single']} vs "
                      f"single-shard", file=sys.stderr)
                sys.exit(1)
            rs = rec["residency_sweep"]["rows"]
            if any(r["max_resident_bytes"] > rs[0]["max_resident_bytes"]
                   for r in rs[1:]):
                print(f"[query_bench] FAIL residency: restricting configs "
                      f"did not shrink resident bytes: {rs}",
                      file=sys.stderr)
                sys.exit(1)
            print(f"[query_bench] rebalance smoke OK: post_swap_imbalance="
                  f"{fs['post_swap_imbalance']} recall_delta="
                  f"{fs['recall_delta']} merge_coverage="
                  f"{fs['merge']['merge_coverage']} rebalanced_final={fin} "
                  f"frozen_final={rb['frozen']['final_imbalance']}")
        if args.faults:
            # Kill-recover gate: killing 1 of N shards mid-open-loop
            # must never drop a request, degraded answers must stay
            # useful (bounded recall, not zero — survivors still own
            # their basins), the failover must actually fire, and the
            # recovered fleet must answer BITWISE what the pre-failure
            # fleet answered (no mutations happened, so any drift is a
            # rebuild/swap bug).
            kf = rec["faults"]["kill_failover"]
            if kf["served"] != kf["submitted"] or kf["shed"] != 0:
                print(f"[query_bench] FAIL faults: dropped requests under "
                      f"shard kill: served={kf['served']}/"
                      f"{kf['submitted']} shed={kf['shed']}",
                      file=sys.stderr)
                sys.exit(1)
            if kf["degraded_served"] == 0:
                print("[query_bench] FAIL faults: kill window served no "
                      "degraded requests (injection did not land)",
                      file=sys.stderr)
                sys.exit(1)
            if kf["degraded_recall"] is None or kf["degraded_recall"] < 0.2:
                print(f"[query_bench] FAIL faults: degraded recall "
                      f"collapsed: {kf['degraded_recall']}",
                      file=sys.stderr)
                sys.exit(1)
            if kf["failovers"] < 1 or not kf["post_recovery_bitwise"]:
                print(f"[query_bench] FAIL faults: failover did not "
                      f"restore the fleet: failovers={kf['failovers']} "
                      f"post_recovery_bitwise="
                      f"{kf['post_recovery_bitwise']}", file=sys.stderr)
                sys.exit(1)
            # Crash-consistency gate: snapshot + WAL replay must be
            # bitwise — tensors AND answers — against the never-crashed
            # mirror.
            cr = rec["faults"]["crash_recovery"]
            if not (cr["crashed"] and cr["rows_bitwise"]
                    and cr["answers_bitwise"]):
                print(f"[query_bench] FAIL faults: crash recovery not "
                      f"bitwise: {cr}", file=sys.stderr)
                sys.exit(1)
            print(f"[query_bench] faults smoke OK: "
                  f"degraded_served={kf['degraded_served']} "
                  f"degraded_recall={kf['degraded_recall']} "
                  f"failovers={kf['failovers']} post_recovery=bitwise "
                  f"crash_recovery=bitwise "
                  f"(snapshots={cr['snapshots']}, "
                  f"wal_records={cr['wal_records_at_crash']})")


if __name__ == "__main__":
    main()
