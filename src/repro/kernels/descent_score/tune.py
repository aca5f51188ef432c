"""Shape-keyed autotuner for the DMA descent hop.

The DMA hop (`descent_score.hop_pallas_dma`) has three launch knobs —
``block_q`` (queries per tile), ``score_chunk`` (candidate lanes per
DMA/score round) and ``n_buffers`` (rotating VMEM row-buffer depth) —
whose good values depend on the *index* shape, not the call site:
``(n, W, beam, kg+kr)`` fixes the candidate count, row width and VMEM
pressure. This module replaces the fixed constants with a small tuner:

* ``hop_params(n, W, beam, kdeg, q)`` → :class:`HopParams`, resolved in
  priority order: in-process memo → on-disk cache (JSON at
  ``$REPRO_TUNE_CACHE``, if set) → measured table (entries recorded by
  :func:`record`) → the VMEM-budget heuristic. Every resolution is
  memoized, so a serving plan asks exactly once per index shape — that
  is what keeps jit from re-tracing across admissions and reshards
  (same shape → same params → same trace; the compile-once regression
  in ``tests/test_descent_dma.py`` pins this).
* ``record(key, params)`` lets a measuring caller (``kernel_bench.py``)
  write a winner back; with ``$REPRO_TUNE_CACHE`` set it persists.
* ``stats`` counts hits/misses for CI gates.

The heuristic targets a scratch budget: the rotating row buffers cost
``n_buffers·block_q·score_chunk·R·4`` bytes (``R = dma_row_words(W)``,
the packed 128-lane row) and must leave room for the lane-id block
(``block_q·beam·(kg+kr)·4``, once in VMEM and once in SMEM) and the
merge inside a few MB of VMEM.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading

from repro.kernels.descent_score.descent_score import dma_row_words
from repro.sketch.goldfinger import MXU_MIN_WORDS

ENV_CACHE = "REPRO_TUNE_CACHE"

# Rotating-row-buffer budget for the heuristic (bytes). Deliberately far
# under real VMEM (16 MB) — the tables' staging and the compiler's own
# spills need the rest.
_SCRATCH_BUDGET = 2 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class HopParams:
    """Launch configuration for one (n, W, beam, kdeg) index shape."""
    block_q: int
    score_chunk: int
    n_buffers: int


stats = {"hits": 0, "misses": 0, "disk_hits": 0}

_lock = threading.Lock()
_memo: dict[tuple[int, int, int, int], HopParams] = {}
_measured: dict[tuple[int, int, int, int], HopParams] = {}
_disk_loaded = False


def shape_key(n: int, W: int, beam: int, kdeg: int) -> tuple[int, int, int, int]:
    return (int(n), int(W), int(beam), int(kdeg))


def _heuristic(n: int, W: int, beam: int, kdeg: int) -> HopParams:
    C = max(1, beam * kdeg)
    mxu = W >= MXU_MIN_WORDS
    # MXU tiles keep bq small (the bit-plane matmul is bq-quadratic in
    # the diagonal trick); popcount tiles amortize the fori_loop better
    # with more queries per tile.
    block_q = 8 if mxu else 16
    # Largest power-of-two chunk that fits the double-buffered budget.
    row_bytes = dma_row_words(W) * 4
    chunk = 128
    while chunk > 16 and 2 * block_q * chunk * row_bytes > _SCRATCH_BUDGET:
        chunk //= 2
    chunk = min(chunk, max(16, C))
    n_buffers = 1 if C <= chunk else 2
    return HopParams(block_q=block_q, score_chunk=chunk,
                     n_buffers=n_buffers)


def _cache_path() -> str | None:
    return os.environ.get(ENV_CACHE) or None


def _load_disk() -> None:
    global _disk_loaded
    if _disk_loaded:
        return
    _disk_loaded = True
    path = _cache_path()
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as fh:
            raw = json.load(fh)
    except (OSError, ValueError):
        return
    for skey, p in raw.items():
        try:
            key = tuple(int(x) for x in skey.split(","))
            if len(key) != 4:
                continue
            _measured[key] = HopParams(int(p["block_q"]),
                                       int(p["score_chunk"]),
                                       int(p["n_buffers"]))
        except (KeyError, TypeError, ValueError):
            continue


def _save_disk() -> None:
    path = _cache_path()
    if not path:
        return
    payload = {
        ",".join(str(x) for x in key): dataclasses.asdict(p)
        for key, p in sorted(_measured.items())
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
    os.replace(tmp, path)


def record(key: tuple[int, int, int, int], params: HopParams) -> None:
    """Record a measured winner for an index shape (and persist it)."""
    with _lock:
        _load_disk()
        _measured[key] = params
        _memo[key] = params
        _save_disk()


def hop_params(n: int, W: int, beam: int, kdeg: int,
               q: int | None = None) -> HopParams:
    """Resolve launch params for one index shape (memoized per process).

    ``q`` (the wave width) only clamps ``block_q`` — it is *not* part of
    the cache key, so admissions of different wave widths against the
    same index reuse one resolution.
    """
    key = shape_key(n, W, beam, kdeg)
    with _lock:
        p = _memo.get(key)
        if p is None:
            _load_disk()
            p = _measured.get(key)
            if p is not None:
                stats["disk_hits"] += 1
            else:
                p = _heuristic(*key)
            stats["misses"] += 1
            _memo[key] = p
        else:
            stats["hits"] += 1
    if q is not None and q > 0 and p.block_q > q:
        p = dataclasses.replace(p, block_q=max(1, q))
    return p


def clear(reset_stats: bool = True) -> None:
    """Drop all in-process state (tests; does not touch the disk cache)."""
    global _disk_loaded
    with _lock:
        _memo.clear()
        _measured.clear()
        _disk_loaded = False
        if reset_stats:
            for k in stats:
                stats[k] = 0
