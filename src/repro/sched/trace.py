"""The program's tracing: jit-trace and launch counters, spans, host counters.

Jit-trace counters exist for compile-count regressions. A
continuous-batching engine must compile its step program ONCE per static
configuration and then reuse it for every tick, no matter how requests
stream in — a silent retrace per admission would turn the latency win
into a compile storm. The counter exploits that a jitted function's
*Python body* runs only while JAX traces it: the engine calls
:func:`bump` inside the traced body, so the count equals the number of
traces (= compiles, modulo cache eviction) for that key.
``tests/test_continuous.py`` asserts the count stays at 1 across
arbitrary admission interleavings.

Spans and host counters say where a build or a wave spends its host
time (see :func:`span`). Every span and counter name starts with
``repro.``; the benchmark's own spans use other prefixes, and its trace
reduction keys on them.
"""
from __future__ import annotations

import math
import time
from collections import Counter
from typing import Hashable

from jax.profiler import TraceAnnotation

_TRACES: Counter = Counter()


def bump(key: Hashable):
    """Record one trace of the program identified by ``key``.

    Call ONLY from inside a jit-traced function body.
    """
    _TRACES[key] += 1


def count(key: Hashable) -> int:
    """Traces recorded for ``key`` since process start (or last reset)."""
    return _TRACES[key]


def compile_count(plan_key: Hashable) -> int:
    """Total traces of every program tagged with ``plan_key``.

    Plan-owned programs (``query/plan.py`` via ``query/search.py`` /
    ``query/sharded.py``) embed the plan's identity tuple
    (:attr:`~repro.query.plan.PlanSpec.key`) in their bump keys; this
    sums the trace counts of every key carrying that tag, whatever the
    program or shape. ``tests/test_plan.py`` / ``tests/test_continuous``
    assert the total goes flat after warmup — compile-once per plan
    across admission interleavings AND delta reshards.
    """
    return sum(v for k, v in _TRACES.items()
               if isinstance(k, tuple) and any(e == plan_key for e in k))


def counts(prefix: str | None = None) -> dict:
    """Snapshot of all counters, optionally filtered by key[0] == prefix."""
    if prefix is None:
        return dict(_TRACES)
    return {k: v for k, v in _TRACES.items()
            if isinstance(k, tuple) and k and k[0] == prefix}


# -- host-side launch counters ---------------------------------------------
#
# ``bump`` counts TRACES (compiles) because it runs inside a jitted body;
# ``launch`` counts host-side program DISPATCHES — it is called from
# ordinary Python right where the engine launches (or would launch) a
# compiled program. The zero-hop-burst regression in ``query/plan.py``
# uses it: a tick's worth of completions must cost ONE slot-result
# snapshot, however many admission chunks fed the tick. Kept in a
# separate store so launch keys can carry plan-key tuples without
# polluting :func:`compile_count`'s tag search.

_LAUNCHES: Counter = Counter()


def launch(key: Hashable):
    """Record one host-side dispatch of the program identified by ``key``."""
    _LAUNCHES[key] += 1


def launch_count(key: Hashable) -> int:
    """Dispatches recorded for ``key`` since process start (or reset)."""
    return _LAUNCHES[key]


# -- spans and host counters -----------------------------------------------
#
# Spans record while tracing is on: after :func:`enable`, or while a JAX
# profiler trace is being captured (``jax.profiler.start_trace`` /
# ``jax.profiler.trace``). While a capture runs, each span is also a
# ``TraceAnnotation`` on the host plane of the trace, so it sits on the
# device trace's clock. Off, a span costs one flag test and one query of
# the profiler, and returns a shared no-op context: no clock read, no
# allocation. Spans are for the thread that drives the build or the
# serving loop; they do not nest across threads.

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_ON = False
_capturing = TraceAnnotation.is_enabled
_SPANS: list[tuple[str, int, float, float]] = []   # (name, parent, t0, t1)
_OPEN: list[int] = []                               # indices into _SPANS
_COUNTERS: Counter = Counter()
_LISTENING = False


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoSpan()


class _Span:
    __slots__ = ("name", "i", "note")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        if not _LISTENING:
            _listen()
        self.note = None
        if _capturing():
            self.note = TraceAnnotation(self.name)
            self.note.__enter__()
        self.i = len(_SPANS)
        _SPANS.append((self.name, _OPEN[-1] if _OPEN else -1,
                       time.perf_counter(), math.nan))
        _OPEN.append(self.i)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if _OPEN and _OPEN[-1] == self.i:  # not cleared while open
            _OPEN.pop()
            name, parent, t0, _ = _SPANS[self.i]
            _SPANS[self.i] = (name, parent, t0, t1)
        if self.note is not None:
            self.note.__exit__(*exc)
        return False


def _on_compile(event: str, duration: float, **kw) -> None:
    """Charge a backend compile (or compile-cache load) to the innermost
    open span, as the counter ``repro.compiles/<span>``."""
    if event == COMPILE_EVENT and _OPEN:
        _COUNTERS["repro.compiles/" + _SPANS[_OPEN[-1]][0]] += 1


def _listen() -> None:
    global _LISTENING
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    _LISTENING = True


def active() -> bool:
    """True while spans and counters record; callers test it before
    computing a count that only :func:`add` would use."""
    return _ON or _capturing()


def span(name: str):
    """Context manager timing one step of the program as ``name``."""
    if not (_ON or _capturing()):
        return _NOOP
    return _Span(name)


def add(name: str, n: int = 1) -> None:
    """Add ``n`` to the host counter ``name`` (no-op while off)."""
    if _ON or _capturing():
        _COUNTERS[name] += n


def enable() -> None:
    """Record spans and counters until :func:`disable`."""
    global _ON
    _ON = True


def disable() -> None:
    global _ON
    _ON = False


def clear_spans() -> None:
    """Drop the recorded spans and host counters."""
    _SPANS.clear()
    _OPEN.clear()
    _COUNTERS.clear()


def records() -> list[tuple[str, int, float, float]]:
    """Closed spans as ``(name, parent, t0, t1)`` on ``time.perf_counter``,
    in the order they opened; ``parent`` indexes this list (-1: none)."""
    return [r for r in _SPANS if not math.isnan(r[3])]


def summary() -> dict:
    """The operator's read-out: per span name, its total seconds, its self
    seconds (total less what child spans cover) and its count; and the
    host counters.

    ``{"spans": {name: {"total_s", "self_s", "count"}}, "counters": {}}``
    """
    child = [0.0] * len(_SPANS)
    for _, parent, t0, t1 in _SPANS:
        if parent >= 0 and not math.isnan(t1):
            child[parent] += t1 - t0
    out: dict = {}
    for i, (name, _, t0, t1) in enumerate(_SPANS):
        if math.isnan(t1):
            continue
        rec = out.setdefault(name, {"total_s": 0.0, "self_s": 0.0,
                                    "count": 0})
        rec["total_s"] += t1 - t0
        rec["self_s"] += t1 - t0 - child[i]
        rec["count"] += 1
    return {"spans": out, "counters": dict(_COUNTERS)}


def reset():
    """Clear all counters and spans (test isolation)."""
    _TRACES.clear()
    _LAUNCHES.clear()
    clear_spans()
