"""The program's own spans and counters (``repro.sched.trace``), as the
per-layer readers see them, and the device's idle time charged to them.

The program records its spans while a profiler trace is being taken, so
a traced run holds them for its window; each span is also an annotation
on the host plane of the trace, named ``repro.<step>``. A program with
no spans of its own gives ``None``, and its metrics are left out.

Program spans nest, unlike the benchmark's: an idle interval of the
device is charged to the innermost program span open over it
(:func:`charge`). ``bench/trace.py`` reads only the benchmark's spans,
so its ``gaps`` and ``breakdown`` are the same with or without these.
"""
from __future__ import annotations

import gzip

from bench import trace as bench_trace

PREFIX = "repro."


def _live(run) -> dict | None:
    from repro.sched import trace
    if not hasattr(trace, "records") or run.spans is None:
        return None
    windows = [(s, e) for n, s, e in run.spans.records
               if n == bench_trace.WINDOW_SPAN]
    if not windows:
        return None
    w0, w1 = windows[0]
    return {"spans": [(n, t0, t1) for n, _, t0, t1 in trace.records()
                      if w0 <= t0 and t1 <= w1],
            "counters": trace.summary()["counters"]}


def program(run) -> dict | None:
    """The window's program records: ``{"spans": [(name, start_s,
    end_s)], "counters": {name: n}}``, from ``run.program`` where the
    view carries them, else from the program's live trace module."""
    rec = getattr(run, "program", None)
    return rec if rec is not None else _live(run)


def total(run, name: str) -> float | None:
    """Seconds in the window's program spans named ``name``; ``None``
    where the program recorded none."""
    rec = program(run)
    if rec is None:
        return None
    durations = [e - s for n, s, e in rec["spans"] if n == name]
    return sum(durations) if durations else None


def counter(run, name: str) -> int | None:
    rec = program(run)
    return None if rec is None else rec["counters"].get(name)


def charge(idle, spans) -> dict:
    """Charge each idle interval to the innermost span open over it.

    ``idle``: disjoint ``(start, end)`` intervals; ``spans``: ``(name,
    start, end)``, any two of them disjoint or one inside the other. Time
    that no span covers goes to ``"other"``. Returns ``{name: time}`` in
    the units given; the values sum to the idle time.
    """
    out: dict = {}
    order = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    stack: list = []
    j = 0
    for s, e in sorted(idle):
        t = s
        while t < e:
            while j < len(order) and order[j][1] <= t:
                stack.append(order[j])
                j += 1
            while stack and stack[-1][2] <= t:
                stack.pop()
            nxt = e
            if j < len(order):
                nxt = min(nxt, order[j][1])
            if stack:
                nxt = min(nxt, stack[-1][2])
            name = stack[-1][0] if stack else "other"
            out[name] = out.get(name, 0) + (nxt - t)
            t = nxt
    return out


def read_trace(path: str) -> dict:
    """From a kept ``.xplane.pb`` (or its gzip): the window's program
    spans in seconds, ``{"spans", "counters": {}}`` as :func:`program`
    gives them, plus ``gaps`` (chip 0's idle seconds charged by
    :func:`charge`) and ``idle_s``."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    chip0, host = None, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and chip0 is None:
            chip0 = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                     for line in plane.lines
                     if line.name == bench_trace.MODULES_LINE
                     for ev in line.events]
        elif plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith(PREFIX)
                     or ev.name == bench_trace.WINDOW_SPAN]
    windows = [(s, e) for n, s, e in host if n == bench_trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {bench_trace.WINDOW_SPAN!r} span in {path}")
    w0, w1 = windows[0]
    spans = [(n, s, e) for n, s, e in host
             if n.startswith(PREFIX) and w0 <= s and e <= w1]
    busy = bench_trace._union([(max(s, w0), min(e, w1))
                               for s, e in chip0 or [] if e > w0 and s < w1])
    idle = [(s, e) for s, e in zip([w0] + [e for _, e in busy],
                                   [s for s, _ in busy] + [w1]) if e > s]
    gaps = charge(idle, spans)
    return {"spans": [(n, s / 1e9, e / 1e9) for n, s, e in spans],
            "counters": {},
            "gaps": {n: v / 1e9 for n, v in gaps.items()},
            "idle_s": sum(e - s for s, e in idle) / 1e9}
