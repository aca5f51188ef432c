"""Reduce a profiler trace to device busy time, program times and gaps.

What a TPU trace holds, as read with ``jax.profiler.ProfileData``: one
plane per chip named ``/device:TPU:<i>``, whose line ``XLA Modules``
carries one event per program run, named ``jit_<function>(<hash>)``
(``jit_slot_hop(…)``, ``jit_descent_kernel(…)``, ``jit__group_knn(…)``),
beside ``XLA Ops``, ``Async XLA Ops``, ``Scalar Unit`` and
``TC Overlay``. The host is the plane ``/host:CPU``; the benchmark's own
spans are ``TraceAnnotation`` events there, on the main thread's line,
named as the spans are (``bench.window``, ``steady.step`` …). Every event
start is in nanoseconds on one clock.

Busy time is the union of the program intervals of a chip, averaged
over the chips; the window is the ``bench.window`` span. A program is
matched by its stable function name, the part of the event name between
``jit_`` and the hash.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re

MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(event_name: str) -> str:
    """``jit_slot_hop(1564…)`` → ``slot_hop``."""
    return _NAME.match(event_name).group(1)


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # averaged over chips
    programs: dict                      # name -> [seconds, runs], all chips
    gaps: dict                          # host span name -> idle seconds
    chips: int

    def program(self, *names) -> tuple[float, int]:
        """(seconds, runs) summed over the programs named."""
        s = sum(self.programs.get(n, [0.0, 0])[0] for n in names)
        c = sum(self.programs.get(n, [0.0, 0])[1] for n in names)
        return s, c

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.programs.items(), key=lambda kv: -kv[1][0])[:top]
        return {"device_ops": [[n, v[0]] for n, v in ops],
                "idle_gaps": [[n, s] for n, s in sorted(
                    self.gaps.items(), key=lambda kv: -kv[1])[:top]]}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def reduce(path: str) -> Reduced:
    """Reduce the ``.xplane.pb`` file at ``path`` (or its gzip)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    chips, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    evs += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
            chips.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith(("bench.", "build.",
                                                 "steady.", "batch."))]
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    w0, w1 = windows[0]
    programs: dict = {}
    busy = []
    for evs in chips:
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                  if e > w0 and s < w1]
        for n, s, e in inside:
            rec = programs.setdefault(program_name(n), [0.0, 0])
            rec[0] += (e - s) / 1e9
            rec[1] += 1
        busy.append(_union([(s, e) for _, s, e in inside]))
    gaps: dict = {}
    if busy:
        idle = [(s, e) for s, e in zip([w0] + [e for _, e in busy[0]],
                                       [s for s, _ in busy[0]] + [w1])
                if e > s]
        inner = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
        # Share each idle interval of chip 0 among the spans it overlaps;
        # the benchmark's spans do not nest. What no span covers is
        # "other".
        j = 0
        for s, e in idle:
            covered = 0
            while j < len(inner) and inner[j][1] <= s:
                j += 1
            k = j
            while k < len(inner) and inner[k][0] < e:
                ov = min(e, inner[k][1]) - max(s, inner[k][0])
                if ov > 0:
                    gaps[inner[k][2]] = gaps.get(inner[k][2], 0.0) + ov / 1e9
                    covered += ov
                k += 1
            if e - s - covered > 0:
                gaps["other"] = gaps.get("other", 0.0) + (e - s - covered) / 1e9
    n_chips = max(len(chips), 1)
    busy_s = sum((e - s) for iv in busy for s, e in iv) / 1e9 / n_chips
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=busy_s,
                   programs=programs, gaps=gaps, chips=len(chips))
