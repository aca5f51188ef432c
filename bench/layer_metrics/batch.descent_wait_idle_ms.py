"""Device milliseconds per wave idle inside the descent (program span
``repro.wave.descent``): the span's time less the wave program's device
time (``descent_kernel``), which runs inside it."""

from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    waited = program_spans.total(run, "repro.wave.descent")
    busy, runs = run.trace.program("descent_kernel")
    if waited is None or not runs:
        return None
    return 1e3 * (waited - busy) / run.counts["waves"]
