"""Host milliseconds per wave from padding the descent's inputs to its
answers as numpy: device sync, dispatch, the program and readback
(program span ``repro.wave.descent``)."""

from bench import program_spans


def read(run):
    s = program_spans.total(run, "repro.wave.descent")
    return None if s is None else 1e3 * s / run.counts["waves"]
