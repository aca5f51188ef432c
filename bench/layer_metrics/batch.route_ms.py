"""Host milliseconds per wave in FRH routing, hash placements included
(program span ``repro.wave.route``)."""

from bench import program_spans


def read(run):
    s = program_spans.total(run, "repro.wave.route")
    return None if s is None else 1e3 * s / run.counts["waves"]
