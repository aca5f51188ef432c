"""Share of the routed seed slots that hold a user: counters
``repro.wave.seeds`` over ``repro.wave.seed_slots`` (queries times
configurations times seeds per configuration)."""

from bench import program_spans


def read(run):
    seeds = program_spans.counter(run, "repro.wave.seeds")
    slots = program_spans.counter(run, "repro.wave.seed_slots")
    if seeds is None or not slots:
        return None
    return 100.0 * seeds / slots
