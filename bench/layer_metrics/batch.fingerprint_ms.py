"""Host milliseconds per wave turning profiles into CSR rows and GoldFinger
fingerprints (program span ``repro.wave.fingerprint``)."""

from bench import program_spans


def read(run):
    s = program_spans.total(run, "repro.wave.fingerprint")
    return None if s is None else 1e3 * s / run.counts["waves"]
