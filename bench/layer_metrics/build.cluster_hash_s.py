"""Host seconds per build hashing users for the FRH cluster plan: item
hashes and each user's distinct-hash sequence (program span
``repro.cluster.hash``)."""

from bench import program_spans


def read(run):
    s = program_spans.total(run, "repro.cluster.hash")
    return None if s is None else s / run.counts["builds"]
