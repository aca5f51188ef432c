"""Host seconds per build in the recursive split of the t configurations
and the assembly of cluster members (program span
``repro.cluster.split``)."""

from bench import program_spans


def read(run):
    s = program_spans.total(run, "repro.cluster.split")
    return None if s is None else s / run.counts["builds"]
