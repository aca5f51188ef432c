"""Host seconds per build building the reverse adjacency of the index
(program span ``repro.index.reverse``, ``reverse_neighbors_np``)."""

from bench import program_spans


def read(run):
    s = program_spans.total(run, "repro.index.reverse")
    return None if s is None else s / run.counts["builds"]
