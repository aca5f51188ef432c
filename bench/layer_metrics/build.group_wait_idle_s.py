"""Device seconds per build idle while the host waits on a group program
(program span ``repro.local_knn.device``: transfers in, the program,
readback): the span's time less the group programs' device time, which
runs inside it."""

from bench import program_spans

PROGRAMS = ("_group_knn", "cluster_knn", "_pallas_group_knn")


def read(run):
    if run.trace is None:
        return None
    waited = program_spans.total(run, "repro.local_knn.device")
    busy, runs = run.trace.program(*PROGRAMS)
    if waited is None or not runs:
        return None
    return (waited - busy) / run.counts["builds"]
