"""Host seconds per build in local KNN outside the group programs' dispatch
and readback: gather, scatter and the Hyrec branch (program span
``repro.local_knn`` less ``repro.local_knn.device``)."""

from bench import program_spans


def read(run):
    whole = program_spans.total(run, "repro.local_knn")
    device = program_spans.total(run, "repro.local_knn.device")
    if whole is None or device is None:
        return None
    return (whole - device) / run.counts["builds"]
