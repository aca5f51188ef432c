"""Host seconds per build in the FRH cluster plan (span ``build.cluster``)."""


def read(run):
    return run.spans.total("build.cluster") / run.counts["builds"]
