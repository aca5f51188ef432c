"""Host seconds per build in index packaging: reverse adjacency and
cluster tables (span ``build.index``)."""


def read(run):
    return run.spans.total("build.index") / run.counts["builds"]
