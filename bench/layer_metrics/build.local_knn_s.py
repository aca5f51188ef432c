"""Seconds per build in local KNN: host gather and scatter around the
device group programs (span ``build.local_knn``)."""


def read(run):
    return run.spans.total("build.local_knn") / run.counts["builds"]
