"""Seconds per build in the merge of the t partial graphs (span ``build.merge``)."""


def read(run):
    return run.spans.total("build.merge") / run.counts["builds"]
