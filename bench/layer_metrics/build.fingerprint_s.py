"""Host seconds per build in GoldFinger fingerprinting (span ``build.fingerprint``)."""


def read(run):
    return run.spans.total("build.fingerprint") / run.counts["builds"]
