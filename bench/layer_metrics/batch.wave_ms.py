"""Host milliseconds per wave through ``QueryEngine.query_batch``,
routing included (span ``batch.wave``)."""


def read(run):
    waves = run.spans.count("batch.wave")
    return 1e3 * run.spans.total("batch.wave") / waves if waves else None
