"""Milliseconds of the traced window per serving step
(``QueryEngine.step``: admission, one hop, releases)."""


def read(run):
    steps = run.spans.count("steady.step")
    return 1e3 * run.window_s / steps if steps else None
