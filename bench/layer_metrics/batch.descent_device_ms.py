"""Device milliseconds per wave in the wave program (``batched_descent``,
traced as ``descent_kernel``)."""


def read(run):
    if run.trace is None:
        return None
    seconds, runs = run.trace.program("descent_kernel")
    return 1e3 * seconds / run.counts["waves"] if runs else None
