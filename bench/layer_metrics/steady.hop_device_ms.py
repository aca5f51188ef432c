"""Device milliseconds per run of the ``slot_hop`` program."""


def read(run):
    if run.trace is None:
        return None
    seconds, runs = run.trace.program("slot_hop")
    return 1e3 * seconds / runs if runs else None
