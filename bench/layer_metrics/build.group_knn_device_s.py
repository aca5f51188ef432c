"""Device seconds per build in the group-KNN programs: the jnp
``_group_knn`` program, or the Pallas cluster kernel's when it is the path."""

PROGRAMS = ("_group_knn", "cluster_knn", "_pallas_group_knn")


def read(run):
    if run.trace is None:
        return None
    seconds, runs = run.trace.program(*PROGRAMS)
    return seconds / run.counts["builds"] if runs else None
