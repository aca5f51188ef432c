"""Share of their roofline that the bit-plane group programs reach: the
int8 operations of the useful pairs and the bytes of each user's row per
hash configuration (bench/group_work.py), over the ``_group_knn`` device
time of the window."""

from bench import group_work, program_spans


def read(run):
    if run.trace is None:
        return None
    seconds, runs = run.trace.program("_group_knn")
    useful = program_spans.counter(run, "repro.local_knn.pairs_useful")
    if not runs or not useful:
        return None
    w = run.work
    ops = group_work.int8_ops(useful, w["words"])
    nbytes = run.counts["builds"] * group_work.build_bytes(
        w["t"], w["n_users"], w["words"])
    return group_work.roofline_pct(ops, nbytes, seconds, run.device_kind)
