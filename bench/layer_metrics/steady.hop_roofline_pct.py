"""Share of its HBM roofline that the ``slot_hop`` program reaches: the
bytes the window's hops had to read (bench/work.py, from the active
query-hops the program counts) at peak bandwidth, over the program's
device time."""

from bench import work


def read(run):
    if run.trace is None:
        return None
    seconds, runs = run.trace.program("slot_hop")
    qh = run.counts.get("hop_queries", 0)
    if not runs or not qh:
        return None
    w = run.work
    nbytes = work.hop_bytes(qh, w["beam"], w["k_graph"], w["r_max"],
                            w["words"])
    return work.roofline_pct(nbytes, seconds, run.device_kind)
