"""Share of its HBM roofline that the wave program reaches: hops times
queries times the bytes of one query-hop (bench/work.py) at peak
bandwidth, over the program's device time."""

from bench import work


def read(run):
    if run.trace is None:
        return None
    seconds, runs = run.trace.program("descent_kernel")
    if not runs:
        return None
    w = run.work
    nbytes = work.hop_bytes(w["hops"] * run.counts["queries"], w["beam"],
                            w["k_graph"], w["r_max"], w["words"])
    return work.roofline_pct(nbytes, seconds, run.device_kind)
