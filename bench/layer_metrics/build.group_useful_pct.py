"""Share of the pairs the group programs score that are pairs of distinct
co-members: counters ``repro.local_knn.pairs_useful`` (sum of
|C|(|C|-1) over brute-force clusters) over ``pairs_computed`` (sum of
m * cap * (cap-1) over dispatches, m the padded cluster count)."""

from bench import program_spans


def read(run):
    useful = program_spans.counter(run, "repro.local_knn.pairs_useful")
    computed = program_spans.counter(run, "repro.local_knn.pairs_computed")
    if not useful or not computed:
        return None
    return 100.0 * useful / computed
