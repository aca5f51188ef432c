"""Faults planted under the timed path, to show that ``correct`` sees them.

Each fault patches one function of the program for the duration of a
``with`` block and clears JAX's in-process caches on the way in and out,
so that a program traced before the patch is traced again with it.
Used by ``bench/tests/test_faults.py`` at a tiny size on the CPU and by
``bench/readings.py`` at the cells' own size on the chip.

Build faults:
  ``half_batch``  half of each capacity group's clusters left out of the
                  group program (their members get no partial neighbours
                  from that configuration);
  ``altered``     the first neighbour of every row replaced by the next
                  user id, as the merge hands the graph back;
  ``rev_empty``   index packaging leaves the reverse adjacency empty;
  ``paths_lost``  index packaging leaves every cluster's split path
                  empty (all ``NO_HASH``).
Serving faults:
  ``unchanged``   the descent hop returns the beam it was given;
  ``half_batch``  continuous: only the first half of the slots hop;
                  wave: the second half of each wave gets the first
                  half's answers;
  ``altered``     the last neighbour of every answer replaced by the
                  next user id, where the answer is produced.
"""
from __future__ import annotations

import contextlib

import numpy as np

BUILD = ("half_batch", "altered", "rev_empty", "paths_lost")
SERVE = ("unchanged", "half_batch", "altered")


@contextlib.contextmanager
def _patched(obj, name, new):
    import jax
    old = getattr(obj, name)
    setattr(obj, name, new)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(obj, name, old)
        jax.clear_caches()


def _shift(ids, col, n):
    ids = np.array(ids, copy=True)
    v = ids[:, col]
    ids[:, col] = np.where(v >= 0, (v + 1) % n, v)
    return ids


def build_fault(name: str):
    from repro.core import local_knn, merge
    from repro.query import index as index_mod
    from repro.types import KNNGraph

    if name == "half_batch":
        orig = local_knn._group_knn

        def half(words, card, member_ids, k):
            m = member_ids.shape[0]
            keep = (np.arange(m) < max(1, m // 2))[:, None]
            return orig(words, card, np.where(keep, member_ids, -1), k)
        return _patched(local_knn, "_group_knn", half)
    if name == "altered":
        orig = merge.merge_partial

        def altered(ids, sims, k):
            g = orig(ids, sims, k)
            return KNNGraph(ids=_shift(g.ids, 0, g.ids.shape[0]), sims=g.sims)
        return _patched(merge, "merge_partial", altered)
    if name == "rev_empty":
        def empty(ids, r_max):
            return np.full((ids.shape[0], r_max), -1, np.int32)
        return _patched(index_mod, "reverse_neighbors_np", empty)
    if name == "paths_lost":
        orig = index_mod.build_index

        def lost(*args, **kw):
            index = orig(*args, **kw)
            index.cluster_paths = np.full_like(index.cluster_paths,
                                               2**31 - 1)
            return index
        return _patched(index_mod, "build_index", lost)
    raise KeyError(name)


def serve_fault(name: str, batching: str):
    import jax.numpy as jnp
    from repro.query import plan, search

    if name == "unchanged":
        def still(graph_ids, rev_ids, words, card, q_words, q_card,
                  beam_ids, beam_sims, **kw):
            return beam_ids, beam_sims, jnp.zeros((beam_ids.shape[0], 3),
                                                  jnp.int32)
        return _patched(search, "descent_step", still)
    if name == "half_batch" and batching == "continuous":
        orig = plan.slot_hop

        def half(*args, **kw):
            active = args[8]
            n = active.shape[0]
            return orig(*args[:8], active & (jnp.arange(n) < n // 2), **kw)
        return _patched(plan, "slot_hop", half)
    if name == "half_batch":
        orig = plan.DescentPlan.query_batch

        def half(self, profiles, k=None, hops=None):
            ids, sims = orig(self, profiles, k=k, hops=hops)
            ids, sims = ids.copy(), sims.copy()
            h = (len(ids) + 1) // 2
            ids[h:], sims[h:] = ids[:len(ids) - h], sims[:len(ids) - h]
            return ids, sims
        return _patched(plan.DescentPlan, "query_batch", half)
    if name == "altered":
        attr = ("_slot_results" if batching == "continuous"
                else "query_batch")
        orig = getattr(plan.DescentPlan, attr)

        def altered(self, *args, **kw):
            ids, sims = orig(self, *args, **kw)
            return _shift(ids, -1, self.index.n), sims
        return _patched(plan.DescentPlan, attr, altered)
    raise KeyError(name)
