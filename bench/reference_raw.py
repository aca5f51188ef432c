"""The plain reference of the raw-data deployment (paper Table V).

C² on the raw profiles: the clustering of ``bench/reference.py``
unchanged, and every similarity the exact Jaccard of the two item sets.
A profile is held as incidence words, uint64 with item ``i`` at bit
``i % 64`` of word ``i // 64``, built from the sorted ``(user, item)``
keys by one OR-reduction per word, so the intersection is a popcount of
ANDed words and the union comes from the set sizes. Like the other
reference, it is numpy written from the paper and the configuration,
with nothing imported from the program.

``dtype`` of :func:`graph_rows` selects the arithmetic of the
similarity: ``float32`` is the reference, ``bfloat16`` its control.
"""
from __future__ import annotations

import numpy as np

from bench.reference import (BF16, Inverted, cluster_tables,  # noqa: F401
                             comembers, graph_rows, hash_seeds,
                             tie_aware_recall)


def incidence(items, offsets, n_items: int):
    """(words uint64[n, ceil(n_items / 64)], card int64[n]) of CSR
    profiles: bit ``i`` of a row is set iff item ``i`` is in the profile,
    ``card`` the number of distinct items."""
    n = len(offsets) - 1
    w = (n_items + 63) // 64
    user = np.repeat(np.arange(n, dtype=np.int64), np.diff(offsets))
    key = np.unique(user * (64 * w) + items.astype(np.int64))
    word = key >> 6
    bit = np.left_shift(np.uint64(1), (key & 63).astype(np.uint64))
    start = np.flatnonzero(np.r_[True, word[1:] != word[:-1]])
    words = np.zeros(n * w, np.uint64)
    words[word[start]] = np.bitwise_or.reduceat(bit, start)
    words = words.reshape(n, w)
    return words, np.bincount(key // (64 * w), minlength=n).astype(np.int64)
