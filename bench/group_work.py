"""Operations and bytes of the local-KNN group programs, from the algorithm.

Counted from what C² needs, not from one implementation, so padding and
the layout the program picks count as waste. On rows of ``W`` 32-bit
words, scored as an int8 bit-plane product on the MXU:

* operations: each unordered pair of distinct co-members is one dot
  product of ``32·W`` bit planes, a multiply and an add per bit, so
  ``2·32·W`` int8 operations; ``pairs_useful`` (the program's counter
  ``repro.local_knn.pairs_useful``) counts ordered pairs, twice the
  unordered, hence ``pairs_useful · 32·W``;
* bytes: each user's row of ``4·W`` bytes read once per hash
  configuration, ``t · n · 4·W`` per build.

The roofline is the larger of the two at the chip's peaks
(``bench/peaks.json``), over the programs' device time.
"""
from __future__ import annotations

from bench import work


def int8_ops(pairs_useful: int, words: int) -> int:
    return 32 * words * pairs_useful


def build_bytes(t: int, n_users: int, words: int) -> int:
    return t * n_users * 4 * words


def roofline_pct(ops: float, nbytes: float, device_s: float,
                 device_kind: str) -> float:
    """Least time for ``ops`` int8 operations and ``nbytes`` of HBM
    traffic at the chip's peaks, as a share of the measured time."""
    peak = work.peaks(device_kind)
    least = max(ops / peak["int8_ops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / device_s
