"""Bytes and operations the algorithm needs, computed from shapes.

Counted from the algorithm, not from one implementation, so every
implementation of a step is read against the same work.

A descent hop, per active query: the beam's ``beam`` rows each offer
``k_graph`` forward and ``r_max`` reverse neighbours, one candidate lane
each. A lane reads its 4-byte id, the candidate's ``W``-word fingerprint
row and its 4-byte cardinality. The popcount work has no published peak
on the chip, so a hop is bounded by bytes alone.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def lanes_per_query_hop(beam: int, k_graph: int, r_max: int) -> int:
    return beam * (k_graph + r_max)


def bytes_per_lane(words: int) -> int:
    return 4 + 4 * words + 4


def hop_bytes(query_hops: int, beam: int, k_graph: int, r_max: int,
              words: int) -> int:
    """Bytes that ``query_hops`` (active query × hop) must read."""
    return (query_hops * lanes_per_query_hop(beam, k_graph, r_max)
            * bytes_per_lane(words))


def roofline_pct(nbytes: float, device_s: float, device_kind: str) -> float:
    """Least time to move ``nbytes`` at peak HBM bandwidth, as a share
    of the measured device time."""
    return 100.0 * nbytes / peaks(device_kind)["hbm_bytes_per_s"] / device_s
