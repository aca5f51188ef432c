"""The plain reference: what the build and the serving path must produce.

Written from the paper's description and the configuration alone, in
numpy, with nothing imported from the program and nothing taken from
what it made. Three things are computed here:

* GoldFinger similarity (paper §II-F): bit ``fmix32(...) mod B`` set per
  item, similarity ``|a ∧ b| / (|a| + |b| − |a ∧ b|)`` in float32, the
  arithmetic the configuration states;
* the C² graph's semantics (paper §II-C/D): FastRandomHash clustering
  under ``t`` hash functions with recursive splitting to ``N``, every
  user's neighbours the top ``k`` by GoldFinger similarity over the
  union of its co-members (exact top-k inside each cluster, merged);
* exact Jaccard on raw profiles, by intersecting sorted item lists
  through an item→users inverted index, for recall.

``dtype`` selects the arithmetic of the similarity: ``float32`` is the
reference, ``bfloat16`` its control (the nearest precision below).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

NO_HASH = np.int64(2**31 - 1)
BF16 = ml_dtypes.bfloat16


def fmix32(x: np.ndarray) -> np.ndarray:
    """Murmur3's 32-bit finaliser, wrapping in uint32."""
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


# -- GoldFinger -------------------------------------------------------------

def fingerprints(items, offsets, n_bits: int, seed: int):
    """(words uint64[n, n_bits/64], card int64[n]) of CSR profiles."""
    x = ((items.astype(np.uint32) + np.uint32(0x9E3779B9))
         ^ np.uint32((seed * 0x85EBCA6B + 1) & 0xFFFFFFFF))
    pos = (fmix32(x) % np.uint32(n_bits)).astype(np.int64)
    n = len(offsets) - 1
    user = np.repeat(np.arange(n), np.diff(offsets))
    bits = np.zeros((n, n_bits), bool)
    bits[user, pos] = True
    words = np.packbits(bits, axis=1, bitorder="little").view(np.uint64)
    return words, bits.sum(axis=1).astype(np.int64)


def gf_sim(inter, card_a, card_b, dtype=np.float32):
    """GoldFinger Jaccard estimate from counts, in ``dtype``."""
    inter = np.asarray(inter).astype(dtype)
    union = (np.asarray(card_a).astype(dtype) + np.asarray(card_b).astype(dtype)
             - inter)
    out = np.where(union > 0, inter / np.maximum(union, dtype(1)), dtype(0))
    return out.astype(dtype)


def gf_inter(wa: np.ndarray, wb: np.ndarray) -> np.ndarray:
    """Row-wise popcount of ``wa & wb`` (broadcasting)."""
    return np.bitwise_count(wa & wb).sum(axis=-1, dtype=np.int64)


def gf_topk_all(q_words, q_card, words, card, k: int, dtype=np.float32,
                block: int = 8192):
    """Per query row, the ``k`` best GoldFinger similarities over every
    row of ``words``, descending. Intersections are bit-vector dot
    products, exact in float32 up to 2**24 bits.
    Returns (sims float32[q, k], ids int64[q, k])."""
    def bits(w):
        return np.unpackbits(w.view(np.uint8), axis=1,
                             bitorder="little").astype(np.float32)

    qb = bits(q_words)
    sims = np.empty((len(q_words), len(words)), np.float32)
    for s in range(0, len(words), block):
        e = min(s + block, len(words))
        inter = qb @ bits(words[s:e]).T
        sims[:, s:e] = gf_sim(inter, q_card[:, None], card[None, s:e], dtype)
    top = np.argpartition(-sims, k - 1, axis=1)[:, :k]
    order = np.argsort(-np.take_along_axis(sims, top, axis=1), axis=1,
                       kind="stable")
    ids = np.take_along_axis(top, order, axis=1).astype(np.int64)
    return np.take_along_axis(sims, ids, axis=1), ids


# -- C² semantics -----------------------------------------------------------

def _distinct_hashes(items, offsets, seed_i: int, b: int, depth: int):
    """Each user's ``depth`` smallest distinct item hashes under one
    FastRandomHash function, ascending, ``NO_HASH`` padded: int64[n, depth]."""
    x = items.astype(np.uint32) ^ np.uint32(((seed_i + 1) * 0x9E3779B9)
                                            & 0xFFFFFFFF)
    h = (fmix32(x) % np.uint32(b)).astype(np.int64)
    n = len(offsets) - 1
    key = np.repeat(np.arange(n, dtype=np.int64) * b, np.diff(offsets)) + h
    key.sort()
    key = key[np.r_[True, key[1:] != key[:-1]]]
    u, hv = key // b, key % b
    start = np.flatnonzero(np.r_[True, u[1:] != u[:-1]])
    rank = np.arange(len(u)) - np.repeat(start, np.diff(np.r_[start, len(u)]))
    out = np.full((n, depth), NO_HASH, np.int64)
    sel = rank < depth
    out[u[sel], rank[sel]] = hv[sel]
    return out


def _split(cands: np.ndarray, max_cluster: int) -> np.ndarray:
    """Recursive splitting of one configuration (paper §II-D).

    A cluster larger than ``max_cluster`` moves each member to its next
    distinct hash; members with none, and members alone in their new
    cluster, stay. Returns each user's final cluster label (−1: in no
    cluster of two or more) and each label's split path: the hashes its
    members share up to the depth at which it closed, ``NO_HASH``
    padded (int64[labels, depth])."""
    n, depth = cands.shape
    label = np.full(n, -1, np.int64)
    paths = []

    def close(mem, d):
        if len(mem) >= 2:
            label[mem] = len(paths)
            path = np.full(depth, NO_HASH, np.int64)
            path[:d] = cands[mem[0], :d]
            paths.append(path)

    def groups(mem, col):
        vals = cands[mem, col]
        o = np.argsort(vals, kind="stable")
        mem, vals = mem[o], vals[o]
        cut = np.flatnonzero(np.r_[True, vals[1:] != vals[:-1]])
        return [(vals[s], mem[s:e]) for s, e in zip(cut, np.r_[cut[1:],
                                                                len(mem)])]

    stack = [(m, 1) for v, m in groups(np.flatnonzero(cands[:, 0] != NO_HASH),
                                       0)]
    while stack:
        mem, d = stack.pop()
        if len(mem) <= max_cluster or d >= depth:
            close(mem, d)
            continue
        stay = []
        for v, child in groups(mem, d):
            if v == NO_HASH or len(child) == 1:
                stay.append(child)
            else:
                stack.append((child, d + 1))
        stay = np.concatenate(stay) if stay else np.zeros(0, np.int64)
        close(stay, d)
    return label, np.stack(paths) if paths else np.zeros((0, depth),
                                                           np.int64)


def hash_seeds(build: dict) -> np.ndarray:
    """The FastRandomHash seed of each configuration."""
    return np.arange(build["t"], dtype=np.int64) + build["seed"] * 1009


def cluster_tables(items, offsets, build: dict):
    """(int64[t, n]: each user's cluster under each hash configuration,
    and per configuration the split path of each of its clusters)."""
    out = [_split(_distinct_hashes(items, offsets, int(s), build["b"],
                                   build["split_depth"]),
                  build["max_cluster"])
           for s in hash_seeds(build)]
    return np.stack([lab for lab, _ in out]), [p for _, p in out]


def cluster_labels(items, offsets, build: dict) -> np.ndarray:
    """int64[t, n]: each user's cluster under each hash configuration."""
    return cluster_tables(items, offsets, build)[0]


def comembers(labels: np.ndarray, users: np.ndarray):
    """For each user in ``users``: (sorted co-member ids over every
    configuration, the size of its largest cluster)."""
    out = []
    for u in users:
        parts, largest = [], 0
        for row in labels:
            if row[u] >= 0:
                mem = np.flatnonzero(row == row[u])
                parts.append(mem)
                largest = max(largest, len(mem))
        cand = np.unique(np.concatenate(parts)) if parts else np.zeros(0, int)
        out.append((cand[cand != u], largest))
    return out


def graph_rows(users, cands, words, card, k: int, dtype=np.float32):
    """Reference rows of the C² graph. Per user: the ``k`` best
    similarities over its co-members and their ids (descending, −inf and
    −1 filled), and the similarity of every co-member, in the order of
    the sorted co-member ids."""
    sims_out = np.full((len(users), k), -np.inf, np.float32)
    ids_out = np.full((len(users), k), -1, np.int64)
    every = []
    for r, (u, (cand, _)) in enumerate(zip(users, cands)):
        s = gf_sim(gf_inter(words[u][None, :], words[cand]), card[u],
                   card[cand], dtype).astype(np.float32)
        top = np.argsort(-s, kind="stable")[:k]
        sims_out[r, :len(top)] = s[top]
        ids_out[r, :len(top)] = cand[top]
        every.append(s)
    return sims_out, ids_out, every


# -- exact Jaccard ----------------------------------------------------------

class Inverted:
    """item → users index over CSR profiles, for exact intersections."""

    def __init__(self, items, offsets):
        self.n = len(offsets) - 1
        self.sizes = np.diff(offsets)
        user = np.repeat(np.arange(self.n), self.sizes)
        order = np.argsort(items, kind="stable")
        self.users = user[order]
        self.starts = np.searchsorted(items[order],
                                      np.arange(items.max() + 2))

    def jaccard(self, profile: np.ndarray) -> np.ndarray:
        """float64[n]: exact Jaccard of ``profile`` with every user."""
        s, e = self.starts[profile], self.starts[profile + 1]
        idx = np.concatenate([np.arange(a, b) for a, b in zip(s, e)])
        inter = np.bincount(self.users[idx], minlength=self.n)
        union = len(profile) + self.sizes - inter
        return inter / np.maximum(union, 1)


def tie_aware_recall(returned: np.ndarray, sims: np.ndarray, k: int,
                     exclude: int | None = None) -> float:
    """Share of the ``k`` slots of ``returned`` holding a neighbour whose
    exact similarity is at least the k-th best exact similarity."""
    s = sims.copy()
    if exclude is not None:
        s[exclude] = -1.0
    kth = np.partition(s, len(s) - k)[len(s) - k]
    got = np.unique(returned[(returned >= 0) & (returned < len(sims))])
    return float(np.sum(s[got] >= kth)) / k
