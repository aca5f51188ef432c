"""Seeded, vectorised generator of a Table I-shaped user–item population.

One call draws the indexed users and a held-out pool of query profiles
together, so both come from the same population: the same item→topic
map, the same popularity law, the same profile-size law. The pool is
the last ``n_pool`` users of the draw; users are independent, so which
rows are held out does not matter.

The law (its shape parameters are assumptions of the configuration, not
the real ratings):

* item popularity is Zipf over item ids (id 0 the most popular), with
  exponent ``zipf_a``;
* every item belongs to one of ``n_topics`` topics, each run of
  ``n_topics`` consecutive popularity ranks spread over all of them, and
  every user has a home topic, as even a split as the count allows;
  both in seeded order;
* profile sizes follow a lognormal of log-sd ``profile_sigma``, clipped
  to ``[min_profile, max_profile_factor · mean_profile]`` and to half
  the universe, whose location is solved so that the mean profile size
  is ``mean_profile``. Every seed gets the same multiset of sizes (the
  law's quantiles), in its own order, so that seeds change which items
  users hold and not how much work the population makes;
* ``round(topic_affinity · size)`` of the profile (at most the topic's
  item count) is a popularity-weighted sample without replacement from
  the home topic (Efraimidis–Spirakis keys), the rest a
  popularity-weighted sample without replacement from the whole
  universe, distinct from the home part.

Everything is numpy on the host and loops over topics and rounds only,
never over users.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Population:
    """CSR profiles of every drawn user; rows are sorted item ids."""

    items: np.ndarray      # int32[nnz]
    offsets: np.ndarray    # int64[n + 1]
    n_items: int
    item_topic: np.ndarray  # int32[n_items]
    user_topic: np.ndarray  # int32[n]

    @property
    def n(self) -> int:
        return len(self.offsets) - 1

    @property
    def sizes(self) -> np.ndarray:
        return np.diff(self.offsets)

    def profile(self, u: int) -> np.ndarray:
        return self.items[self.offsets[u]:self.offsets[u + 1]]

    def rows(self, lo: int, hi: int) -> "Population":
        """Users ``lo..hi-1`` as a population of their own."""
        a, b = self.offsets[lo], self.offsets[hi]
        return Population(items=self.items[a:b],
                          offsets=self.offsets[lo:hi + 1] - a,
                          n_items=self.n_items, item_topic=self.item_topic,
                          user_topic=self.user_topic[lo:hi])


def _balanced(rng, n: int, k: int) -> np.ndarray:
    """``n`` labels in ``range(k)``, as even as can be, in seeded order."""
    return rng.permutation(np.arange(n) % k).astype(np.int32)


def _stratified(rng, n: int, k: int) -> np.ndarray:
    """Labels for ``n`` popularity-ranked items: each run of ``k``
    consecutive ranks holds every label once, in seeded order, so every
    topic gets the same share of popular and of niche items."""
    blocks = -(-n // k)
    return rng.permuted(np.tile(np.arange(k), (blocks, 1)),
                        axis=1).reshape(-1)[:n].astype(np.int32)


def _profile_sizes(rng, n: int, stats: dict) -> np.ndarray:
    """The same multiset of sizes for every seed, in seeded order: the
    lognormal's quantiles at (i + 1/2) / n."""
    from statistics import NormalDist

    mean = float(stats["mean_profile"])
    lo = int(stats["min_profile"])
    hi = min(int(mean * stats["max_profile_factor"]), stats["n_items"] // 2)
    inv = NormalDist().inv_cdf
    z = rng.permutation(np.array([inv((i + 0.5) / n) for i in range(n)]))
    sigma = float(stats["profile_sigma"])

    def sizes(mu):
        return np.clip(np.floor(np.exp(mu + sigma * z)), lo, hi)

    a, b = np.log(lo) - 4 * sigma, np.log(hi)
    for _ in range(60):  # bisection on the location: mean size is monotone
        mid = 0.5 * (a + b)
        if sizes(mid).mean() < mean:
            a = mid
        else:
            b = mid
    return sizes(0.5 * (a + b)).astype(np.int64)


def _home_items(rng, user_topic, n_home, item_topic, weights, n_topics):
    """Per topic, each user's ``n_home`` smallest exponential keys
    ``E / w`` over the topic's items: a weighted sample without
    replacement. Returns sorted unique keys ``user · n_items + item``."""
    n_items = len(item_topic)
    out = []
    for t in range(n_topics):
        items_t = np.flatnonzero(item_topic == t)
        users_t = np.flatnonzero((user_topic == t) & (n_home > 0))
        if len(items_t) == 0 or len(users_t) == 0:
            continue
        keys = (rng.standard_exponential((len(users_t), len(items_t)),
                                         dtype=np.float32)
                / weights[items_t].astype(np.float32))
        need = n_home[users_t]
        # Bucket rows by a power-of-two bound on their count, so the
        # partition only orders as many columns as the bucket needs.
        cap = np.maximum(1, 2 ** np.ceil(np.log2(np.maximum(need, 1))))
        cap = np.minimum(cap, len(items_t)).astype(np.int64)
        for c in np.unique(cap):
            rows = np.flatnonzero(cap == c)
            kr = keys[rows]
            part = (np.argpartition(kr, c - 1, axis=1)[:, :c]
                    if c < len(items_t) else
                    np.broadcast_to(np.arange(c), (len(rows), c)))
            order = np.argsort(np.take_along_axis(kr, part, axis=1), axis=1)
            ranked = np.take_along_axis(part, order, axis=1)
            keep = np.arange(c)[None, :] < need[rows][:, None]
            u = np.broadcast_to(users_t[rows][:, None], ranked.shape)[keep]
            out.append(u.astype(np.int64) * n_items + items_t[ranked[keep]])
    return np.unique(np.concatenate(out)) if out else np.zeros(0, np.int64)


def _background(rng, chosen, target, cdf, n_items, max_rounds=64):
    """Add popularity-weighted items, distinct per user from ``chosen``,
    until each user holds ``target`` items: rounds of draws with
    replacement, first occurrences kept, over the users still short."""
    n = len(target)
    for _ in range(max_rounds):
        need = target - np.bincount(chosen // n_items, minlength=n)
        rows = np.flatnonzero(need > 0)
        if len(rows) == 0:
            return chosen
        u = np.repeat(rows, 2 * need[rows] + 4)
        item = np.searchsorted(cdf, rng.random(len(u)), side="right")
        key = u * n_items + np.minimum(item, n_items - 1)
        pos = np.searchsorted(chosen, key)
        pos = np.minimum(pos, max(len(chosen) - 1, 0))
        fresh = chosen[pos] != key if len(chosen) else np.ones(len(key), bool)
        key = key[fresh]
        _, first = np.unique(key, return_index=True)
        key = key[np.sort(first)]          # draw order, grouped by user
        uu = key // n_items
        start = np.flatnonzero(np.r_[True, uu[1:] != uu[:-1]])
        rank = np.arange(len(key)) - np.repeat(start, np.diff(np.r_[start,
                                                                  len(key)]))
        chosen = np.union1d(chosen, key[rank < need[uu]])
    raise RuntimeError("background sampling did not fill every profile")


def generate(stats: dict, seed: int, n_pool: int) -> Population:
    """Draw ``stats['n_users'] + n_pool`` users from ``seed``."""
    rng = np.random.default_rng(seed)
    n = int(stats["n_users"]) + int(n_pool)
    n_items = int(stats["n_items"])
    n_topics = int(stats["n_topics"])
    item_topic = _stratified(rng, n_items, n_topics)
    weights = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** float(
        stats["zipf_a"])
    weights /= weights.sum()
    user_topic = _balanced(rng, n, n_topics)
    sizes = _profile_sizes(rng, n, stats)
    topic_size = np.bincount(item_topic, minlength=n_topics)
    n_home = np.minimum(np.round(sizes * float(stats["topic_affinity"])),
                        topic_size[user_topic]).astype(np.int64)
    chosen = _home_items(rng, user_topic, n_home, item_topic, weights,
                         n_topics)
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    chosen = _background(rng, chosen, sizes, cdf, n_items)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(chosen // n_items, minlength=n), out=offsets[1:])
    return Population(items=(chosen % n_items).astype(np.int32),
                      offsets=offsets, n_items=n_items,
                      item_topic=item_topic, user_topic=user_topic)
