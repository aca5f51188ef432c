"""FastRandomHash (paper §II-D), JAX/numpy vectorized.

A *generative* hash function h_i maps item ids onto the bounded interval
[0, b). The FastRandomHash of a user is the minimum hash over her profile::

    H_i(u) = min_{item ∈ P_u} h_i(item)                      (paper Eq. 3)

The paper uses Jenkins' hash; any approximately-random h satisfies Theorems
1/2, so we use the murmur3 ``fmix32`` finalizer (4 vector ops on the VPU),
which vectorizes over both numpy and jnp uint32 arrays.

Splitting support: ``H\\η(u) = min_{item ∈ P_u, h(item) > η} h(item)`` is what
recursive splitting (§II-D) evaluates; we expose per-user *sorted distinct
hash values* so the split planner can walk down each user's candidate
sequence without rehashing (``core/splitting.py``). Two paths build that
table and share only the hash: :func:`user_distinct_hashes_device`, one
jitted program over a whole dataset (the build's cluster plan), and
:func:`user_distinct_hashes_np` for the small, variable-shape tables of
query routing and insert cohorts. ``PERF.md`` §3 names the host counters
that say which path each caller takes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.sched import trace

NO_HASH = np.int32(2**31 - 1)  # "H undefined" sentinel (empty masked min)


def fmix32(x):
    """Murmur3 finalizer. Works on numpy and jnp uint32 arrays (wrapping)."""
    is_np = isinstance(x, np.ndarray)
    u32 = (lambda v: np.uint32(v)) if is_np else (lambda v: jnp.uint32(v))
    x = x ^ (x >> u32(16))
    x = x * u32(0x85EB_CA6B)
    x = x ^ (x >> u32(13))
    x = x * u32(0xC2B2_AE35)
    x = x ^ (x >> u32(16))
    return x


def item_hashes(items, seeds, b: int):
    """h_i(item) for every (hash function i, item): int32[t, nnz] in [0, b).

    ``items``: int32[nnz]; ``seeds``: int32[t]. numpy in → numpy out,
    jnp in → jnp out (the device path is used by the fused Pallas kernel's
    reference, by distributed hashing and by :func:`distinct_hash_table`).
    """
    is_np = isinstance(items, np.ndarray)
    xp = np if is_np else jnp
    items_u = items.astype(xp.uint32)
    seeds_u = xp.asarray(seeds).astype(xp.uint32)
    # Distinct stream per hash function: mix(item ⊕ golden·(seed+1)).
    x = items_u[None, :] ^ ((seeds_u[:, None] + xp.uint32(1)) * xp.uint32(0x9E37_79B9))
    return (fmix32(x) % xp.uint32(b)).astype(xp.int32)


def user_min_hash_np(item_h: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """H_i(u) per (function, user): int32[t, n]. Host CSR segment-min."""
    t, _ = item_h.shape
    n = len(offsets) - 1
    out = np.full((t, n), NO_HASH, dtype=np.int32)
    nonempty = np.diff(offsets) > 0
    starts = offsets[:-1][nonempty]
    for i in range(t):
        mins = np.minimum.reduceat(item_h[i], starts)
        out[i, nonempty] = mins
    return out


def user_min_hash_jnp(item_h: jax.Array, user_of: jax.Array, n_users: int) -> jax.Array:
    """Device segment-min: item_h int32[t, nnz], user_of int32[nnz] → [t, n]."""
    return jax.vmap(
        lambda h: jax.ops.segment_min(h, user_of, num_segments=n_users)
    )(item_h).astype(jnp.int32)


def user_hash_above_np(item_h_row: np.ndarray, offsets: np.ndarray,
                       eta: int, user_ids: np.ndarray) -> np.ndarray:
    """H\\η for a subset of users under one hash function (host).

    Returns int32[len(user_ids)]; NO_HASH where no item hash exceeds η
    (the "single item" case of §II-D — those users remain in the cluster).
    """
    out = np.full(len(user_ids), NO_HASH, dtype=np.int32)
    for j, u in enumerate(user_ids):
        h = item_h_row[offsets[u]:offsets[u + 1]]
        h = h[h > eta]
        if len(h):
            out[j] = h.min()
    return out


def user_distinct_hashes_np(item_h: np.ndarray, offsets: np.ndarray,
                            depth: int) -> np.ndarray:
    """Per (function, user): the ``depth`` smallest *distinct* hash values,
    ascending, padded with NO_HASH — int32[t, n, depth].

    Recursive splitting only ever moves a user to its next distinct hash
    value above the current cluster index, so this table fully determines
    every split decision (``core/splitting.py``).

    Host path, for routing and insert cohorts (a whole dataset takes
    :func:`user_distinct_hashes_device`): ``depth`` passes of masked
    ``minimum.reduceat`` — O(depth·nnz) with no sort. The lexsort
    formulation kept below is the test oracle.
    """
    t, nnz = item_h.shape
    n = len(offsets) - 1
    trace.add("repro.hash.host_users", n)
    out = np.full((t, n, depth), NO_HASH, dtype=np.int32)
    sizes = np.diff(offsets)
    nonempty = sizes > 0
    starts = offsets[:-1][nonempty]
    user_of = np.repeat(np.arange(n, dtype=np.int64), sizes)
    for i in range(t):
        h = item_h[i].copy()
        for d in range(depth):
            mins = np.minimum.reduceat(h, starts)
            out[i, nonempty, d] = mins
            if d + 1 == depth:
                break
            # Mask out the level-d minimum everywhere it occurs, so the
            # next pass yields the next *distinct* value.
            cur = out[i][user_of, d]
            h[h == cur] = NO_HASH
            if (out[i, nonempty, d] == NO_HASH).all():
                break
    return out


def user_distinct_hashes_np_ref(item_h: np.ndarray, offsets: np.ndarray,
                                depth: int) -> np.ndarray:
    """Lexsort-based oracle for :func:`user_distinct_hashes_np` (tests)."""
    t, nnz = item_h.shape
    n = len(offsets) - 1
    out = np.full((t, n, depth), NO_HASH, dtype=np.int32)
    sizes = np.diff(offsets)
    user_of = np.repeat(np.arange(n, dtype=np.int64), sizes)
    for i in range(t):
        row = item_h[i]
        order = np.lexsort((row, user_of))
        uu, hh = user_of[order], row[order]
        keep = np.ones(nnz, dtype=bool)
        keep[1:] = (uu[1:] != uu[:-1]) | (hh[1:] != hh[:-1])
        du, dh = uu[keep], hh[keep]
        seg_start = np.zeros(len(du), dtype=np.int64)
        new_seg = np.ones(len(du), dtype=bool)
        new_seg[1:] = du[1:] != du[:-1]
        seg_idx = np.flatnonzero(new_seg)
        seg_start[seg_idx] = seg_idx
        seg_start = np.maximum.accumulate(seg_start)
        rank = np.arange(len(du)) - seg_start
        sel = rank < depth
        out[i, du[sel], rank[sel]] = dh[sel]
    return out


def packs(n_users: int, b: int) -> bool:
    """Whether ``user · (b + 1) + v`` fits int32 for every user and every
    ``v`` in ``[0, b]``: the device table's segmented maxima are then plain
    cumulative maxima of that one key. True for every Table I dataset at
    b = 4096 (ml20M: 5.7e8)."""
    return n_users * (b + 1) < 2**31


def _segment_max(low, user, n_users: int, b: int, reverse: bool):
    """Running max of ``low`` (int32[t, nnz], values in [0, b]) within each
    user's segment, forward or backward. ``user``: int32[nnz], the
    nondecreasing user id of each element."""
    if packs(n_users, b):
        # A later segment's key beats every value of an earlier one.
        key = ((n_users - 1 - user) if reverse else user) * (b + 1)
        return lax.cummax(key[None, :] + low, axis=1, reverse=reverse) - key
    # The scan's first element of each segment restarts the maximum.
    nxt = jnp.roll(user, 1 if not reverse else -1)
    restart = jnp.broadcast_to(user != nxt, low.shape)
    restart = restart.at[:, -1 if reverse else 0].set(True)

    def combine(a, c):
        return a[0] | c[0], jnp.where(c[0], c[1], jnp.maximum(a[1], c[1]))

    return lax.associative_scan(combine, (restart, low), reverse=reverse,
                                axis=1)[1]


@functools.partial(jax.jit, static_argnames=("b", "depth"))
def distinct_hash_table(items, offsets, seeds, *, b: int, depth: int):
    """Device program behind :func:`user_distinct_hashes_device`.

    ``items``: int32[nnz] (nnz ≥ 1); ``offsets``: int32[n + 1]; ``seeds``:
    int32[t] → int32[t, n, depth]. Works on ``low = b − h``, so a user's
    smallest remaining hash is its segment's largest ``low`` and a masked
    element reads 0. Each of the ``depth`` levels takes every user's
    segment maximum (forward and backward running maxima), writes it as
    the level's column, and masks every element equal to it: the numpy
    path's passes, with segmented scans in place of ``reduceat``.
    """
    n = offsets.shape[0] - 1
    nnz = items.shape[0]
    low0 = b - item_hashes(items, seeds, b)            # [t, nnz] in [1, b]
    user = jnp.cumsum(jnp.zeros(nnz, jnp.int32)
                      .at[offsets[1:-1]].add(1, mode="drop"))
    first = jnp.minimum(offsets[:-1], nnz - 1)
    nonempty = offsets[1:] > offsets[:-1]

    def level(d, carry):
        low, table = carry
        seg_max = jnp.maximum(_segment_max(low, user, n, b, False),
                              _segment_max(low, user, n, b, True))
        head = seg_max[:, first]
        col = jnp.where(nonempty & (head > 0), b - head, NO_HASH)
        table = lax.dynamic_update_index_in_dim(table, col, d, axis=2)
        return jnp.where(low == seg_max, 0, low), table

    table = jnp.full((seeds.shape[0], n, depth), NO_HASH, jnp.int32)
    return lax.fori_loop(0, depth, level, (low0, table))[1]


def user_distinct_hashes_device(items: np.ndarray, offsets: np.ndarray,
                                seeds: np.ndarray, b: int,
                                depth: int) -> np.ndarray:
    """:func:`user_distinct_hashes_np` of ``item_hashes(items, seeds, b)``,
    bit for bit, computed on the device: int32[t, n, depth] on the host.

    One upload of the CSR arrays, one jitted program (compiled once per
    dataset shape), one readback. For whole datasets: the build's cluster
    plan (``core/clustering.build_plan``).
    """
    n = len(offsets) - 1
    trace.add("repro.hash.device_users", n)
    if len(items) == 0:
        return np.full((len(seeds), n, depth), NO_HASH, dtype=np.int32)
    table = distinct_hash_table(
        jnp.asarray(items, jnp.int32), jnp.asarray(offsets, jnp.int32),
        jnp.asarray(seeds, jnp.int32), b=b, depth=depth)
    return np.asarray(table)
