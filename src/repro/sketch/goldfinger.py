"""GoldFinger compact profile fingerprints (paper §II-F, refs [19]/[40]).

GoldFinger summarizes each user's profile into a B-bit vector (64–8096 bits;
the paper's experiments use 1024). Bit ``hash(item) mod B`` is set for every
item in the profile. The Jaccard similarity of two profiles is then estimated
from the fingerprints as::

    J(u, v) ≈ |fp_u ∧ fp_v| / |fp_u ∨ fp_v|
            = popcount(fp_u & fp_v) / (card_u + card_v − popcount(fp_u & fp_v))

where ``card_u = popcount(fp_u)`` is precomputed once per user. Keeping the
union in terms of precomputed cardinalities is what lets the TPU kernel turn
the intersection into a single matmul (see kernels/goldfinger_knn).

The same layout holds the paper's raw-data mode (Table V): one bit per item
of the universe (:func:`incidence_fingerprint`), whose popcount Jaccard is
the exact set Jaccard. :func:`sketch_dataset` makes whichever of the two a
build's :class:`~repro.core.params.C2Params` asks for.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashing import fmix32
from repro.sched import trace
from repro.types import Dataset

DEFAULT_BITS = 1024

# Sketch kinds: hashed GoldFinger bits, or one bit per item (exact Jaccard).
GOLDFINGER = "goldfinger"
INCIDENCE = "incidence"
SKETCHES = (GOLDFINGER, INCIDENCE)


@dataclasses.dataclass(frozen=True)
class GoldFinger:
    """Fingerprints for a set of users: ``words`` uint32[n, W], ``card``
    int32[n]; ``sketch`` says how the bits were made (``SKETCHES``)."""

    words: np.ndarray | jax.Array  # uint32[n, W]
    card: np.ndarray | jax.Array   # int32[n]  (popcount of each row)
    sketch: str = GOLDFINGER

    @property
    def n(self) -> int:
        return self.words.shape[0]

    @property
    def n_bits(self) -> int:
        return self.words.shape[1] * 32

    def take(self, idx) -> "GoldFinger":
        return GoldFinger(words=self.words[idx], card=self.card[idx],
                          sketch=self.sketch)


def item_bit_positions(items: np.ndarray, n_bits: int, seed: int) -> np.ndarray:
    """Map item ids to bit positions in [0, n_bits) with a mixed hash."""
    x = (items.astype(np.uint32) + np.uint32(0x9E3779B9)) ^ np.uint32(seed * 0x85EBCA6B + 1)
    return (fmix32(x) % np.uint32(n_bits)).astype(np.int64)


def fingerprint_dataset(ds: Dataset, n_bits: int = DEFAULT_BITS, seed: int = 0) -> GoldFinger:
    """Build GoldFinger fingerprints for every user of ``ds`` (host-side)."""
    assert n_bits % 32 == 0, "n_bits must be a multiple of 32"
    with trace.span("repro.fingerprint"):
        W = n_bits // 32
        pos = item_bit_positions(ds.items, n_bits, seed)
        word_idx = (pos // 32).astype(np.int64)
        bit = np.uint32(1) << (pos % 32).astype(np.uint32)
        words = np.zeros((ds.n_users, W), dtype=np.uint32)
        # Scatter-OR each item's bit into its user's row.
        user_of = np.repeat(np.arange(ds.n_users, dtype=np.int64),
                            ds.profile_sizes)
        np.bitwise_or.at(words, (user_of, word_idx), bit)
        card = popcount_rows(words)
        trace.add("repro.fingerprint.words", W)
    return GoldFinger(words=words, card=card)


def popcount_rows(words: np.ndarray) -> np.ndarray:
    """Row-wise popcount on host (numpy)."""
    return np.unpackbits(words.view(np.uint8), axis=-1).sum(axis=-1).astype(np.int32)


def incidence_fingerprint(ds: Dataset, n_bits: int | None = None) -> GoldFinger:
    """Full-universe incidence rows ("raw data" mode, Table V).

    Item ``i`` sets bit ``i``: popcount Jaccard over these rows is the
    *exact* set Jaccard (no hash collisions), at |I|/n_bits times the
    memory and compute of a GoldFinger sketch. ``n_bits`` is the row width,
    by default the universe rounded up to whole words; a query of an index
    passes the index's. An item at or past ``n_bits`` sets no bit but still
    counts, once, in ``card``, so a query's sims against the index's rows
    stay the exact Jaccard; an incidence index refuses to store such a row.
    """
    with trace.span("repro.fingerprint"):
        if n_bits is None:
            n_bits = 32 * ((ds.n_items + 31) // 32)
        assert n_bits % 32 == 0, "n_bits must be a multiple of 32"
        W = n_bits // 32
        words = np.zeros((ds.n_users, W), dtype=np.uint32)
        user_of = np.repeat(np.arange(ds.n_users, dtype=np.int64),
                            ds.profile_sizes)
        pos = ds.items.astype(np.int64)
        inside = pos < n_bits
        np.bitwise_or.at(words, (user_of[inside], pos[inside] // 32),
                         np.uint32(1) << (pos[inside] % 32).astype(np.uint32))
        card = popcount_rows(words)
        if not inside.all():
            past = np.unique(np.stack([user_of[~inside], pos[~inside]]),
                             axis=1)
            card += np.bincount(past[0], minlength=ds.n_users).astype(
                np.int32)
        trace.add("repro.fingerprint.words", W)
    return GoldFinger(words=words, card=card, sketch=INCIDENCE)


def sketch_dataset(ds: Dataset, params) -> GoldFinger:
    """The rows a build scores, as ``params`` (a ``C2Params``) asks:
    GoldFinger at ``params.n_bits`` bits and ``params.seed``, or, with
    ``params.use_goldfinger`` False, incidence rows of the universe."""
    if params.use_goldfinger:
        return fingerprint_dataset(ds, n_bits=params.n_bits, seed=params.seed)
    return incidence_fingerprint(ds)


# --------------------------------------------------------------------------
# Pure-jnp pairwise similarity (also the oracle for the Pallas kernel).
# --------------------------------------------------------------------------

def jaccard_pairwise(words_a: jax.Array, card_a: jax.Array,
                     words_b: jax.Array, card_b: jax.Array,
                     word_chunk: int = 64) -> jax.Array:
    """Estimated Jaccard sims for all pairs: float32[n_a, n_b].

    Pure-jnp reference: popcount of ANDed words, union from cardinalities.
    Wide sketches (raw-incidence mode: W = |I|/32 can be thousands of
    words) are scanned in word chunks so the [n_a, n_b, W] AND tensor is
    never materialized.
    """
    W = words_a.shape[-1]
    if W <= word_chunk:
        inter = jnp.sum(
            jax.lax.population_count(
                words_a[:, None, :] & words_b[None, :, :]),
            axis=-1,
        ).astype(jnp.float32)
    else:
        pad = (-W) % word_chunk
        wa = jnp.pad(words_a, ((0, 0), (0, pad)))
        wb = jnp.pad(words_b, ((0, 0), (0, pad)))
        nc = wa.shape[-1] // word_chunk
        wa = jnp.moveaxis(wa.reshape(-1, nc, word_chunk), 1, 0)
        wb = jnp.moveaxis(wb.reshape(-1, nc, word_chunk), 1, 0)

        def body(acc, ab):
            a, b = ab
            p = jnp.sum(jax.lax.population_count(
                a[:, None, :] & b[None, :, :]), axis=-1, dtype=jnp.int32)
            return acc + p, None

        acc0 = jnp.zeros((words_a.shape[0], words_b.shape[0]), jnp.int32)
        inter, _ = jax.lax.scan(body, acc0, (wa, wb))
        inter = inter.astype(jnp.float32)
    union = card_a[:, None].astype(jnp.float32) + card_b[None, :].astype(jnp.float32) - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1.0), 0.0)


def unpack_bits_int8(words: jax.Array) -> jax.Array:
    """uint32[n, W] → int8[n, W·32] {0,1} bit planes (LSB-first per word).

    This is the MXU path: ``popcount(a & b) == unpack(a) @ unpack(b).T``,
    turning bit intersection into an int8 matmul. ``_group_knn``
    (core/local_knn.py) takes it for incidence rows; PERF.md §3 lists the
    metrics that read it.
    """
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(words.shape[0], -1).astype(jnp.int8)


@jax.jit
def jaccard_pairwise_mxu(words_a, card_a, words_b, card_b):
    """MXU-friendly variant of :func:`jaccard_pairwise` (bit-plane matmul)."""
    ba = unpack_bits_int8(words_a)
    bb = unpack_bits_int8(words_b)
    inter = jax.lax.dot_general(
        ba, bb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    union = card_a[:, None].astype(jnp.float32) + card_b[None, :].astype(jnp.float32) - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1.0), 0.0)


# Sketches at least this many uint32 words wide score through the MXU
# bit-plane matmul instead of the VPU popcount loop. 64 words = 2048 bits
# is where jaccard_pairwise starts chunk-scanning the AND tensor — beyond
# it the raw-incidence layouts (W = |I|/32, thousands of words) amortize
# the 8× unpack blow-up against the systolic array's throughput.
MXU_MIN_WORDS = 64


def jaccard_pairwise_auto(words_a, card_a, words_b, card_b):
    """Width-dispatched estimator: popcount for narrow sketches, bit-plane
    MXU matmul for wide (raw-incidence) ones.

    Results are bitwise identical either way — the intersection is an
    exact integer in both layouts and the f32 epilogue is the same ops in
    the same order — so callers (descent scoring, ``_group_knn``) switch
    purely on the compute layout.
    """
    if words_a.shape[-1] >= MXU_MIN_WORDS:
        return jaccard_pairwise_mxu(words_a, card_a, words_b, card_b)
    return jaccard_pairwise(words_a, card_a, words_b, card_b)
