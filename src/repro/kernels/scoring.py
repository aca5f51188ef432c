"""Shared bounded-VMEM GoldFinger scoring tiles for the Pallas kernels.

Every kernel that estimates Jaccard similarities — the descent hop's
gathered-lane scoring (VMEM and DMA variants) and the build-time
``goldfinger_knn`` all-pairs sweep — runs the same estimator:

    inter = popcount(fp_u & fp_v)            (exact integer, two layouts)
    union = card_u + card_v - inter
    sim   = inter / max(union, 1)  if union > 0 else 0

These helpers are the *single* implementation of that chunk-shaped
epilogue, so the kernels stay bitwise-interchangeable with each other and
with ``sketch.goldfinger.jaccard_pairwise_auto``: the intersection is an
exact int32 either way (VPU popcount or int8 bit-plane MXU matmul) and
the f32 epilogue is the same ops in the same order. Both helpers score a
bounded tile — ``[bq, chunk]`` lanes or ``[bq, bd_chunk]`` pairs — so no
caller ever materializes an ``[n, n]``-scale interaction tensor in VMEM;
chunking a scoring loop over either helper is bitwise-invisible because
each output element depends only on its own (query, candidate) pair.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sketch.goldfinger import unpack_bits_int8
from repro.types import NEG_INF


def score_gathered_chunk(qw, qcf, q_bits, cw, ccf, need_c, *, mxu: bool):
    """Score one chunk of per-lane gathered candidate fingerprints.

    qw u32[bq, W] query fingerprints; qcf f32[bq, 1] query cardinalities;
    q_bits int8[bq, W·32] pre-unpacked bit planes (only read when
    ``mxu``); cw u32[bq, ch, W] gathered candidate rows;
    ccf f32[bq, ch] candidate cardinalities (0 on suppressed lanes);
    need_c bool[bq, ch] surviving-lane mask. Returns f32[bq, ch] sims
    with ``NEG_INF`` on suppressed lanes. Suppressed lanes may hold
    arbitrary garbage in ``cw``/``ccf`` — each lane's score depends only
    on its own row (the MXU path keeps the per-row diagonal), so garbage
    never leaks into surviving lanes, and the final ``where`` retires it.
    """
    bq, ch = need_c.shape
    W = qw.shape[1]
    if mxu:
        # Tile-dense bit-plane matmul: chunk candidates × ALL tile
        # queries on the MXU, keep the per-row diagonal.
        c_bits = unpack_bits_int8(cw.reshape(bq * ch, W))  # [bq·ch, W·32]
        inter3 = jax.lax.dot_general(
            c_bits, q_bits, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).reshape(bq, ch, bq)
        own = jax.lax.broadcasted_iota(jnp.int32, (bq, ch, bq), 0)
        qid = jax.lax.broadcasted_iota(jnp.int32, (bq, ch, bq), 2)
        inter = jnp.sum(jnp.where(own == qid, inter3, 0),
                        axis=-1).astype(jnp.float32)
    else:
        # Per-word counts are <= 32, so the int32 sum is exact (Mosaic
        # has no unsigned reduction).
        inter = jnp.sum(
            jax.lax.population_count(qw[:, None, :] & cw)
            .astype(jnp.int32), axis=-1).astype(jnp.float32)  # [bq, ch]
    union = qcf + ccf - inter
    s_c = jnp.where(union > 0, inter / jnp.maximum(union, 1.0), 0.0)
    return jnp.where(need_c, s_c, NEG_INF)


def jaccard_bitplane_tile(q_bits, q_card_col, d_bits, d_card_row):
    """Dense Jaccard tile from pre-unpacked bit planes (build-time sweep).

    q_bits int8[bq, B] {0,1}; q_card_col f32[bq, 1];
    d_bits int8[ch, B]; d_card_row f32[1, ch]. Returns f32[bq, ch].
    ``ch`` is a *chunk* of the database block — callers loop chunks so
    the interaction tile stays bounded instead of one [bq, bd] matmul.
    """
    inter = jax.lax.dot_general(
        q_bits, d_bits, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)                               # [bq, ch]
    union = q_card_col + d_card_row - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1.0), 0.0)
