"""Public wrappers for the goldfinger_knn kernel.

Handles bit-plane unpacking, padding to block multiples, and the batched
per-cluster entry point used by core/local_knn. Interpret-vs-compiled is
resolved per call through ``repro.kernels.config`` (interpreted on the
CPU backend, compiled on TPU); the flag is a static arg of the inner
jit, so flipping it re-traces instead of reusing a stale cache entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import config
from repro.kernels.goldfinger_knn.goldfinger_knn import knn_pallas
from repro.sketch.goldfinger import unpack_bits_int8
from repro.types import NEG_INF, PAD_ID


def _pad_rows(x, to: int, fill):
    n = x.shape[0]
    if n % to == 0:
        return x
    pad = to - n % to
    return jnp.concatenate(
        [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_d",
                                    "score_chunk", "interpret"))
def _knn_jit(q_words, q_card, q_ids, d_words, d_card, d_ids, *, k: int,
             block_q: int, block_d: int, score_chunk: int,
             interpret: bool):
    nq = q_words.shape[0]
    q_bits = _pad_rows(unpack_bits_int8(q_words), block_q, 0)
    d_bits = _pad_rows(unpack_bits_int8(d_words), block_d, 0)
    qc = _pad_rows(q_card.reshape(-1, 1).astype(jnp.int32), block_q, 0)
    qi = _pad_rows(q_ids.reshape(-1, 1).astype(jnp.int32), block_q, PAD_ID)
    dc = _pad_rows(d_card.reshape(-1, 1).astype(jnp.int32), block_d, 0)
    di = _pad_rows(d_ids.reshape(-1, 1).astype(jnp.int32), block_d, PAD_ID)
    out_ids, out_sims = knn_pallas(
        q_bits, qc, qi, d_bits, dc, di, k,
        block_q=block_q, block_d=block_d, score_chunk=score_chunk,
        interpret=interpret)
    return out_ids[:nq], out_sims[:nq]


def knn(q_words, q_card, q_ids, d_words, d_card, d_ids, k: int,
        block_q: int = 128, block_d: int = 512, score_chunk: int = 128):
    """Top-k neighbors of each query among the database rows.

    Same contract as ref.knn_ref but words are packed uint32[n, W];
    unpacking to MXU bit-planes happens here (fused by jit).
    ``score_chunk`` bounds the per-round interaction tile at
    [block_q, score_chunk] — the same bounded-VMEM scoring-loop shape as
    the descent hop — and is bitwise-invisible (streaming chunk merges
    equal one block-wide merge).
    """
    return _knn_jit(jnp.asarray(q_words), jnp.asarray(q_card),
                    jnp.asarray(q_ids), jnp.asarray(d_words),
                    jnp.asarray(d_card), jnp.asarray(d_ids), k=k,
                    block_q=block_q, block_d=block_d,
                    score_chunk=score_chunk,
                    interpret=config.interpret_mode())


@functools.partial(jax.jit, static_argnames=("k",))
def cluster_knn(words, card, member_ids, k: int):
    """Batched per-cluster KNN: words uint32[m, cap, W] → ([m, cap, k] ×2).

    Matches core/local_knn._group_knn's contract: PAD rows yield PAD/−inf.
    Caps are powers of two ≥ 32, so blocks divide evenly.
    """
    m, cap, _ = words.shape
    bq = min(128, cap)
    bd = min(512, cap)

    def one(w, c, ids):
        oi, os = knn(w, c, ids, w, c, ids, k, block_q=bq, block_d=bd)
        # Dead (PAD) query rows: normalize sims to −inf for the caller.
        dead = (ids == PAD_ID)[:, None]
        return (jnp.where(dead, PAD_ID, oi),
                jnp.where(dead, NEG_INF, os))

    return jax.vmap(one)(words, card, member_ids)
