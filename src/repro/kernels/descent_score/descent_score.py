"""Fused descent-hop (gather → suppress → score → merge) — Pallas TPU kernel.

Serving's hot loop (``query/search.descent_step``) was an unfused chain:
two adjacency gathers materialize a ``[q, beam·(kg+kr)]`` candidate
tensor in HBM, the GoldFinger estimator scores *every* lane, a
double-argsort ``dedup_mask`` then throws most of those scores away, and
a wide ``lax.top_k`` re-sorts the lot. The friend-of-a-friend expansion
is heavily duplicated — most popcounts re-score candidates already in
the beam ("A Note on Graph-Based Nearest Neighbor Search": distance
evaluations on revisited candidates dominate graph-search cost). This
kernel does one hop per query-tile entirely in VMEM:

* **Gather (a):** forward + reverse neighbor ids of the current beam —
  ids only (``[bq, beam·(kg+kr)]`` int32); fingerprints are fetched per
  score chunk, so the full candidate-fingerprint tensor never exists.
* **Suppress before scoring (b):** PAD lanes, lanes under PAD beam rows,
  and lanes already in the beam are retired in-tile *before* the
  estimator runs. Suppressed lanes have their gather index zeroed (no
  stray HBM row touch) and are excluded from the scored-lane count the
  kernel reports (``n_scored``), which quantifies the dedup win per hop
  against the unfused ``beam·(kg+kr)``.
* **Score (c):** GoldFinger AND-popcount on the VPU in candidate chunks;
  for wide sketches (raw-incidence mode) an int8 bit-plane variant
  (``unpack_bits_int8``) turns the intersection into an MXU
  ``dot_general`` — tile-dense: the chunk's candidates score against the
  whole query tile in one matmul and the matching diagonal is kept
  (redundant flops on the systolic array beat per-lane popcount loops
  once W is thousands of words).
* **Merge (d):** in-register top-``beam`` via
  :func:`repro.knn.topk.select_topk` with winner-id retirement over
  ``[beam | fwd | rev]`` in the reference column order. Retiring every
  lane of a round's winning id also resolves duplicates *between*
  candidate lanes exactly like ``dedup_mask`` + ``lax.top_k`` would:
  duplicate lanes of an id carry identical sims, so the selected column
  is always the id's first occurrence.

Results are bitwise identical to ``ref.descent_hop_ref`` (the historical
jnp path): same ids, same sims, same tie-breaks — asserted across PAD
patterns and beam widths by ``tests/test_descent_kernel.py``. One
precondition, which every real beam satisfies by construction (beams are
``merge_topk``/``select_topk`` outputs): a beam row never repeats an id.
A repeated beam id at two different sims would be ranked at its *first*
lane by the reference's dedup and at its *max* lane here.

Two memory placements:

* :func:`hop_pallas` — index arrays ride in whole as VMEM operands
  (index_map pins block 0) and rows are gathered with whole-table
  takes. Interpret mode only: Mosaic lowers no such gather, and real
  tables do not fit VMEM — compiled, it raises before tracing.
* :func:`hop_pallas_dma` — the compiled layout. Steps (a) and (b) run
  as XLA ops ahead of the kernel (ids only: the ``[q, beam·(kg+kr)]``
  int32 lane-id array, never the candidate fingerprints), because
  Mosaic lowers neither arbitrary-row takes nor DMAs of rows narrower
  than its 128-lane tile. Each index row's fingerprint and cardinality
  are packed into one 128-lane-aligned uint32 row
  (:func:`dma_row_words`) that stays in HBM; the kernel reads the
  surviving lane ids as scalars from SMEM and fetches their rows per
  score chunk by double-buffered async-copy DMA — copy-in of chunk c+1
  overlaps scoring of chunk c — so suppressed lanes move no bytes. It
  emits per-query ``dma_bytes`` / ``bytes_saved`` (packed-row bytes;
  ``dma_bytes == n_scored·4·dma_row_words(W)`` is test-enforced).

Both are bitwise-identical to each other and to the reference: they
share the suppression rule, the chunked estimator
(:func:`repro.kernels.scoring.score_gathered_chunk`) and the merge.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.descent_score.ref import hop_candidates
from repro.kernels.scoring import score_gathered_chunk
from repro.knn.topk import select_topk
from repro.sketch.goldfinger import unpack_bits_int8
from repro.types import NEG_INF, PAD_ID

# Default scoped-VMEM limit of the Mosaic compiler on TPU v5e.
_VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def _not_in_beam(cand, beam_ids):
    """(b) Lanes worth scoring: not PAD and not already in the beam
    (merge would retire them as duplicates of columns 0..B-1 — scoring
    them first is the waste the fused hop removes). Tombstoned lanes are
    PAD by now (``hop_candidates``), so they retire here too and stay
    out of n_scored, which is how tests observe the suppression."""
    return (cand != PAD_ID) & ~jnp.any(
        cand[:, :, None] == beam_ids[:, None, :], axis=-1)


def _merge(beam_ids, beam_sims, cand, cand_sims, out_ids_ref, out_sims_ref):
    """(d) in-register merge over [beam | fwd | rev] — the reference
    column order, so tie-breaks land exactly where lax.top_k puts them."""
    B = beam_ids.shape[1]
    top_sims, top_ids = select_topk(
        jnp.concatenate([beam_sims, cand_sims], axis=1),
        jnp.concatenate([beam_ids, cand], axis=1),
        B, dedup_ids=True)
    out_ids_ref[...] = jnp.where(top_sims == NEG_INF, PAD_ID, top_ids)
    out_sims_ref[...] = top_sims


def _hop_kernel(graph_ref, rev_ref, words_ref, card_ref, tomb_ref,
                qw_ref, qc_ref, bi_ref, bs_ref,
                out_ids_ref, out_sims_ref, nsc_ref,
                *, chunk: int, mxu: bool):
    # (a) adjacency gather — candidate *ids* only — with dead beam and
    # candidate lanes retired, exactly as the reference does it.
    beam_ids, beam_sims, cand = hop_candidates(
        graph_ref[...], rev_ref[...], bi_ref[...], bs_ref[...],
        tomb_ref[...][:, 0] > 0)                        # cand [bq, C]
    bq, C = cand.shape
    need = _not_in_beam(cand, beam_ids)
    nsc_ref[...] = jnp.sum(need, axis=1, dtype=jnp.int32).reshape(bq, 1)

    # (c) score surviving lanes, in chunks — the gathered fingerprint
    # block is [bq, chunk, W], never [bq, C, W].
    qw = qw_ref[...]                                    # [bq, W] u32
    qcf = qc_ref[...].astype(jnp.float32)               # [bq, 1]
    words = words_ref[...]
    card = card_ref[...]                                # [n, 1] i32
    q_bits = unpack_bits_int8(qw) if mxu else None      # [bq, W·32] i8
    sims_chunks = []
    for s in range(0, C, chunk):
        ids_c = cand[:, s:s + chunk]
        need_c = need[:, s:s + chunk]
        ch = ids_c.shape[1]
        safe = jnp.where(need_c, ids_c, 0).reshape(-1)
        cw = jnp.take(words, safe, axis=0).reshape(bq, ch, -1)
        cc = jnp.where(need_c,
                       jnp.take(card, safe, axis=0).reshape(bq, ch),
                       0).astype(jnp.float32)
        sims_chunks.append(
            score_gathered_chunk(qw, qcf, q_bits, cw, cc, need_c, mxu=mxu))
    cand_sims = jnp.concatenate(sims_chunks, axis=1)

    _merge(beam_ids, beam_sims, cand, cand_sims, out_ids_ref, out_sims_ref)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "chunk", "mxu", "interpret"),
)
def hop_pallas(graph_ids, rev_ids, words, card, tomb, q_words, q_card,
               beam_ids, beam_sims, *,
               block_q: int = 64, chunk: int = 256,
               mxu: bool = False, interpret: bool = True):
    """One fused descent hop for a wave of queries (see ref.descent_hop_ref).

    graph_ids i32[n, kg], rev_ids i32[n, kr]; words u32[n, W],
    card i32[n, 1]; tomb i32[n, 1] (1 = tombstoned row — all-zeros for a
    delete-free index); q_words u32[q, W], q_card i32[q, 1];
    beam_ids i32[q, B], beam_sims f32[q, B]. q % block_q == 0 (ops.py
    pads). Returns (beam_ids i32[q, B], beam_sims f32[q, B],
    n_scored i32[q, 1]) — the beam after the hop plus the per-query count
    of candidate lanes that survived suppression (PAD / in-beam /
    tombstoned all retire first) and were scored.
    """
    q, B = beam_ids.shape
    n, W = words.shape
    kg, kr = graph_ids.shape[1], rev_ids.shape[1]
    if not interpret:
        table_bytes = sum(n * 4 * -(-w // 128) * 128
                          for w in (kg, kr, W, 1, 1))
        raise NotImplementedError(
            f"hop_pallas runs in interpret mode only: it holds the index "
            f"tables whole in VMEM ({table_bytes:,} bytes at n={n}, "
            f"against the {_VMEM_LIMIT_BYTES:,}-byte scoped VMEM limit) "
            f"and gathers their rows with whole-table takes, which Mosaic "
            f"does not lower. Compiled serving uses hop_pallas_dma "
            f"(QueryConfig(kernel=True, dma=True)).")
    bq = min(block_q, q)
    assert q % bq == 0, (q, bq)
    grid = (q // bq,)

    out_ids, out_sims, n_scored = pl.pallas_call(
        functools.partial(_hop_kernel, chunk=chunk, mxu=mxu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, kg), lambda i: (0, 0)),
            pl.BlockSpec((n, kr), lambda i: (0, 0)),
            pl.BlockSpec((n, W), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((bq, W), lambda i: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
            pl.BlockSpec((bq, B), lambda i: (i, 0)),
            pl.BlockSpec((bq, B), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, B), lambda i: (i, 0)),
            pl.BlockSpec((bq, B), lambda i: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, B), jnp.int32),
            jax.ShapeDtypeStruct((q, B), jnp.float32),
            jax.ShapeDtypeStruct((q, 1), jnp.int32),
        ],
        interpret=interpret,
    )(graph_ids, rev_ids, words, card, tomb, q_words, q_card,
      beam_ids, beam_sims)
    return out_ids, out_sims, n_scored


def dma_row_words(W: int) -> int:
    """uint32 lanes of one packed DMA row: the W fingerprint words, then
    the cardinality, zero-padded to whole 128-lane tiles — Mosaic only
    DMAs rows that fill its lane tiling."""
    return -(-(W + 1) // 128) * 128


def _hop_kernel_dma(rows_hbm, qw_ref, qc_ref, bi_ref, bs_ref, ids_ref,
                    ids_smem,
                    out_ids_ref, out_sims_ref, nsc_ref, dmab_ref, save_ref,
                    buf, sem, *, W: int, chunk: int, mxu: bool,
                    n_buffers: int):
    """HBM-resident scoring + merge over pre-suppressed candidate lanes.

    ``ids_ref``/``ids_smem`` are the same i32[bq, C] block of lane ids
    (PAD on every suppressed lane) in VMEM and in SMEM: the vector side
    builds the scoring mask from it, the per-lane loops read it as
    scalars to guard each row's DMA. Each chunk's surviving rows are
    DMA'd from ``rows_hbm`` (packed u32[n, R] rows, :func:`dma_row_words`)
    into a rotating ``n_buffers``-deep VMEM buffer — chunk c+1's copies
    are in flight while chunk c scores. The start and wait loops rebuild
    identical descriptors under the identical guard, so every started
    copy is waited exactly once; per-slot semaphores keep chunk c+1's
    signals from satisfying chunk c's waits. Skipped buffer lanes keep
    whatever bytes a previous chunk left — harmless, the scorer masks
    them by ``need``.
    """
    beam_ids = bi_ref[...]                              # [bq, B] i32
    beam_sims = bs_ref[...]                             # [bq, B] f32
    ids = ids_ref[...]                                  # [bq, C] i32
    bq, C = ids.shape
    R = rows_hbm.shape[1]
    need = ids != PAD_ID
    n_scored = jnp.sum(need, axis=1, dtype=jnp.int32).reshape(bq, 1)
    nsc_ref[...] = n_scored
    # The DMA guard reads the same lane ids the mask is built from, so
    # rows moved == lanes scored, exactly.
    dmab_ref[...] = n_scored * (R * 4)
    save_ref[...] = (C - n_scored) * (R * 4)

    qw = qw_ref[...]                                    # [bq, R] u32
    qcf = qc_ref[...].astype(jnp.float32)               # [bq, 1]
    q_bits = unpack_bits_int8(qw) if mxu else None
    n_chunks = -(-C // chunk)

    def _lane_copy(t, s, ch, slot):
        i = t // ch
        j = t % ch
        v = ids_smem[i, s + j]
        ok = v != PAD_ID
        row = jnp.where(ok, v, 0)
        return ok, pltpu.make_async_copy(rows_hbm.at[row],
                                         buf.at[slot, i, j], sem.at[slot])

    def start_chunk(ci, slot):
        s = ci * chunk
        ch = min(chunk, C - s)

        def body(t, carry):
            ok, cp = _lane_copy(t, s, ch, slot)

            @pl.when(ok)
            def _():
                cp.start()
            return carry

        jax.lax.fori_loop(0, bq * ch, body, 0)

    def wait_chunk(ci, slot):
        s = ci * chunk
        ch = min(chunk, C - s)

        def body(t, carry):
            ok, cp = _lane_copy(t, s, ch, slot)

            @pl.when(ok)
            def _():
                cp.wait()
            return carry

        jax.lax.fori_loop(0, bq * ch, body, 0)

    def score_chunk(ci, slot):
        s = ci * chunk
        ch = min(chunk, C - s)
        need_c = need[:, s:s + ch]
        cw = buf[slot, :, :ch, :]                       # [bq, ch, R] u32
        lane = jax.lax.broadcasted_iota(jnp.int32, cw.shape, 2)
        card = jnp.sum(jnp.where(
            lane == W, jax.lax.bitcast_convert_type(cw, jnp.int32), 0),
            axis=-1)
        cc = jnp.where(need_c, card, 0).astype(jnp.float32)
        # The query's lanes past W are zero, so the packed cardinality
        # and padding lanes add nothing to the intersection.
        return score_gathered_chunk(qw, qcf, q_bits, cw, cc, need_c,
                                    mxu=mxu)

    sims_chunks = []
    if n_buffers > 1:
        start_chunk(0, 0)
        for ci in range(n_chunks):
            if ci + 1 < n_chunks:
                start_chunk(ci + 1, (ci + 1) % n_buffers)
            wait_chunk(ci, ci % n_buffers)
            sims_chunks.append(score_chunk(ci, ci % n_buffers))
    else:
        # n_buffers == 1: no overlap — a degenerate tuning point kept
        # for the autotuner's smallest-VMEM configurations.
        for ci in range(n_chunks):
            start_chunk(ci, 0)
            wait_chunk(ci, 0)
            sims_chunks.append(score_chunk(ci, 0))
    cand_sims = jnp.concatenate(sims_chunks, axis=1)

    # Suppressed lanes carry PAD ids here, not their original ids: they
    # score −inf, and an id on a −inf lane never reaches the output.
    _merge(beam_ids, beam_sims, ids, cand_sims, out_ids_ref, out_sims_ref)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "chunk", "mxu", "n_buffers", "interpret"),
)
def hop_pallas_dma(graph_ids, rev_ids, words, card, tomb, q_words, q_card,
                   beam_ids, beam_sims, *,
                   block_q: int = 16, chunk: int = 128,
                   mxu: bool = False, n_buffers: int = 2,
                   interpret: bool = True):
    """Memory-hierarchy-aware fused hop: HBM rows, per-chunk DMA.

    Same contract as :func:`hop_pallas` (and bitwise-identical to it and
    to ``ref.descent_hop_ref``), plus two extra outputs:
    ``dma_bytes i32[q, 1]`` — packed-row bytes actually DMA'd for this
    hop per query — and ``bytes_saved i32[q, 1]`` — the bytes the
    suppressed lanes did *not* move out of the ``beam·(kg+kr)`` lanes.
    ``(block_q, chunk, n_buffers)`` come from ``tune.hop_params`` via
    ops.py; VMEM scratch is ``n_buffers·block_q·chunk·R·4`` bytes for
    the rotating row buffers, ``R = dma_row_words(W)``.
    """
    q, B = beam_ids.shape
    n, W = words.shape
    kg, kr = graph_ids.shape[1], rev_ids.shape[1]
    C = B * (kg + kr)
    R = dma_row_words(W)
    bq = min(block_q, q)
    assert q % bq == 0, (q, bq)
    nb = max(1, min(n_buffers, -(-C // chunk)))
    ch = min(chunk, C)

    # (a)+(b) as XLA ops: gather lane ids, retire tombstoned, PAD and
    # in-beam lanes. Dead beam lanes leave the beam here too.
    beam_ids, beam_sims, cand = hop_candidates(
        graph_ids, rev_ids, beam_ids, beam_sims, tomb[:, 0] > 0)
    lane_ids = jnp.where(_not_in_beam(cand, beam_ids), cand, PAD_ID)
    rows = jnp.concatenate(
        [words, card.astype(jnp.uint32),
         jnp.zeros((n, R - W - 1), jnp.uint32)], axis=1)
    qw = jnp.pad(q_words, ((0, 0), (0, R - W)))

    return pl.pallas_call(
        functools.partial(_hop_kernel_dma, W=W, chunk=chunk, mxu=mxu,
                          n_buffers=nb),
        grid=(q // bq,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),          # packed rows
            pl.BlockSpec((bq, R), lambda i: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
            pl.BlockSpec((bq, B), lambda i: (i, 0)),
            pl.BlockSpec((bq, B), lambda i: (i, 0)),
            pl.BlockSpec((bq, C), lambda i: (i, 0)),
            pl.BlockSpec((bq, C), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bq, B), lambda i: (i, 0)),
            pl.BlockSpec((bq, B), lambda i: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, B), jnp.int32),
            jax.ShapeDtypeStruct((q, B), jnp.float32),
            jax.ShapeDtypeStruct((q, 1), jnp.int32),
            jax.ShapeDtypeStruct((q, 1), jnp.int32),
            jax.ShapeDtypeStruct((q, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, bq, ch, R), jnp.uint32),    # row buffers
            pltpu.SemaphoreType.DMA((nb,)),
        ],
        interpret=interpret,
    )(rows, qw, q_card, beam_ids, beam_sims, lane_ids, lane_ids)
