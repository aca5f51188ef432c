"""Public wrappers for the descent_score kernel.

Handles query-row padding to block multiples, card reshaping to the
kernel's 2-D layout, the popcount-vs-MXU layout choice by sketch width,
and the VMEM-vs-DMA placement choice (``dma=``). Launch parameters are
resolved at plain-Python level — interpret mode through
``repro.kernels.config`` (chosen by the backend), DMA tile shapes
through the shape-keyed ``tune`` cache — then handed to an inner jit as
static arguments. ``descent_hop`` itself is *not* jitted: it runs at
trace time of whatever jitted program calls it (wave scan, slot hop,
sharded vmap), so the resolution happens once per outer trace and the
tuner memo keeps repeated shapes from ever re-tracing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import config
from repro.kernels.descent_score import tune
from repro.kernels.descent_score.descent_score import (hop_pallas,
                                                       hop_pallas_dma)
from repro.sketch.goldfinger import MXU_MIN_WORDS
from repro.types import NEG_INF, PAD_ID


def _pad_rows(x, to: int, fill):
    n = x.shape[0]
    if n % to == 0:
        return x
    pad = to - n % to
    return jnp.concatenate(
        [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "chunk", "mxu", "dma",
                                    "n_buffers", "with_counts",
                                    "interpret"))
def _hop_jit(graph_ids, rev_ids, words, card, t2d, q_words, q_card,
             beam_ids, beam_sims, *, block_q: int, chunk: int, mxu: bool,
             dma: bool, n_buffers: int, with_counts: bool,
             interpret: bool):
    q = beam_ids.shape[0]
    qw = _pad_rows(jnp.asarray(q_words), block_q, 0)
    qc = _pad_rows(jnp.asarray(q_card).reshape(-1, 1).astype(jnp.int32),
                   block_q, 0)
    bi = _pad_rows(beam_ids, block_q, PAD_ID)
    bs = _pad_rows(beam_sims, block_q, NEG_INF)
    tables = (jnp.asarray(graph_ids), jnp.asarray(rev_ids),
              jnp.asarray(words),
              jnp.asarray(card).reshape(-1, 1).astype(jnp.int32), t2d)
    if dma:
        out_ids, out_sims, n_scored, dma_bytes, bytes_saved = hop_pallas_dma(
            *tables, qw, qc, bi, bs,
            block_q=block_q, chunk=chunk, mxu=mxu, n_buffers=n_buffers,
            interpret=interpret)
    else:
        out_ids, out_sims, n_scored = hop_pallas(
            *tables, qw, qc, bi, bs,
            block_q=block_q, chunk=chunk, mxu=mxu, interpret=interpret)
        # The VMEM placement moves whole tables as operands — no per-row
        # DMA happens, so the byte counters are identically zero.
        dma_bytes = jnp.zeros_like(n_scored)
        bytes_saved = jnp.zeros_like(n_scored)
    if with_counts:
        return (out_ids[:q], out_sims[:q], n_scored[:q, 0],
                dma_bytes[:q, 0], bytes_saved[:q, 0])
    return out_ids[:q], out_sims[:q]


def descent_hop(graph_ids, rev_ids, words, card, q_words, q_card,
                beam_ids, beam_sims, *, block_q: int | None = None,
                mxu: bool | None = None, with_counts: bool = False,
                tomb=None, dma: bool = False,
                score_chunk: int | None = None,
                n_buffers: int | None = None):
    """One fused descent hop; same contract as ref.descent_hop_ref.

    Padded query rows (PAD beams) produce PAD/−inf rows and score
    nothing; they are sliced off before returning. ``tomb`` (bool[n] or
    None) marks tombstoned index rows: their lanes retire with the
    PAD/in-beam suppression, before the estimator — None synthesizes an
    all-live mask, which is bitwise a no-op.

    ``dma=True`` selects the HBM-resident placement
    (:func:`~.descent_score.hop_pallas_dma`): tables stay in ANY/HBM
    memory and only surviving lanes' fingerprint rows are DMA'd, with
    ``(block_q, score_chunk, n_buffers)`` resolved per index shape by
    ``tune.hop_params`` unless overridden. Results are bitwise-identical
    to the VMEM placement and the jnp reference either way.

    With ``with_counts`` returns a 5-tuple ``(ids, sims, n_scored,
    dma_bytes, bytes_saved)``, each i32[q] per query for this hop:
    lanes that survived in-tile suppression and were scored (the
    unfused path always scores ``beam·(kg+kr)``), packed-row bytes
    DMA'd (``n_scored·4·dma_row_words(W)`` for the DMA placement, 0 for
    VMEM), and the bytes the suppression skipped at the DMA level.
    """
    q = beam_ids.shape[0]
    B = beam_ids.shape[1]
    n, W = words.shape
    kg, kr = graph_ids.shape[1], rev_ids.shape[1]
    if tomb is None:
        t2d = jnp.zeros((n, 1), jnp.int32)
    else:
        t2d = jnp.asarray(tomb).astype(jnp.int32).reshape(-1, 1)
    if mxu is None:
        mxu = W >= MXU_MIN_WORDS
    if dma:
        p = tune.hop_params(n, W, B, kg + kr, q)
        if block_q is None:
            block_q = min(p.block_q, max(q, 1))
        if score_chunk is None:
            score_chunk = p.score_chunk
        if n_buffers is None:
            n_buffers = p.n_buffers
    else:
        if block_q is None:
            # Wide sketches blow up 8× when unpacked to bit-planes —
            # keep the per-tile candidate block small; narrow sketches
            # amortize grid overhead with bigger tiles. Capped at the
            # actual row count so small waves / slot arrays (continuous
            # serving runs q = n_slots every tick) never do dense
            # estimator work on padding.
            block_q = min(8 if mxu else 64, max(q, 1))
        if score_chunk is None:
            score_chunk = 256
        n_buffers = 1
    return _hop_jit(graph_ids, rev_ids, words, card, t2d, q_words, q_card,
                    beam_ids, beam_sims, block_q=block_q,
                    chunk=score_chunk, mxu=mxu, dma=dma,
                    n_buffers=n_buffers, with_counts=with_counts,
                    interpret=config.interpret_mode())
