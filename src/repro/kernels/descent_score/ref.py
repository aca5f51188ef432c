"""Pure-jnp oracle for the fused descent-hop kernel.

This is the historical ``query/search.descent_step`` body, verbatim
semantics: gather forward + reverse neighbors of the beam, score every
candidate lane with the GoldFinger estimator, then let ``merge_topk``
mask duplicates/PADs and run one wide ``lax.top_k``. The fused kernel
must match it bit for bit (ids and sims); ``query/search`` also serves
through it when ``QueryConfig(kernel=False)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.knn.topk import merge_topk
from repro.sketch.goldfinger import jaccard_pairwise_auto
from repro.types import NEG_INF, PAD_ID


def row_scorer(words, card):
    """Row scorer: sims of one query against a PAD_ID-padded id list.

    The estimator layout is width-dispatched (``jaccard_pairwise_auto``):
    VPU popcount for narrow sketches, int8 bit-plane MXU matmul for wide
    raw-incidence ones — bitwise-identical results either way.
    """

    def score_row(qw, qc, cids):
        safe = jnp.where(cids == PAD_ID, 0, cids)
        cw = words[safe]
        cc = jnp.where(cids == PAD_ID, 0, card[safe])
        s = jaccard_pairwise_auto(qw[None], qc[None], cw, cc)[0]
        return jnp.where(cids == PAD_ID, NEG_INF, s)

    return jax.vmap(score_row)


def mask_dead(tomb, ids, sims=None):
    """PAD out lanes naming tombstoned rows (``tomb`` bool[n]), in place
    positionally — no compaction, so lane order (and therefore every
    downstream tie-break) is exactly what an index with those references
    excised would produce. With ``sims``, masked lanes also drop to
    −inf (beam lanes carry a sim; candidate lanes are scored later)."""
    t = jnp.asarray(tomb)
    safe = jnp.where(ids == PAD_ID, 0, ids)
    dead = (ids != PAD_ID) & t[safe]
    out_ids = jnp.where(dead, PAD_ID, ids)
    if sims is None:
        return out_ids
    return out_ids, jnp.where(dead, NEG_INF, sims)


def hop_candidates(graph_ids, rev_ids, beam_ids, beam_sims, tomb=None):
    """The gather half of a hop: ``(beam_ids, beam_sims, cand)``.

    Dead beam lanes drop to PAD/−inf, and ``cand`` i32[q, beam·(kg+kr)]
    holds the forward then reverse neighbors of each beam lane in
    ``[fwd | rev]`` column order, with PAD under PAD beam lanes and on
    lanes naming tombstoned rows (``tomb`` bool[n] or None).
    """
    if tomb is not None:
        beam_ids, beam_sims = mask_dead(tomb, beam_ids, beam_sims)
    nq = beam_ids.shape[0]
    kg, kr = graph_ids.shape[1], rev_ids.shape[1]
    safe = jnp.where(beam_ids == PAD_ID, 0, beam_ids)
    fwd = graph_ids[safe].reshape(nq, -1)
    fwd = jnp.where((beam_ids == PAD_ID).repeat(kg, axis=1), PAD_ID, fwd)
    rev = rev_ids[safe].reshape(nq, -1)
    rev = jnp.where((beam_ids == PAD_ID).repeat(kr, axis=1), PAD_ID, rev)
    cand = jnp.concatenate([fwd, rev], axis=1)      # [q, beam·(kg+kr)]
    if tomb is not None:
        cand = mask_dead(tomb, cand)
    return beam_ids, beam_sims, cand


def descent_hop_ref(graph_ids, rev_ids, words, card,
                    q_words, q_card, beam_ids, beam_sims, tomb=None):
    """One friend-of-a-friend hop, unfused: gather → score ALL lanes →
    dedup after the fact → wide top-k. Returns (beam_ids, beam_sims).

    ``tomb`` (bool[n] or None) masks tombstoned rows out *before* any
    scoring: dead beam lanes become PAD/−inf (a row deleted mid-descent
    leaves the beam) and dead candidate lanes become PAD (stale edges to
    deleted rows score nothing) — the same pre-masking the fused kernel
    applies, so the bitwise ref↔kernel equivalence is unchanged.
    """
    beam_ids, beam_sims, cand = hop_candidates(graph_ids, rev_ids,
                                               beam_ids, beam_sims, tomb)
    cand_sims = row_scorer(words, card)(q_words, q_card, cand)
    return merge_topk(
        jnp.concatenate([beam_ids, cand], axis=1),
        jnp.concatenate([beam_sims, cand_sims], axis=1),
        beam_ids.shape[1])
