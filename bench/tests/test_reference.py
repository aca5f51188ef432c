"""The reference agrees with the program where both are exact: the
GoldFinger arithmetic, and the C² graph's co-member semantics."""
import numpy as np

from bench import data, reference, system
from bench.tests.tiny import tiny


def _setup():
    loaded = tiny("ml10M.build")
    cfg = loaded["config"]
    pop = data.generate(cfg["stats"], seed=2**36 + 3, n_pool=0)
    return cfg, pop, system.dataset(pop, pop.n, "tiny")


def test_goldfinger_similarity_matches_the_program():
    from repro.sketch.goldfinger import fingerprint_dataset, jaccard_pairwise
    cfg, pop, ds = _setup()
    b = cfg["build"]
    gf = fingerprint_dataset(ds, n_bits=b["n_bits"], seed=b["seed"])
    words, card = reference.fingerprints(pop.items, pop.offsets,
                                         b["n_bits"], b["seed"])
    np.testing.assert_array_equal(card, np.asarray(gf.card))
    prog = np.asarray(jaccard_pairwise(gf.words[:50], gf.card[:50],
                                       gf.words, gf.card))
    inter = reference.gf_inter(words[:50, None, :], words[None, :, :])
    ref = reference.gf_sim(inter, card[:50, None], card[None, :])
    np.testing.assert_array_equal(prog, ref)


def test_cluster_semantics_match_the_program_plan():
    from repro.core.clustering import build_plan
    cfg, pop, ds = _setup()
    plan = build_plan(ds, system.c2_params(cfg))
    labels = reference.cluster_labels(pop.items, pop.offsets, cfg["build"])
    ref = {(c, frozenset(np.flatnonzero(labels[c] == lab).tolist()))
           for c in range(labels.shape[0])
           for lab in np.unique(labels[c][labels[c] >= 0])}
    prog = {(int(c), frozenset(m.tolist()))
            for c, m in zip(plan.config_of, plan.members)}
    assert ref == prog


def test_tie_aware_recall_counts_ties_and_ignores_repeats():
    sims = np.array([0.9, 0.5, 0.5, 0.1, 0.0])
    assert reference.tie_aware_recall(np.array([0, 2]), sims, 2) == 1.0
    assert reference.tie_aware_recall(np.array([0, 0]), sims, 2) == 0.5
    assert reference.tie_aware_recall(np.array([3, -1]), sims, 2) == 0.0
