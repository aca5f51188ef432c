"""The index check of the build cells sees each kind of damage to the
packaged index, and reads 0 on the index as built."""
import copy

import numpy as np
import pytest

from bench import checks, data, reference, system
from bench.tests.tiny import tiny


@pytest.fixture(scope="module")
def built():
    from repro.core import clustering, local_knn, merge
    from repro.query.index import build_index
    from repro.sketch import goldfinger

    cfg = tiny("ml10M.build")["config"]
    b = cfg["build"]
    pop = data.generate(cfg["stats"], seed=2**37 + 9, n_pool=0)
    ds = system.dataset(pop, pop.n, "tiny")
    p = system.c2_params(cfg)
    gf = goldfinger.fingerprint_dataset(ds, n_bits=p.n_bits, seed=p.seed)
    plan = clustering.build_plan(ds, p)
    graph = merge.merge_partial(*local_knn.local_knn(plan, gf, p), p.k)
    index = build_index(ds, p, gf=gf, plan=plan, graph=graph)
    words, card = reference.fingerprints(pop.items, pop.offsets,
                                         b["n_bits"], b["seed"])
    labels, paths = reference.cluster_tables(pop.items, pop.offsets, b)
    ref = (words, card, labels, paths, reference.hash_seeds(b))
    return index, (np.asarray(graph.ids), np.asarray(graph.sims)), ref


def _rev_foreign(ix, graph):
    u = int(np.flatnonzero(ix.rev_ids[:, 0] >= 0)[0])
    ix.rev_ids[u, 0] = u  # a user is never its own in-neighbour


def _rev_short(ix, graph):
    u = int(np.flatnonzero(ix.rev_ids[:, 0] >= 0)[0])
    ix.rev_ids[u] = np.r_[ix.rev_ids[u, 1:], -1]


def _member_moved(ix, graph):
    # Swap a member of one cluster with one of another cluster of the
    # same configuration: sizes stay, the partition changes.
    off, cfg = ix.cluster_offsets, ix.cluster_config
    a = 0
    b = int(np.flatnonzero(cfg == cfg[a])[1])
    m = ix.cluster_members
    m[off[a]], m[off[b]] = m[off[b]], m[off[a]]


def _seed_changed(ix, graph):
    ix.hash_seeds[0] += 1


def _bit_flipped(ix, graph):
    ix.words[3, 0] ^= 1


def _graph_copy_stale(ix, graph):
    ix.graph_ids[7] = np.roll(ix.graph_ids[7], 1)


DAMAGE = {"rev_foreign": ("rev_faults", _rev_foreign),
          "rev_short": ("rev_faults", _rev_short),
          "member_moved": ("cluster_faults", _member_moved),
          "seed_changed": ("cluster_faults", _seed_changed),
          "bit_flipped": ("index_row_faults", _bit_flipped),
          "graph_copy_stale": ("index_row_faults", _graph_copy_stale)}


def test_sound_index_reads_zero(built):
    index, graph, ref = built
    assert checks.check_index(index, graph, *ref) == {
        "index_row_faults": 0, "rev_faults": 0, "cluster_faults": 0}


@pytest.mark.parametrize("damage", sorted(DAMAGE))
def test_damage_is_counted(built, damage):
    index, graph, ref = built
    number, harm = DAMAGE[damage]
    ix = copy.deepcopy(index)
    harm(ix, graph)
    assert checks.check_index(ix, graph, *ref)[number] > 0
