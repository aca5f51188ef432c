"""FastRandomHash unit + property tests, incl. Theorem 1 (paper §III)."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # [test] extra; skip, don't break collection
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import hashing
from repro.types import dataset_from_profiles


def _hash_profile(profile, seed, b):
    ds = dataset_from_profiles("x", [sorted(profile)], 10**6)
    h = hashing.item_hashes(ds.items, np.array([seed], np.int32), b)
    return hashing.user_min_hash_np(h, ds.offsets)[0, 0], h[0]


def test_range_and_determinism():
    items = np.arange(1000, dtype=np.int32)
    h1 = hashing.item_hashes(items, np.arange(4, dtype=np.int32), 256)
    h2 = hashing.item_hashes(items, np.arange(4, dtype=np.int32), 256)
    assert (h1 == h2).all()
    assert h1.min() >= 0 and h1.max() < 256
    # Different seeds give different streams.
    assert (h1[0] != h1[1]).any()


def test_min_hash_is_min_of_item_hashes():
    rng = np.random.default_rng(1)
    profiles = [rng.choice(5000, size=rng.integers(1, 50), replace=False)
                for _ in range(30)]
    ds = dataset_from_profiles("x", [sorted(p) for p in profiles], 5000)
    seeds = np.arange(3, dtype=np.int32)
    item_h = hashing.item_hashes(ds.items, seeds, 512)
    H = hashing.user_min_hash_np(item_h, ds.offsets)
    for i in range(3):
        for u in range(ds.n_users):
            hs = item_h[i, ds.offsets[u]:ds.offsets[u + 1]]
            assert H[i, u] == hs.min()


def test_distinct_hashes_ascending_and_complete():
    rng = np.random.default_rng(2)
    profiles = [rng.choice(2000, size=rng.integers(1, 40), replace=False)
                for _ in range(25)]
    ds = dataset_from_profiles("x", [sorted(p) for p in profiles], 2000)
    seeds = np.arange(2, dtype=np.int32)
    item_h = hashing.item_hashes(ds.items, seeds, 64)
    cands = hashing.user_distinct_hashes_np(item_h, ds.offsets, depth=5)
    for i in range(2):
        for u in range(ds.n_users):
            expected = np.unique(item_h[i, ds.offsets[u]:ds.offsets[u + 1]])[:5]
            got = cands[i, u][cands[i, u] != hashing.NO_HASH]
            assert (got == expected).all()
            assert (np.diff(got) > 0).all()  # strictly ascending


def test_hash_above():
    items = np.array([3, 7, 42, 99], dtype=np.int32)
    ds = dataset_from_profiles("x", [items], 1000)
    h = hashing.item_hashes(ds.items, np.array([0], np.int32), 128)
    hs = np.sort(np.unique(h[0]))
    eta = int(hs[0])
    out = hashing.user_hash_above_np(h[0], ds.offsets, eta, np.array([0]))
    if len(hs) > 1:
        assert out[0] == hs[1]
    else:
        assert out[0] == hashing.NO_HASH


@settings(deadline=None, max_examples=20)
@given(
    shared=st.sets(st.integers(0, 9999), min_size=5, max_size=40),
    only1=st.sets(st.integers(10000, 19999), min_size=0, max_size=30),
    only2=st.sets(st.integers(20000, 29999), min_size=0, max_size=30),
)
def test_theorem1_collision_probability(shared, only1, only2):
    """P[H(u1)=H(u2)] ∈ [J − κ/ℓ, (J + κ/ℓ)/(1 − κ/ℓ)] for every h (Eq. 9).

    We check the *per-hash-function* identity (6): the empirical rate over
    many seeds must respect the bound built from each seed's own κ.
    """
    p1 = sorted(shared | only1)
    p2 = sorted(shared | only2)
    union = sorted(shared | only1 | only2)
    ell = len(union)
    j12 = len(shared) / ell
    b = 4096
    n_seeds = 300
    ds = dataset_from_profiles("x", [p1, p2, union], 30000)
    seeds = np.arange(n_seeds, dtype=np.int32)
    item_h = hashing.item_hashes(ds.items, seeds, b)
    H = hashing.user_min_hash_np(item_h, ds.offsets)
    hits = (H[:, 0] == H[:, 1]).mean()
    # κ per seed: collisions of h on P1 ∪ P2.
    o_u = slice(ds.offsets[2], ds.offsets[3])
    kappas = np.array([ell - len(np.unique(item_h[s, o_u]))
                       for s in range(n_seeds)])
    kl = kappas.mean() / ell
    lo = j12 - kl
    hi = (j12 + kl) / max(1 - kl, 1e-9)
    # 3σ slack for the empirical estimate over n_seeds draws.
    sigma = 3 * np.sqrt(max(hits * (1 - hits), 0.25 / n_seeds) / n_seeds)
    assert lo - sigma <= hits <= hi + sigma


def _csr(seed, n, max_size, n_items):
    """Seeded CSR profiles with empty users, single-item users and items
    repeated inside a profile (drawn with replacement)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(2, max(max_size, 2) + 1, size=n)
    sizes[rng.random(n) < 0.15] = 1
    sizes[(rng.random(n) < 0.15) | (max_size == 0)] = 0
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(sizes, out=offsets[1:])
    items = rng.integers(0, n_items, size=int(offsets[-1])).astype(np.int32)
    return items, offsets


# (seed, users, widest profile, item universe, b, depth, one packed key)
DEVICE_CASES = {
    "ties_b8_depth6": (1, 120, 40, 300, 8, 6, True),
    "ties_b8_depth1": (2, 120, 40, 300, 8, 1, True),
    "b4096_depth6": (3, 400, 60, 5000, 4096, 6, True),
    "b64_few_distinct": (4, 200, 4, 50, 64, 6, True),
    "two_key_scan": (5, 256, 30, 10**6, 2**23, 6, False),
    "all_empty": (6, 5, 0, 10, 64, 6, True),
}


@pytest.mark.parametrize("case", DEVICE_CASES.values(), ids=DEVICE_CASES)
def test_device_table_matches_host_and_oracle(case):
    seed, n, max_size, n_items, b, depth, packed = case
    items, offsets = _csr(seed, n, max_size, n_items)
    seeds = np.arange(3, dtype=np.int32) + np.int32(seed * 1009)
    assert hashing.packs(n, b) == packed  # which scan the shapes choose
    item_h = hashing.item_hashes(items, seeds, b)
    host = hashing.user_distinct_hashes_np(item_h, offsets, depth)
    oracle = hashing.user_distinct_hashes_np_ref(item_h, offsets, depth)
    dev = hashing.user_distinct_hashes_device(items, offsets, seeds, b, depth)
    assert dev.dtype == np.int32 and dev.shape == (3, n, depth)
    np.testing.assert_array_equal(dev, host)
    np.testing.assert_array_equal(dev, oracle)


def test_build_plan_matches_the_host_table_plan(monkeypatch):
    from repro.core.clustering import build_plan
    from repro.core.params import C2Params
    from repro.data.synthetic import make_dataset

    ds = make_dataset("synth", scale=0.2, seed=11)
    p = C2Params(k=10, b=64, t=4, max_cluster=40)
    dev = build_plan(ds, p)

    def host_table(items, offsets, seeds, b, depth):
        return hashing.user_distinct_hashes_np(
            hashing.item_hashes(items, seeds, b), offsets, depth)

    monkeypatch.setattr(hashing, "user_distinct_hashes_device", host_table)
    host = build_plan(ds, p)
    assert dev.n_clusters == host.n_clusters > 0
    assert any(len(path) > 1 for path in dev.paths)  # splits happened
    for a, b in zip(dev.members, host.members):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(dev.config_of, host.config_of)
    assert dev.paths == host.paths
