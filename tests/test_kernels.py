"""Per-kernel interpret-mode validation: shape sweeps vs pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashing
from repro.kernels.frh_minhash import ops as mh_ops
from repro.kernels.frh_minhash import ref as mh_ref
from repro.kernels.goldfinger_knn import ops as gk_ops
from repro.kernels.goldfinger_knn import ref as gk_ref
from repro.types import PAD_ID


def _random_gf(rng, n, n_bits, density=0.1):
    words = rng.integers(0, 2**32, size=(n, n_bits // 32), dtype=np.uint64)
    # Sparsify: AND a few random masks so popcounts vary.
    for _ in range(3):
        words &= rng.integers(0, 2**32, size=words.shape, dtype=np.uint64)
    words = words.astype(np.uint32)
    card = np.unpackbits(words.view(np.uint8), axis=1).sum(1).astype(np.int32)
    return jnp.asarray(words), jnp.asarray(card)


@pytest.mark.parametrize("nq,nd", [(32, 32), (64, 128), (128, 512),
                                   (200, 300), (1, 64)])
@pytest.mark.parametrize("n_bits", [512, 1024])
@pytest.mark.parametrize("k", [5, 30])
def test_knn_kernel_matches_ref(nq, nd, n_bits, k):
    rng = np.random.default_rng(nq * 1000 + nd + k)
    qw, qc = _random_gf(rng, nq, n_bits)
    dw, dc = _random_gf(rng, nd, n_bits)
    qi = jnp.arange(nq, dtype=jnp.int32)
    di = jnp.arange(nd, dtype=jnp.int32)
    ri, rs = gk_ref.knn_ref(qw, qc, qi, dw, dc, di, k)
    ki, ks = gk_ops.knn(qw, qc, qi, dw, dc, di, k)
    np.testing.assert_allclose(np.asarray(ks), np.asarray(rs), atol=0)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))


@pytest.mark.parametrize("block_q,block_d", [(32, 64), (128, 128), (64, 512)])
def test_knn_kernel_block_shape_invariance(block_q, block_d):
    rng = np.random.default_rng(9)
    w, c = _random_gf(rng, 256, 1024)
    ids = jnp.arange(256, dtype=jnp.int32)
    ri, rs = gk_ref.knn_ref(w, c, ids, w, c, ids, 10)
    ki, ks = gk_ops.knn(w, c, ids, w, c, ids, 10,
                        block_q=block_q, block_d=block_d)
    np.testing.assert_allclose(np.asarray(ks), np.asarray(rs), atol=0)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))


def test_knn_kernel_pad_rows_and_self_exclusion():
    rng = np.random.default_rng(4)
    w, c = _random_gf(rng, 64, 512)
    ids = np.arange(64, dtype=np.int32)
    ids[10:20] = PAD_ID
    ids_j = jnp.asarray(ids)
    ki, ks = gk_ops.knn(w, c, ids_j, w, c, ids_j, 8)
    ki = np.asarray(ki)
    # PAD query rows produce PAD ids everywhere they would self-match;
    # no row may list itself or a PAD id as a neighbor.
    live = ids != PAD_ID
    assert (ki[live] != ids[live, None]).all()
    assert (ki[live] != PAD_ID).sum() > 0
    ri, rs = gk_ref.knn_ref(w, c, ids_j, w, c, ids_j, 8)
    np.testing.assert_array_equal(ki[live], np.asarray(ri)[live])


@pytest.mark.parametrize("m,cap", [(1, 32), (3, 64), (2, 256)])
def test_cluster_knn_matches_group_ref(m, cap):
    rng = np.random.default_rng(m * 17 + cap)
    w, c = _random_gf(rng, m * cap, 512)
    mem = np.full((m, cap), PAD_ID, np.int32)
    for j in range(m):
        sz = int(rng.integers(2, cap + 1))
        mem[j, :sz] = rng.choice(m * cap, sz, replace=False)
    gm = np.where(mem == PAD_ID, 0, mem)
    wc = jnp.asarray(np.asarray(w)[gm])
    cc = jnp.asarray(np.where(mem == PAD_ID, 0, np.asarray(c)[gm]))
    memj = jnp.asarray(mem)
    ri, rs = gk_ref.cluster_knn_ref(wc, cc, memj, 6)
    ki, ks = gk_ops.cluster_knn(wc, cc, memj, 6)
    valid = (mem != PAD_ID)[..., None]
    np.testing.assert_allclose(np.where(valid, np.asarray(ks), 0),
                               np.where(valid, np.asarray(rs), 0), atol=0)
    np.testing.assert_array_equal(np.where(valid, np.asarray(ki), -9),
                                  np.where(valid, np.asarray(ri), -9))


def test_local_knn_pallas_path_matches_jnp_path(small_ds, small_gf):
    from repro.core.clustering import build_plan
    from repro.core.local_knn import local_knn
    from repro.core.params import C2Params

    p_jnp = C2Params(k=6, b=128, t=2, max_cluster=100, use_pallas=False)
    p_pal = C2Params(k=6, b=128, t=2, max_cluster=100, use_pallas=True)
    plan = build_plan(small_ds, p_jnp)
    i1, s1 = local_knn(plan, small_gf, p_jnp)
    i2, s2 = local_knn(plan, small_gf, p_pal)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(np.where(i1 == PAD_ID, 0, s1),
                               np.where(i2 == PAD_ID, 0, s2), atol=0)


# ---------------------------------------------------------------- minhash


@pytest.mark.parametrize("n,P", [(8, 16), (100, 40), (256, 64), (300, 7)])
@pytest.mark.parametrize("t", [1, 8])
@pytest.mark.parametrize("b", [256, 4096])
def test_minhash_kernel_matches_ref(n, P, t, b):
    rng = np.random.default_rng(n + P + t + b)
    padded = rng.integers(0, 10**6, size=(n, P)).astype(np.int32)
    # Random padding tails.
    for i in range(n):
        cut = int(rng.integers(1, P + 1))
        padded[i, cut:] = PAD_ID
    seeds = np.arange(t, dtype=np.int32) * 7 + 1
    r = mh_ref.minhash_ref(jnp.asarray(padded), jnp.asarray(seeds), b)
    k = mh_ops.minhash(jnp.asarray(padded), seeds, b)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


def test_minhash_kernel_matches_host_csr(small_ds):
    seeds = np.arange(4, dtype=np.int32)
    host = hashing.user_min_hash_np(
        hashing.item_hashes(small_ds.items, seeds, 1024), small_ds.offsets)
    dev = mh_ops.dataset_minhash(small_ds, seeds, 1024)
    np.testing.assert_array_equal(dev, host)


# -- interpret-mode configuration (kernels/config.py) -----------------------


def test_interpret_flag_shared_by_all_kernel_packages():
    """One switch, three packages: every kernel wrapper resolves its
    ``interpret=`` through ``kernels.config.interpret_mode()`` — none
    carries a private INTERPRET constant that could drift."""
    import inspect

    from repro.kernels import config
    from repro.kernels.descent_score import ops as ds_ops

    for mod in (ds_ops, gk_ops, mh_ops):
        assert not hasattr(mod, "INTERPRET"), mod.__name__
        assert getattr(mod, "config") is config, mod.__name__
        assert "config.interpret_mode()" in inspect.getsource(mod), \
            mod.__name__
    # All three agree by construction: the shared resolver is the only
    # source of the flag.
    assert config.interpret_mode() in (True, False)


def test_interpret_env_parsing(monkeypatch):
    """The platform decides, not the environment: CPU interprets, TPU
    compiles, any other backend is refused, and the retired
    ``REPRO_PALLAS_INTERPRET`` variable has no effect."""
    from repro.kernels import config

    monkeypatch.setattr(config, "_override", None)
    for raw in (None, "0", "1"):
        if raw is None:
            monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        else:
            monkeypatch.setenv("REPRO_PALLAS_INTERPRET", raw)
        for backend, expect in (("cpu", True), ("tpu", False)):
            monkeypatch.setattr(config.jax, "default_backend",
                                lambda b=backend: b)
            assert config.interpret_mode() is expect, (raw, backend)
        monkeypatch.setattr(config.jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            config.interpret_mode()


def test_interpret_override_beats_env(monkeypatch):
    """``set_interpret`` pins the mode over the platform rule (on either
    backend), and ``set_interpret(None)`` hands control back to it."""
    from repro.kernels import config

    monkeypatch.setattr(config.jax, "default_backend", lambda: "tpu")
    config.set_interpret(True)
    try:
        assert config.interpret_mode() is True
        config.set_interpret(None)  # back to the platform rule
        assert config.interpret_mode() is False
        monkeypatch.setattr(config.jax, "default_backend", lambda: "cpu")
        config.set_interpret(False)
        assert config.interpret_mode() is False
    finally:
        config.set_interpret(None)
