"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The main path's kernels and jitted programs are compiled for one chip of
a ``v5e:2x2`` topology at the shapes ``chip_smoke.py`` runs: the
MovieLens-10M index (69,816 users, padded by the serving plan to
131,072 rows), 1024-bit GoldFinger sketches (W = 32 words), k = 30
forward and reverse neighbors, a 256-query wave with beam 32, the
build's 2048-row capacity groups, the build's FRH distinct-hash
table (t = 8, b = 4096, depth 6) over ml10M's 5.9 M items, and the
raw-data build's group program on 10,496-bit incidence rows (W = 328
words), which takes the int8 bit-plane matmul. A compile
that passes here is not a chip run; what the chip's compiler refuses
fails here for free.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import config
from repro.kernels.descent_score import tune
from repro.kernels.descent_score.descent_score import (hop_pallas,
                                                       hop_pallas_dma)

N_ROWS = 131_072      # capacity_of(69_816, minimum=64)
W = 32                # 1024-bit GoldFinger words
KG = KR = 30          # forward / reverse neighbors per row (k = 30)
BEAM = 32
Q = 256               # one wave
SEEDS = 128           # 8 hash configurations x 16 routed seeds
CAP, M_GROUP = 2048, 16   # one capacity group of the build
W_RAW = 328           # ml10M incidence rows: 10,472 items in 10,496 bits
N_PROFILES, P_MAX = 70_144, 1348  # ml10M users padded to 256; widest profile
N_USERS, NNZ = 69_816, 5_885_489  # ml10M users and their items, unpadded


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # A compile for a described chip is written to a persistent cache
    # but cannot be read back without one: keep the cache out of it.
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_kernels():
    config.set_interpret(False)
    try:
        yield
    finally:
        config.set_interpret(None)


def _shape(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hop_args(sharding=None):
    s = lambda shape, dt: _shape(shape, dt, sharding)  # noqa: E731
    return (s((N_ROWS, KG), jnp.int32), s((N_ROWS, KR), jnp.int32),
            s((N_ROWS, W), jnp.uint32), s((N_ROWS, 1), jnp.int32),
            s((N_ROWS, 1), jnp.int32), s((Q, W), jnp.uint32),
            s((Q, 1), jnp.int32), s((Q, BEAM), jnp.int32),
            s((Q, BEAM), jnp.float32))


def test_dma_hop_compiles_at_smoke_shapes(one_chip):
    p = tune.hop_params(N_ROWS, W, BEAM, KG + KR, Q)
    hop = jax.jit(lambda *a: hop_pallas_dma(
        *a, block_q=p.block_q, chunk=p.score_chunk, n_buffers=p.n_buffers,
        interpret=False))
    compiled = hop.lower(*_hop_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_blocked_hop_refuses_compiled_mode_at_smoke_shapes():
    hop = jax.jit(lambda *a: hop_pallas(*a, interpret=False))
    with pytest.raises(NotImplementedError,
                       match=r"interpret mode only.*scoped VMEM limit"):
        hop.lower(*_hop_args())


def test_cluster_knn_kernel_compiles_at_capacity_group(one_chip,
                                                        compiled_kernels):
    from repro.kernels.goldfinger_knn import ops as gk_ops

    compiled = gk_ops.cluster_knn.lower(
        _shape((M_GROUP, CAP, W), jnp.uint32, one_chip),
        _shape((M_GROUP, CAP), jnp.int32, one_chip),
        _shape((M_GROUP, CAP), jnp.int32, one_chip), k=KG).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_minhash_kernel_compiles_at_ml10m_profile_width(one_chip):
    from repro.kernels.frh_minhash.frh_minhash import minhash_pallas

    compiled = minhash_pallas.lower(
        _shape((N_PROFILES, P_MAX), jnp.int32, one_chip),
        seeds=tuple(range(1, 9)), b=4096, block_n=256,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_distinct_hash_table_compiles_at_ml10m_shapes(one_chip):
    from repro.core.hashing import distinct_hash_table

    compiled = distinct_hash_table.lower(
        _shape((NNZ,), jnp.int32, one_chip),
        _shape((N_USERS + 1,), jnp.int32, one_chip),
        _shape((8,), jnp.int32, one_chip), b=4096, depth=6).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


def test_jnp_wave_program_compiles_at_smoke_shapes(one_chip):
    from repro.query.search import batched_descent

    s = lambda shape, dt: _shape(shape, dt, one_chip)  # noqa: E731
    compiled = batched_descent.lower(
        s((N_ROWS, KG), jnp.int32), s((N_ROWS, KR), jnp.int32),
        s((N_ROWS, W), jnp.uint32), s((N_ROWS,), jnp.int32),
        s((Q, W), jnp.uint32), s((Q,), jnp.int32), s((Q, SEEDS), jnp.int32),
        k=10, beam=BEAM, hops=3, tomb=s((N_ROWS,), jnp.bool_)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


def test_group_knn_takes_the_mxu_branch_at_incidence_width(one_chip):
    from repro.core.local_knn import _group_knn

    compiled = _group_knn.lower(
        _shape((M_GROUP, CAP, W_RAW), jnp.uint32, one_chip),
        _shape((M_GROUP, CAP), jnp.int32, one_chip),
        _shape((M_GROUP, CAP), jnp.int32, one_chip), k=KG).compile()
    text = compiled.as_text()
    # The intersection is an int8 product with int32 accumulation.
    assert "convolution" in text and "s8[" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**30
