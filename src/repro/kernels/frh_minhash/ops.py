"""jit'd public wrapper for the frh_minhash kernel.

Interpret-vs-compiled resolves per call through
``repro.kernels.config``: interpreted on the CPU backend, compiled on TPU.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import config
from repro.kernels.frh_minhash.frh_minhash import minhash_pallas
from repro.types import PAD_ID, Dataset


def minhash(padded_items, seeds, b: int, block_n: int = 256):
    """int32[n, P] (PAD_ID padded) → int32[n, t] FastRandomHash values."""
    n, P = padded_items.shape
    bn = min(block_n, max(8, n))
    pad = (-n) % bn
    if pad:
        padded_items = jnp.concatenate(
            [jnp.asarray(padded_items),
             jnp.full((pad, P), PAD_ID, jnp.int32)], axis=0)
    out = minhash_pallas(jnp.asarray(padded_items),
                         tuple(int(s) for s in seeds), b,
                         block_n=bn, interpret=config.interpret_mode())
    return out[:n]


def dataset_minhash(ds: Dataset, seeds, b: int) -> np.ndarray:
    """Host entry: returns int32[t, n] to match hashing.user_min_hash_np."""
    padded, _ = ds.padded_profiles()
    out = minhash(jnp.asarray(padded), seeds, b)
    return np.asarray(out).T.copy()
