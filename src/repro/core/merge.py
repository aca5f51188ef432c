"""Step 3 of C²: merging the t partial KNN graphs (paper Alg. 3).

The paper inserts each partial neighborhood into per-user bounded heaps,
reusing similarity values. The vectorized equivalent: concatenate each
user's t×k candidates, mask duplicates (reuse, not recompute), and take one
wide top-k (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.knn.topk import merge_topk
from repro.sched import trace
from repro.types import KNNGraph


@functools.partial(jax.jit, static_argnames=("k",))
def _merge(ids_tkn, sims_tkn, k: int):
    t, n, _ = ids_tkn.shape
    ids = jnp.transpose(ids_tkn, (1, 0, 2)).reshape(n, -1)
    sims = jnp.transpose(sims_tkn, (1, 0, 2)).reshape(n, -1)
    self_ids = jnp.arange(n, dtype=ids.dtype)
    return merge_topk(ids, sims, k, self_ids)


def merge_partial(ids: np.ndarray, sims: np.ndarray, k: int) -> KNNGraph:
    """ids/sims: [t, n, k'] per-configuration partial KNNs → final graph."""
    with trace.span("repro.merge"):
        out_ids, out_sims = _merge(jnp.asarray(ids), jnp.asarray(sims), k)
        return KNNGraph(ids=np.asarray(out_ids), sims=np.asarray(out_sims))
