"""The system under test, as the benchmark drives it.

Turns a configuration file into the program's own objects. Everything
that belongs to one configuration is data; this module only maps keys.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell's data files and the run's seed."""

    config: dict
    traffic: dict
    seed: int
    spans: object  # bench.spans.Spans


def dataset(pop, n: int, name: str):
    """The first ``n`` users of a population as the program's Dataset."""
    from repro.types import Dataset
    return Dataset(name=name, n_users=n, n_items=pop.n_items,
                   items=pop.items[:pop.offsets[n]].copy(),
                   offsets=pop.offsets[:n + 1].copy())


def c2_params(config: dict):
    from repro.core.params import C2Params
    return C2Params(**config["build"])


def query_config(config: dict, batching: str):
    from repro.query.engine import QueryConfig
    return QueryConfig(continuous=batching == "continuous",
                       **config["serve"])


def sample(seed: int, salt: int, n: int, size: int) -> np.ndarray:
    """``size`` distinct indices of ``range(n)`` drawn from the seed,
    sorted; a stream of its own per ``salt``."""
    rng = np.random.default_rng([seed, salt])
    return np.sort(rng.choice(n, size=min(size, n), replace=False))
