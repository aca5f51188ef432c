"""The generator matches its configuration and is a function of the seed."""
import json

import numpy as np
import pytest

from bench import data
from bench.tests.tiny import ROOT, TINY_STATS


def _stats(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["stats"]


@pytest.mark.parametrize("name", ["ml10M", "AM"])
def test_users_items_and_mean_profile_match_the_config(name):
    stats = _stats(name)
    pop = data.generate(stats, seed=2**35 + 9, n_pool=512)
    assert pop.n == stats["n_users"] + 512
    assert pop.n_items == stats["n_items"]
    assert 0 <= pop.items.min() and pop.items.max() < stats["n_items"]
    mean = pop.sizes[:stats["n_users"]].mean()
    assert abs(mean - stats["mean_profile"]) < 0.01 * stats["mean_profile"]
    assert pop.sizes.min() >= stats["min_profile"]
    # rows are sorted and hold distinct items
    for u in range(0, pop.n, 997):
        p = pop.profile(u)
        assert np.all(np.diff(p) > 0)


def test_held_out_queries_share_the_index_topic_map():
    stats = dict(_stats("ml10M"), **TINY_STATS)
    pop = data.generate(stats, seed=5, n_pool=300)
    n = stats["n_users"]

    def home_share(lo, hi):
        part = pop.rows(lo, hi)
        user = np.repeat(np.arange(part.n), part.sizes)
        return np.mean(pop.item_topic[part.items] == part.user_topic[user])

    index_share, pool_share = home_share(0, n), home_share(n, pop.n)
    # Pool profiles follow the same item->topic map as the index's users:
    # their home share is the configured affinity, not the 1/n_topics a
    # foreign map would give.
    assert abs(pool_share - index_share) < 0.05
    assert pool_share > 0.6 > 1.0 / stats["n_topics"]


def test_same_seed_same_bytes_other_seed_other_bytes():
    stats = dict(_stats("AM"), **TINY_STATS)
    a = data.generate(stats, seed=2**33 + 1, n_pool=64)
    b = data.generate(stats, seed=2**33 + 1, n_pool=64)
    c = data.generate(stats, seed=2**33 + 2, n_pool=64)
    assert a.items.tobytes() == b.items.tobytes()
    assert a.offsets.tobytes() == b.offsets.tobytes()
    assert a.items.tobytes() != c.items.tobytes()
