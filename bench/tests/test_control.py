"""The control — the reference in bfloat16 in the program's place — fails
a limit of every cell, while the program passes them."""
import numpy as np
import pytest

from bench import spans, system
from bench import run
from bench.tests.tiny import tiny


@pytest.mark.parametrize("cell", ["ml10M.build", "AM.build",
                                  "ml10M.serve_steady", "ml10M.serve_batch"])
def test_control_fails_a_limit(cell):
    loaded = tiny(cell)
    drv = run.driver(loaded["traffic"]["driver"])
    ctx = system.Ctx(config=loaded["config"], traffic=loaded["traffic"],
                     seed=2**34 + 5, spans=spans.Spans())
    st = drv.setup(ctx)
    win = drv.measure(st, 0.5)
    limits = loaded["limits"]

    def passes(numbers):
        return all(v <= limits[k] for k, v in numbers.items())

    assert passes(drv.check(st, win)["numbers"])
    control = drv.check(st, win, control=True)["numbers"]
    assert not passes(control), control
    assert np.isfinite(list(control.values())).all()
