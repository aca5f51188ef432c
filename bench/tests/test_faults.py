"""A run with its timed path broken underneath comes out not correct,
once for each fault a cell can have."""
import io

import jax
import pytest

from bench import faults, run
from bench.tests.tiny import tiny

CASES = ([("ml10M.build", f) for f in faults.BUILD]
         + [(c, f) for c in ("ml10M.serve_steady", "ml10M.serve_batch")
            for f in faults.SERVE])


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_makes_the_run_incorrect(cell, fault):
    loaded = tiny(cell)
    planted = (faults.build_fault(fault) if cell.endswith(".build") else
               faults.serve_fault(fault, loaded["traffic"]["batching"]))
    with planted:
        result = run.execute(loaded, 2**35 + 3, 0.5, False,
                             jax.devices()[:1], err=io.StringIO())
    assert not result["correct"], result["checks"]
