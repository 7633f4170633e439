"""A run with the timed path broken underneath must come out not
``correct``: with its state returned unchanged, with half of each batch
left out (the mean taken over the rest), and with an answer altered where
it is produced.  The harness's look for a chip is skipped; everything else
is a run of the cell on the CPU, at the cell's sizes with a short
window."""
import pytest

import _paths  # noqa: F401
from benchlib import harness, readings

ROUND_CELLS = ("sec4.round", "sec4-codec.round")
SEED = 3_000_000_017


def _run(workload, plant=None, seconds=0.3):
    return harness.run(workload, SEED, seconds, False, require_tpu=False,
                       log=lambda s: None, plant=plant)


@pytest.mark.parametrize("workload", ROUND_CELLS)
def test_sound_run_is_correct(workload, cache_restored):
    out = _run(workload)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ("altered", "unchanged", "half_batch"))
@pytest.mark.parametrize("workload", ROUND_CELLS)
def test_planted_fault_is_not_correct(workload, fault, cache_restored):
    out = _run(workload, plant=readings.FAULTS[fault])
    assert not out["correct"], out["checks"]
