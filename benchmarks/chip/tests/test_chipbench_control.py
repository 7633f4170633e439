"""The control: the program's own bf16 path, one precision below the f32
policy the configuration states.  On the chip the f32 policy already
takes its products in one bf16 pass, and the control reads like sound
runs there, so the limits cannot fail it (PERF.md, section 2); on the
CPU, where the f32 policy is exact, the same comparison must see the
control plainly.  The sound program lands within rounding of the
reference, the control a hundred times further."""
import pytest

import _paths  # noqa: F401
from benchlib import readings

SEED = 3_000_000_029


@pytest.mark.parametrize("workload", ("sec4.round", "sec4-codec.round"))
def test_comparison_sees_the_bf16_control(workload, cache_restored):
    sound = readings.reading(workload, SEED, "program", require_tpu=False)
    control = readings.reading(workload, SEED, "bf16", require_tpu=False)
    assert sound["count_rounds_off"] == control["count_rounds_off"] == 0
    assert sound["update1_gap"] < 1e-4
    assert control["update1_gap"] > 100 * sound["update1_gap"]
