"""A run at a small size, with the timed path broken underneath, comes
out not correct; the same run unbroken comes out correct; and the
lower-precision control, put in the program's place, comes out not
correct through the same comparison. The limits and the compared rounds
are the cell's own (``bench/limits``)."""
import pytest

from bench import check, generate
from bench.cells import family
from bench.tests.tiny import SEED, run_tiny, tiny

CELL = "silo8.cc_power"


def test_sound_run_is_correct():
    out = run_tiny(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"client_rounds_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_is_caught(fault):
    out = run_tiny(CELL, fault)
    assert not out["correct"], out["checks"]


def test_bfloat16_control_is_not_correct():
    c = tiny(CELL)
    inputs = generate.make_inputs(family(c), c.config, c.traffic, SEED)
    ref = check.reference_outputs(c, inputs)
    ctl = check.control_outputs(c, inputs)
    correct, compared = check.verdict(
        check.numbers(inputs.params, ctl, ref), c.limits)
    assert not correct, compared
