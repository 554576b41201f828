"""The control (the reference in float32, in the program's place) has to
come out NOT correct, by the very comparison a run uses."""

import pytest

import control


@pytest.mark.parametrize("seed", [3, 2**31 + 17, 424242])
@pytest.mark.parametrize("cell", ["tpch-sf1.scan", "tpch-sf1.join"])
def test_float32_control_is_not_correct(cell, seed):
    r = control.control_reading(cell, seed, sf=0.01)
    assert r["correct"] is False
    assert r["wrong_answers"] > 0 or r["max_rel_err"] > 3 * r["limit"]
