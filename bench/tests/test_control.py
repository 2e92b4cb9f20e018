"""The control — the reference computed in float32, put in the program's
place — must fail the comparison in every cell; the program must pass."""

import pytest

from readings import readings

CELLS = ["fb150_k2.sweep", "fb48_k4.stream", "fb48_k4.sweep"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cell, tiny_config):
    rows = readings(cell, [5], [5], require_tpu=False, cache=False,
                    config=tiny_config(cell.split(".")[0]), log=lambda s: 0)
    prog, ctrl = rows[0]["program"], rows[0]["control"]
    exact = [k for k in prog if k.endswith("_mismatch") or k == "violations"]
    assert all(prog[k] == 0 for k in exact), prog
    assert any(ctrl[k] > 0 for k in exact), ctrl
