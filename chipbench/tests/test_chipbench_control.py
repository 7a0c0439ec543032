"""The control — the plain reference computed in bfloat16, put in the
program's place — must come out not correct in every cell, while the
reference compared with itself reads 0.  At a small scale on the CPU;
``python3 chipbench/control.py`` runs the same at the cells' own scale."""
import _paths

import pytest

from chipbench import control, harness

BENCH = _paths.bench()
SMALL = {"scale": 0.002}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_control_fails_and_reference_passes(cell):
    limits = harness.Cell.load(BENCH, cell).config["limits"]
    ctl = control.readings(BENCH, cell, 2 ** 33 + 5, config_override=SMALL)
    assert any(v > limits[k] for k, v in ctl.items()), ctl
    same = control.readings(BENCH, cell, 2 ** 33 + 5, config_override=SMALL,
                            control=False)
    assert all(v == 0 for v in same.values()), same
