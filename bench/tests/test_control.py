"""The control of each cell, the reference one precision below the
configuration's put in the program's place, fails the cell's committed
limits; here at a tiny size on the CPU, on the chip at the cell's size
through ``python bench/control.py``."""
from __future__ import annotations

import pytest

from bench import control, harness
from bench.tests import tiny


@pytest.mark.parametrize("name", ["transe-fb15k.train", "transe-fb15k.eval",
                                  "distmult-fb15k.serve",
                                  "transe-fb15k.train-4chip"])
def test_control_fails_the_limits(name):
    c, config, mix, _ = tiny.cell(name)
    limits = harness.load_json(harness.find("limits", name))
    for seed in (3, 2**33 + 4):
        got = control.readings(config, mix, seed)
        correct = control.verdicts(got, limits)
        name = [k for k in got if k.startswith("control")][0]
        assert correct[name] is False, (got, limits)
