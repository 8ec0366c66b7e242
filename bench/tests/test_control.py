"""The control of each cell's check: the plain reference one precision
step below what the configuration states, put in the program's place,
must come out as not correct; the program itself must not. At a tiny
size here; ``bench/control.py`` takes the same readings on the chip at
the cells' own sizes."""
import pytest

import control
from conftest import TINY, tiny_config
from lib import spec


def _cells(bench):
    return [w["name"] for w in bench["workloads"]]


@pytest.mark.parametrize("program", [False, True],
                         ids=["control", "program"])
def test_control_fails_the_limit_and_the_program_passes(bench, program):
    for cell in _cells(bench):
        c = spec.cell(bench, cell)
        assert c["config"] in TINY, cell
        cfg = tiny_config(c["config"])
        traffic = spec.traffic(c["traffic"])
        limits = traffic["check"]["limits"]
        mod = spec.config_module(c["config"])
        for seed in (1, 2, 2**31 + 3):
            got = control.readings(cfg, mod, traffic, seed, program)
            failing = [n for n, limit in limits.items() if got[n] > limit]
            assert bool(failing) is not program, (cell, seed, got, limits)
