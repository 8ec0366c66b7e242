"""A run with its timed path broken underneath reports ``correct``
false: the harness's check is shown to catch each fault a cell can
have. (A cell on one chip has no exchange between chips, and no cell
here averages over a batch.)"""
import pytest

import run
from conftest import tiny_config
from lib import spec
from lib.backends import ProgramBackend


class Broken:
    """The program's backend with one fault planted in it."""

    def __init__(self, inner, fault):
        self.inner, self.fault, self.loaded = inner, fault, set()

    @property
    def arrays(self):
        return self.inner.arrays

    def load(self, name, array):
        if self.fault == "unchanged" and name in self.loaded:
            return                       # the update never takes effect
        self.loaded.add(name)
        self.inner.load(name, array)

    def collect(self, expr):
        out = self.inner.collect(expr)
        if self.fault == "altered":
            # the first half of the answer's rows 10% off
            half = -(-out.shape[0] // 2)
            return out.at[:half].multiply(1.1)
        return out


@pytest.mark.parametrize("fault", [None, "unchanged", "altered"])
def test_the_check_catches_the_fault(bench, fault):
    hooks = None
    if fault is not None:
        class Hooks:
            @staticmethod
            def wrap_backend(b):
                assert isinstance(b, ProgramBackend)
                return Broken(b, fault)
        hooks = Hooks
    for cell in bench["workloads"]:
        result, _ = run.run_cell(
            bench, cell["name"], 2**31 + 11, 0.5, False,
            cfg=tiny_config(cell["config"]), require_chip=False,
            hooks=hooks)
        assert result["correct"] is (fault is None), result["checks"]
        assert list(result)[-1] == "checks"
