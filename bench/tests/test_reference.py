"""The plain reference agrees with ``Session.collect()`` on every query
the traffic sends and on every operation of the query language, at a
tiny size."""
import pytest

import run
from conftest import tiny_config
from lib import compare, dsl, spec
from lib.backends import ProgramBackend
from lib.reference import Reference

# every operation of lib.dsl, over the netflix-pnmf catalog (A, W, H)
LANGUAGE = [
    ["sum", ["emul", "A", ["multiply", "W", "H"]], "a"],
    ["sum", ["ediv", "A", ["multiply", "W", "H"]], "c"],
    ["nnz", "A", "r"],
    ["nnz", ["t", "A"], "a"],
    ["add", ["multiply", "W", "H"], "A"],
]


def _queries(traffic):
    return [e for s in traffic["steps"] for e in s["collect"].values()]


@pytest.mark.parametrize("extra", [False, True],
                         ids=["traffic", "language"])
def test_reference_matches_the_program(bench, extra):
    from repro.core import Session
    for c in bench["workloads"]:
        cfg = tiny_config(c["config"])
        data = spec.config_module(c["config"]).catalog(cfg,
                                                       run.seed_key(3))
        program = ProgramBackend(Session(block_size=cfg["block_size"],
                                         mode=cfg["mode"], n_workers=1))
        ref = Reference.for_config(cfg)
        for name, value in data.items():
            program.load(name, value)
            ref.load(name, value)
        exprs = LANGUAGE if extra else _queries(spec.traffic(c["traffic"]))
        for expr in exprs:
            dsl.validate(expr)
            err = compare.rel_error(program.collect(expr), ref.eval(expr))
            assert err < 1e-6, (expr, err)


def test_language_is_checked():
    for bad in (["sum", "A", "x"], ["t", "A", "B"], ["join", "A", "B"],
                ["multiply", "A"]):
        with pytest.raises(ValueError):
            dsl.validate(bad)


def test_rel_error():
    import jax.numpy as jnp
    w = jnp.array([[3.0, 4.0]])
    assert compare.rel_error(w, w) == 0.0
    assert compare.rel_error(w * 1.1, w) == pytest.approx(0.1, rel=1e-5)
    assert compare.rel_error(w, w[:, :1]) == float("inf")
    assert compare.rel_error(jnp.zeros(2), jnp.zeros(2)) == 0.0
