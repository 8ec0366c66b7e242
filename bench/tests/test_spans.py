import types

from lib import spans


class S:
    def __init__(self, name, duration, children=()):
        self.name, self.duration, self.children = name, duration, list(
            children)


def tree():
    return S("window", 10.0, [
        S("optimize", 3.0, [S("lower", 1.0, [S("mask_propagation", 0.5)]),
                            S("optimize", 0.2)]),
        S("lower", 0.4, [S("mask_propagation", 0.1)]),
        S("mask_propagation", 0.3),
        S("stage_compile", 2.0, [S("stage_compile", 1.5)]),
    ])


def test_inclusive_counts_each_outermost_span_once():
    root = tree()
    assert spans.inclusive_s(root, ["optimize"]) == (3.0, 1)
    assert spans.inclusive_s(root, ["stage_compile"]) == (2.0, 1)
    # the planner outside the optimizer's dry runs
    got = spans.inclusive_s(root, ["lower", "mask_propagation"],
                            not_under=["optimize"])
    assert got[0] == 0.4 + 0.3 and got[1] == 2


def test_per_step_ms_none_without_spans():
    ctx = types.SimpleNamespace(root=tree(), steps=4)
    assert spans.per_step_ms(ctx, ["optimize"]) == 3.0 / 4 * 1e3
    assert spans.per_step_ms(ctx, ["schemes_dp"]) is None
