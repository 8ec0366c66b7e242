"""Every cell, configuration, traffic mix and metric of BENCHMARK.json
is found by name, and the file keeps to the benchmark's format."""
import re

from lib import dsl, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_cells_find_their_files(bench):
    for cell in bench["workloads"]:
        cfg = spec.config(cell["config"])
        assert cfg["name"] == cell["config"]
        assert callable(spec.config_module(cell["config"]).catalog)
        traffic = spec.traffic(cell["traffic"])
        assert callable(spec.shape(traffic["shape"]).Traffic)
        assert traffic["check"]["limits"]
        for step in traffic["steps"]:
            assert callable(spec.rule(step["update"]["rule"]).update)
            for expr in step["collect"].values():
                dsl.validate(expr)


def test_config_entries_point_at_their_files(bench):
    for c in bench["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert set(c["reduced"]) == set(spec.config(c["name"])["reduced"])


def test_metrics_have_readers_and_names(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
        assert set(m.get("workloads", cells)) <= cells
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert spec.cell_metrics(bench, w["name"], "per_layer")
        e2e = [m["name"]
               for m in spec.cell_metrics(bench, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2


def test_readers_are_found_by_base_name():
    assert spec.metric_base("optimize_ms.iter") == "optimize_ms"
    assert spec.metric_reader("device_idle.iter").__name__ \
        == "bench_metric_device_idle"
    assert spec.metric_reader("setup_s").read(
        type("ctx", (), {"setup_s": 2.5})) == 2.5
    assert spec.shape("pipeline") is spec.shape("pipeline")
