"""``chip_smoke.py``'s phases at a tiny size on the CPU, with the Pallas
kernel bodies pinned to the interpreter: the answers must match the
script's plain references and every failure counter must stay 0, so the
chip run cannot break unseen. The script's TPU check lives in ``main``
only; these tests call the phase functions."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas-interpret")


def test_query_phase_matches_references(interpret):
    report = chip_smoke.query_phase(n=1024, bs=256, seed=0)
    assert set(report["queries"]) == {
        "trace_gram", "select_gram_1_1", "overlay_x_y", "sddmm",
        "sddmm_sum_all", "sddmm_sum_rows", "sddmm_sum_cols", "join_d2d",
        "join_v2v"}
    c = report["counters"]
    for name in chip_smoke.KERNEL_COUNTERS + chip_smoke.EXECUTOR_COUNTERS:
        assert c[name] == 0, name
    for kernel in chip_smoke.TPU_KERNELS:
        assert c["kernel_dispatches"][
            f"{{backend=pallas-interpret,kernel={kernel}}}"] > 0
    q = report["queries"]
    assert q["overlay_x_y"]["backends"] == {"merge_join": "pallas-interpret"}
    assert q["sddmm"]["backends"] == {"masked_matmul": "pallas-interpret"}
    assert q["join_d2d"]["matches"] > 0 and q["join_v2v"]["matches"] > 0


def test_served_phase_matches_serial_collect(interpret):
    report = chip_smoke.served_phase(dim=64, bs=16, clients=60, seed=0)
    c = report["counters"]
    for name in (chip_smoke.KERNEL_COUNTERS + chip_smoke.EXECUTOR_COUNTERS
                 + chip_smoke.ENGINE_COUNTERS):
        assert c[name] == 0, name
    assert c["serve_completed"] == c["serve_submitted"] > 60
    assert report["templates_served"] >= 2
    assert max(report["parity_rel_err"].values()) <= 1e-2


def test_spmd_phase_on_four_host_devices():
    """The cross-chip phase on four virtual CPU devices, in a process of
    its own (the device count is fixed when JAX starts)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    code = ("import json, chip_smoke; "
            "r = chip_smoke.spmd_phase(4, n=1024, bs=256); "
            "print(json.dumps({k: r[k] for k in "
            "('mesh_devices', 'result_devices', 'counters')}))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["mesh_devices"] == [0, 1, 2, 3]
    assert r["result_devices"] == [0, 1, 2, 3]
    assert r["counters"]["executor_staged_sparse_spmd"] > 0


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out


@pytest.fixture
def restore_cache_dir():
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path,
                                           restore_cache_dir):
    import jax

    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads it


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                   restore_cache_dir):
    import jax

    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.setup_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
