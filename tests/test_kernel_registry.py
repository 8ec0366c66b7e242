"""Kernel backend registry: capability detection, backend parity
(dense oracle ≡ pallas-interpret) across all kernels × dtypes ×
non-square/unaligned shapes, and the block-size autotuner cache."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bloom import BloomParams, build
from repro.kernels import autotune, registry
from repro.kernels.merge_join import MODE_ALL, MODE_BOTH, MODE_X, MODE_Y

DTYPES = [jnp.float32, jnp.bfloat16]
# deliberately non-square and not multiples of the block size (pad paths)
SHAPES_MM = [(48, 40, 56, 16), (100, 36, 68, 32), (33, 17, 65, 16)]
SHAPES_MJ = [(48, 56, 16), (100, 68, 32), (33, 65, 16)]


@pytest.fixture(autouse=True)
def _isolated_autotune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune.clear_cache()
    yield
    autotune.clear_cache()


# ---------------------------------------------------------------------------
# Registry surface.
# ---------------------------------------------------------------------------

ALL_KERNELS = ("masked_matmul", "merge_join", "bloom_probe",
               "coo_expand", "sddmm_agg")


# the kernels whose bodies Mosaic compiles; bloom_probe and coo_expand
# gather from 1-D arrays, which it does not lower (docs/kernels.md).
# GSPMD cannot partition a Mosaic kernel, so mesh plans never pick them.
TPU_KERNELS = ("masked_matmul", "merge_join", "sddmm_agg")


def test_builtin_kernels_registered():
    assert set(registry.kernels()) >= set(ALL_KERNELS)
    for name in ALL_KERNELS:
        spec = registry.get(name)
        want = {registry.DENSE, registry.INTERPRET, registry.GPU}
        if name in TPU_KERNELS:
            want.add(registry.TPU)
        assert set(spec.backends()) == want


def test_capability_detection_cpu():
    avail = registry.available_backends()
    assert registry.DENSE in avail
    assert registry.INTERPRET in avail  # pallas imports in this container
    # default resolution on CPU is the dense oracle, never interpret
    assert registry.resolve_backend("masked_matmul") in (registry.DENSE,
                                                         registry.TPU)


def test_env_var_backend_override(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", registry.INTERPRET)
    assert registry.resolve_backend("merge_join") == registry.INTERPRET


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        registry.resolve_backend("masked_matmul", "cuda-graphs")
    with pytest.raises(KeyError):
        registry.get("nonexistent_kernel")


# ---------------------------------------------------------------------------
# pallas-gpu tier: registers everywhere, capability-gates cleanly.
# ---------------------------------------------------------------------------

def _fake_gpu(monkeypatch):
    """Pretend this process sits on a Triton-capable GPU host (the real
    impls are never *executed* through this — only selection logic is)."""
    monkeypatch.setattr(registry.compat, "has_triton", lambda: True)
    monkeypatch.setattr(registry.jax, "default_backend", lambda: "gpu")


def test_gpu_tier_gates_on_capability(monkeypatch):
    # this container has no GPU: the tier registers but never resolves
    assert registry.GPU not in registry.available_backends()
    with pytest.raises(RuntimeError, match="unavailable"):
        registry.resolve_backend("sddmm_agg", registry.GPU)
    # a Triton import alone is not enough — the default backend must be gpu
    monkeypatch.setattr(registry.compat, "has_triton", lambda: True)
    assert registry.GPU not in registry.available_backends()
    # with both, pallas-gpu becomes the native accelerator tier
    _fake_gpu(monkeypatch)
    assert registry.GPU in registry.available_backends()
    for name in ALL_KERNELS:
        assert registry.resolve_backend(name) == registry.GPU


def test_gpu_quarantine_degrades_to_next_tier(monkeypatch, rng):
    """A quarantined pallas-gpu backend is skipped outright: dispatch
    degrades down the capability ladder without ever attempting it."""
    _fake_gpu(monkeypatch)
    registry.BREAKER.reset()
    try:
        for _ in range(registry.BREAKER.threshold):
            registry.BREAKER.record_failure(registry.GPU)
        assert registry.BREAKER.state(registry.GPU) == "open"
        a = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
        mask = jnp.ones((2, 2), bool)
        # the gpu impl would fail if actually run on this CPU host — the
        # quarantine skip is what keeps this dispatch alive
        out = registry.dispatch("masked_matmul", a, b, mask,
                                backend=registry.GPU, block_size=16)
        want = registry.dispatch("masked_matmul", a, b, mask,
                                 backend=registry.DENSE, block_size=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5)
    finally:
        registry.BREAKER.reset()


def test_fallback_chain_walks_gpu_tpu_dense(monkeypatch):
    """gpu → tpu → dense: a failing gpu impl lands on the NEXT tier, not
    straight on the oracle (uses a scratch kernel so no real pallas body
    has to fail on purpose)."""
    monkeypatch.setattr(
        registry, "available_backends",
        lambda: (registry.DENSE, registry.INTERPRET, registry.TPU,
                 registry.GPU))
    name = "_test_chain_kernel"
    registry.register(name, registry.DENSE)(lambda *a, tiles=None: "dense")
    registry.register(name, registry.TPU)(lambda *a, tiles=None: "tpu")

    def gpu_impl(*a, tiles=None):
        raise RuntimeError("boom")

    registry.register(name, registry.GPU)(gpu_impl)
    registry.BREAKER.reset()
    try:
        assert registry.dispatch(name, backend=registry.GPU) == "tpu"
        # the failure fed the breaker (one hop per failed dispatch)
        assert registry.BREAKER._entry(registry.GPU)[0] == 1
    finally:
        registry.BREAKER.reset()
        registry._REGISTRY.pop(name, None)


def test_fault_injected_gpu_dispatch_degrades(monkeypatch):
    """REPRO_FAULTS kernel_dispatch:backend=pallas-gpu scope-matches the
    chosen gpu dispatch only; containment degrades it down the chain and
    the fallback hop runs clean."""
    from repro.runtime import faults
    monkeypatch.setattr(
        registry, "available_backends",
        lambda: (registry.DENSE, registry.INTERPRET, registry.GPU))
    name = "_test_fault_kernel"
    registry.register(name, registry.DENSE)(lambda *a, tiles=None: "dense")
    registry.register(name, registry.GPU)(lambda *a, tiles=None: "gpu")
    registry.BREAKER.reset()
    try:
        with faults.inject("kernel_dispatch:backend=pallas-gpu"):
            assert registry.dispatch(name, backend=registry.GPU) == "dense"
            # dense dispatches never match the scope filter
            assert registry.dispatch(name, backend=registry.DENSE) == "dense"
        assert registry.dispatch(name, backend=registry.GPU) == "gpu"
    finally:
        registry.BREAKER.reset()
        registry._REGISTRY.pop(name, None)


# ---------------------------------------------------------------------------
# planned_backend: cost-priced plan-time choice (+ kill switch).
# ---------------------------------------------------------------------------

class _StubModel:
    version = "stub"

    def __init__(self, prices):
        self._prices = prices

    def model_for(self, device):
        return self._prices.get(device)

    def predict(self, features, device=None):
        return self._prices[device]


def test_planned_backend_prices_candidates(monkeypatch):
    from repro.core import calibrate
    monkeypatch.setattr(
        registry, "available_backends",
        lambda: (registry.DENSE, registry.INTERPRET, registry.TPU))
    feats = {k: 1.0 for k in calibrate.FEATURES}
    dense_key = calibrate.device_key(backend=registry.DENSE)
    tpu_key = calibrate.device_key(backend=registry.TPU)
    # static policy would pick the native tier (pallas-tpu); the fitted
    # model prices dense cheaper, so pricing overrides it
    model = _StubModel({dense_key: 0.1, tpu_key: 2.0})
    assert registry.planned_backend("sddmm_agg", cost_model=model,
                                    features=feats) == registry.DENSE
    flipped = _StubModel({dense_key: 2.0, tpu_key: 0.1})
    assert registry.planned_backend("sddmm_agg", cost_model=flipped,
                                    features=feats) == registry.TPU
    # kill switch: fleet-wide revert to the static policy
    monkeypatch.setenv("REPRO_BACKEND_CHOICE", "static")
    assert registry.planned_backend("sddmm_agg", cost_model=model,
                                    features=feats) == registry.TPU
    monkeypatch.delenv("REPRO_BACKEND_CHOICE")
    # an explicit pin always wins over pricing
    assert registry.planned_backend("sddmm_agg", registry.DENSE,
                                    cost_model=model,
                                    features=feats) == registry.DENSE
    # a one-sided fit must not let an unpriced backend win by default
    lone = _StubModel({dense_key: 0.1})
    assert registry.planned_backend("sddmm_agg", cost_model=lone,
                                    features=feats) == registry.TPU


def _tpu_available(monkeypatch):
    monkeypatch.setattr(
        registry, "available_backends",
        lambda: (registry.DENSE, registry.INTERPRET, registry.TPU))


@pytest.mark.parametrize("kernel", TPU_KERNELS)
@pytest.mark.parametrize("block_size,n_workers,want", [
    (256, 1, registry.TPU),       # the MXU-aligned single-chip plan
    (128, 1, registry.TPU),
    (8, 1, registry.DENSE),       # no (8, 128) tiling for an 8² block
    (200, 1, registry.DENSE),
    (256, 4, registry.DENSE),     # GSPMD cannot partition Mosaic kernels
])
def test_planned_backend_gates_tpu_tiling(monkeypatch, kernel, block_size,
                                          n_workers, want):
    _tpu_available(monkeypatch)
    assert registry.planned_backend(kernel, block_size=block_size,
                                    n_workers=n_workers) == want


def test_pinned_tpu_rejects_untileable_block_size(monkeypatch):
    _tpu_available(monkeypatch)
    with pytest.raises(ValueError):
        registry.resolve_backend("masked_matmul", registry.TPU,
                                 block_size=8)
    with pytest.raises(ValueError):
        registry.resolve_backend("merge_join", registry.TPU,
                                 block_size=256, n_workers=4)


def test_builder_plans_dense_for_untileable_block(monkeypatch, rng):
    """A plan whose block size no TPU tiling accepts carries ``dense`` on
    its kernel nodes, so dispatch never tries (and fails) the TPU impl."""
    from repro.core import Session
    from tests.conftest import sparse
    _tpu_available(monkeypatch)
    for bs, want in ((8, registry.DENSE), (128, registry.TPU)):
        s = Session(block_size=bs, n_workers=1)
        X = s.load(sparse(rng, 256, 256, 0.01), "X")
        W = s.load(rng.normal(size=(256, 8)).astype(np.float32), "W")
        H = s.load(rng.normal(size=(8, 256)).astype(np.float32), "H")
        plan = X.emul(W.multiply(H)).physical_plan()
        backends = {n.backend for n in plan.nodes if n.kernel}
        assert backends == {want}, (bs, backends)


def test_fallback_is_logged_once_per_kernel_backend(monkeypatch, caplog):
    monkeypatch.setattr(
        registry, "available_backends",
        lambda: (registry.DENSE, registry.INTERPRET, registry.TPU))
    name = "_test_warn_kernel"
    registry.register(name, registry.DENSE)(lambda *a, tiles=None: "dense")

    def broken(*a, tiles=None):
        raise RuntimeError("mosaic refused")

    registry.register(name, registry.TPU)(broken)
    registry.BREAKER.reset()
    try:
        with caplog.at_level("WARNING", logger=registry.__name__):
            for _ in range(2):
                assert registry.dispatch(name) == "dense"
        hits = [r for r in caplog.records if name in r.getMessage()]
        assert len(hits) == 1
        assert "mosaic refused" in hits[0].getMessage()
    finally:
        registry.BREAKER.reset()
        registry._REGISTRY.pop(name, None)


def test_planned_backend_static_without_model():
    # no model / no features → exactly the dispatch-time policy
    assert registry.planned_backend("coo_expand") \
        == registry.resolve_backend("coo_expand")
    assert registry.planned_backend("coo_expand", features={"ops": 1.0}) \
        == registry.resolve_backend("coo_expand")


# ---------------------------------------------------------------------------
# Parity sweep: dense oracle ≡ pallas-interpret, via the registry.
# ---------------------------------------------------------------------------

def _tol(dtype):
    return dict(atol=1e-4 if dtype == jnp.float32 else 6e-2, rtol=1e-2)


@pytest.mark.parametrize("m,k,n,bs", SHAPES_MM)
@pytest.mark.parametrize("dtype", DTYPES)
def test_parity_masked_matmul(rng, m, k, n, bs, dtype):
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    b = jnp.asarray(rng.normal(size=(k, n)), dtype)
    gm, gn = -(-m // bs), -(-n // bs)
    mask = jnp.asarray(rng.uniform(size=(gm, gn)) < 0.5)
    dense = registry.dispatch("masked_matmul", a, b, mask,
                              backend=registry.DENSE, block_size=bs)
    interp = registry.dispatch("masked_matmul", a, b, mask,
                               backend=registry.INTERPRET, block_size=bs)
    assert interp.shape == dense.shape == (m, n)
    np.testing.assert_allclose(np.asarray(interp, np.float32),
                               np.asarray(dense, np.float32), **_tol(dtype))


@pytest.mark.parametrize("m,n,bs", SHAPES_MJ)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("mode", [MODE_BOTH, MODE_X, MODE_Y, MODE_ALL])
def test_parity_merge_join(rng, m, n, bs, dtype, mode):
    a = jnp.asarray(rng.normal(size=(m, n)), dtype)
    b = jnp.asarray(rng.normal(size=(m, n)), dtype)
    gm, gn = -(-m // bs), -(-n // bs)
    ma = jnp.asarray(rng.uniform(size=(gm, gn)) < 0.5)
    mb = jnp.asarray(rng.uniform(size=(gm, gn)) < 0.5)
    f = lambda x, y: x * y + 0.5 * y
    dense = registry.dispatch("merge_join", a, b, ma, mb,
                              backend=registry.DENSE, merge=f, mode=mode,
                              block_size=bs)
    interp = registry.dispatch("merge_join", a, b, ma, mb,
                               backend=registry.INTERPRET, merge=f,
                               mode=mode, block_size=bs)
    np.testing.assert_allclose(np.asarray(interp, np.float32),
                               np.asarray(dense, np.float32), **_tol(dtype))


@pytest.mark.parametrize("n,log2_bits", [(1000, 12), (5000, 14)])
def test_parity_bloom_probe(rng, n, log2_bits):
    vals = jnp.asarray(np.round(rng.normal(size=n), 1).astype(np.float32))
    params = BloomParams(log2_bits=log2_bits, num_hashes=3)
    words = build(vals[: n // 2], params)
    dense = registry.dispatch("bloom_probe", words, vals,
                              backend=registry.DENSE, num_hashes=3,
                              log2_bits=log2_bits)
    interp = registry.dispatch("bloom_probe", words, vals,
                               backend=registry.INTERPRET, num_hashes=3,
                               log2_bits=log2_bits)
    assert np.array_equal(np.asarray(dense), np.asarray(interp))
    members = np.asarray(vals[: n // 2])
    assert np.asarray(interp)[: n // 2][members != 0].all()


def test_parity_via_executor_pinned_backend(rng):
    """The executor's masked-matmul pattern gives identical results with the
    kernel backend pinned to interpret vs the dense default."""
    from repro.core import Session
    from repro.core.executor import Executor
    from tests.conftest import sparse
    a = sparse(rng, 48, 48, 0.05)
    w = rng.normal(size=(48, 8)).astype(np.float32)
    h = rng.normal(size=(8, 48)).astype(np.float32)
    s = Session(block_size=16)
    A, W, H = s.load(a), s.load(w), s.load(h)
    plan = A.emul(W.multiply(H)).plan
    outs = {}
    for backend in (registry.DENSE, registry.INTERPRET):
        ex = Executor(s.env, mode="sparse", block_size=16,
                      kernel_backend=backend)
        outs[backend] = np.asarray(ex.run(plan).value)
        assert ex.stats["masked_matmuls"] == 1
    np.testing.assert_allclose(outs[registry.DENSE],
                               outs[registry.INTERPRET], atol=1e-4)


def test_executor_backend_pin_reaches_join_kernels(rng):
    """The kernel_backend pin must flow through join_sparse into the
    overlay merge_join and V2V bloom_probe dispatches, not just the
    executor's own masked-matmul site."""
    from repro.core import Session
    from repro.core.executor import Executor
    from tests.conftest import sparse
    a = sparse(rng, 64, 64, 0.05, round_vals=True)
    b = sparse(rng, 64, 64, 0.05, round_vals=True)
    a[:16, :16] = 0  # force a dead block: the overlay must take the
    b[:16, :16] = 0  # partial-mask merge_join dispatch, not the all-live
    s = Session(block_size=16)  # plain-merge shortcut
    A, B = s.load(a, "A"), s.load(b, "B")
    plans = {
        "overlay": A.join(B, "RID=RID AND CID=CID",
                          lambda x, y: x * y).plan,
        "v2v": A.join(B, "VAL=VAL", lambda x, y: x + y).plan,
    }
    for tag, plan in plans.items():
        outs = []
        for backend in (registry.DENSE, registry.INTERPRET):
            ex = Executor(s.env, mode="sparse", block_size=16,
                          kernel_backend=backend)
            r = ex.run(plan)
            outs.append(np.asarray(r.value if hasattr(r, "value")
                                   else r.to_dense()))
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-5, err_msg=tag)


# ---------------------------------------------------------------------------
# Autotuner.
# ---------------------------------------------------------------------------

def test_autotune_second_lookup_is_cache_hit():
    calls = []

    def runner(tiles):
        calls.append(dict(tiles))
        return None

    args = ("masked_matmul", [(64, 32), (32, 64)], "float32",
            registry.INTERPRET)
    first = autotune.best_tiles(*args, runner=runner)
    assert first in [dict(t) for t in registry.get(
        "masked_matmul").tile_grid]
    n_timed = len(calls)
    assert n_timed > 0
    second = autotune.best_tiles(*args, runner=runner)
    assert second == first
    assert len(calls) == n_timed  # no re-timing on the second lookup


def test_autotune_shape_bucketing_shares_entries():
    key_a = autotune.cache_key("k", [(65, 100)], "float32", "dense")
    key_b = autotune.cache_key("k", [(128, 128)], "float32", "dense")
    assert key_a == key_b  # both bucket to (128, 128)
    assert autotune.cache_key("k", [(64, 64)], "float32", "dense") != key_a


def test_autotune_graceful_fallback_without_timing():
    # no runner at all → kernel defaults, nothing cached
    tiles = autotune.best_tiles("masked_matmul", [(64, 64)], "float32",
                                registry.DENSE)
    assert tiles == registry.get("masked_matmul").default_tiles
    assert autotune.cached_tiles("masked_matmul", [(64, 64)], "float32",
                                 registry.DENSE) is None

    # every candidate fails to time → defaults, still nothing cached
    def broken(tiles):
        raise RuntimeError("no timer on this host")

    tiles = autotune.best_tiles("bloom_probe", [(128,)], "float32",
                                registry.DENSE, runner=broken)
    assert tiles == registry.get("bloom_probe").default_tiles
    assert autotune.cached_tiles("bloom_probe", [(128,)], "float32",
                                 registry.DENSE) is None


def test_autotune_disk_round_trip():
    best = autotune.best_tiles("bloom_probe", [(4096,)], "float32",
                               registry.INTERPRET, runner=lambda t: None)
    path = autotune.save_cache()
    autotune.clear_cache()  # drop the in-process cache; disk survives
    hit = autotune.cached_tiles("bloom_probe", [(4096,)], "float32",
                                registry.INTERPRET)
    assert hit == best, path


def test_autotune_key_is_device_and_backend_scoped():
    kind = autotune.device_kind()
    assert "|" not in kind and " " not in kind  # scrubbed key segment
    key = autotune.cache_key("k", [(64, 64)], "float32", registry.DENSE)
    assert key.endswith(f"|{registry.DENSE}|{kind}")
    # tiles tuned for one backend never serve another
    assert key != autotune.cache_key("k", [(64, 64)], "float32",
                                     registry.GPU)


def test_autotune_stats_prove_warm_start():
    """The fleet acceptance check: a covered bucket costs zero trials on
    the second pass, and cache hits are visible as warm_hits."""
    autotune.reset_stats()
    args = ("masked_matmul", [(64, 32), (32, 64)], "float32",
            registry.INTERPRET)
    autotune.best_tiles(*args, runner=lambda t: None)
    cold = autotune.tune_stats()
    assert cold["trials"] > 0
    # warm pass: served from cache, no new trials, one warm hit
    autotune.best_tiles(*args, runner=lambda t: None)
    warm = autotune.tune_stats()
    assert warm["trials"] == cold["trials"]
    assert warm["warm_hits"] == cold["warm_hits"] + 1
    # and the same holds across a process "restart" via the disk artifact
    autotune.save_cache()
    autotune.clear_cache()
    autotune.reset_stats()
    autotune.load_cache()
    autotune.best_tiles(*args, runner=lambda t: None)
    assert autotune.tune_stats() == {"trials": 0, "warm_hits": 1}


def test_autotune_save_is_write_temp_then_rename(tmp_path, monkeypatch):
    """Concurrent-writer tolerance is pinned to the mechanism: saves go
    through a pid-suffixed temp file in the target dir + os.replace, so a
    racing reader can never observe a torn JSON."""
    import os as osmod
    target = tmp_path / "fleet" / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(target))
    replaced = []
    real = osmod.replace

    def spy(src, dst):
        replaced.append((str(src), str(dst)))
        assert osmod.path.exists(src)  # fully written before the swap
        real(src, dst)

    monkeypatch.setattr(autotune.os, "replace", spy)
    autotune._CACHE["k|64|float32|dense|cpu:cpu"] = {"bk": 64}
    autotune.save_cache()
    (src, dst), = replaced
    assert dst == str(target)
    assert src == f"{target}.{osmod.getpid()}.tmp"
    assert not osmod.path.exists(src)  # temp is gone, target is whole
    import json
    blob = json.load(open(target))
    assert blob["_schema"] == autotune._SCHEMA
    assert blob["entries"]["k|64|float32|dense|cpu:cpu"] == {"bk": 64}


def _artifact(path, entries, schema=None):
    import json
    path.write_text(json.dumps(
        {"_schema": autotune._SCHEMA if schema is None else schema,
         "entries": entries}))
    return str(path)


def test_autotune_merge_later_wins_and_rejects_schema(tmp_path):
    import json
    a = _artifact(tmp_path / "a.json",
                  {"k1|…|cpu": {"bk": 64}, "k2|…|cpu": {"bt": 256}})
    b = _artifact(tmp_path / "b.json",
                  {"k1|…|cpu": {"bk": 128}, "k3|…|gpu": {"bs": 4096}})
    out = str(tmp_path / "merged.json")
    path, n = autotune.merge_files([a, b], out)
    assert (path, n) == (out, 3)
    entries = json.load(open(out))["entries"]
    assert entries["k1|…|cpu"] == {"bk": 128}  # later input wins
    assert set(entries) == {"k1|…|cpu", "k2|…|cpu", "k3|…|gpu"}
    # a schema-1 artifact (pre device-kind keys) must be refused loudly
    old = _artifact(tmp_path / "old.json", {"k|64|f32|dense": {"bk": 64}},
                    schema=1)
    with pytest.raises(ValueError, match="schema"):
        autotune.merge_files([a, old], str(tmp_path / "bad.json"))


def test_autotune_merge_cli(tmp_path, capsys):
    a = _artifact(tmp_path / "a.json", {"ka": {"bk": 64}})
    b = _artifact(tmp_path / "b.json", {"kb": {"bt": 512}})
    out = str(tmp_path / "m.json")
    assert autotune._main(["merge", a, b, "-o", out]) == 0
    assert "merged 2 artifacts" in capsys.readouterr().out
    bad = _artifact(tmp_path / "bad.json", {"k": {"x": 1}}, schema=99)
    assert autotune._main(["merge", a, bad, "-o", out]) == 1


def test_autotuned_dispatch_reads_cache(rng, monkeypatch):
    """REPRO_AUTOTUNE=1 makes dispatch consult the cache (and still give
    bit-identical results — tiles change scheduling, not math)."""
    vals = jnp.asarray(np.round(rng.normal(size=600), 1).astype(np.float32))
    params = BloomParams(log2_bits=12, num_hashes=2)
    words = build(vals, params)
    base = registry.dispatch("bloom_probe", words, vals,
                             backend=registry.INTERPRET, num_hashes=2,
                             log2_bits=12)
    key = autotune.cache_key("bloom_probe",
                             [tuple(words.shape), tuple(vals.shape)],
                             str(vals.dtype), registry.INTERPRET)
    autotune._CACHE[key] = {"bs": 256}
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    tuned = registry.dispatch("bloom_probe", words, vals,
                              backend=registry.INTERPRET, num_hashes=2,
                              log2_bits=12)
    assert np.array_equal(np.asarray(base), np.asarray(tuned))
