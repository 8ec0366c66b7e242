"""Kernel backend registry: logical kernel names → per-backend physical impls.

The paper pushes relational operators down to hardware kernels (masked
matmul for select/agg pipelines, merge-function overlay joins, Bloom
probes). Callers above this layer (``core.executor``, ``core.joins``, the
benchmarks) name the *logical* kernel; the registry picks the *physical*
implementation at call time from runtime capability detection:

* ``dense``            — pure-jnp oracle (``ref.py``); always available, and
                         the correctness reference every backend is tested
                         against.
* ``pallas-interpret`` — the Pallas kernel body run by the interpreter;
                         always available (CPU CI included).
* ``pallas-tpu``       — the compiled Mosaic kernel; available when the
                         default JAX backend is TPU. Registered only for the
                         kernels whose bodies Mosaic compiles, and planned
                         only for block sizes its tiling accepts and for
                         single-worker plans (see ``accepts``).
* ``pallas-gpu``       — the same kernel body through the Triton lowering;
                         available when ``jax.experimental.pallas.triton``
                         imports AND the default JAX backend is GPU.

Selection order: explicit ``backend=`` argument > ``REPRO_KERNEL_BACKEND``
env var > the native accelerator tier (``pallas-tpu`` on TPU,
``pallas-gpu`` on GPU) > ``dense``. Interpret mode is opt-in (it validates
kernel bodies; it is never the fastest CPU path). With a calibrated cost
model in hand, ``planned_backend`` can instead *price* the candidate
backends per node (``REPRO_BACKEND_CHOICE=static`` disables that).

Registering a new kernel:

    from repro.kernels import registry

    @registry.register("my_kernel", registry.DENSE)
    def _my_kernel_dense(x, *, tiles=None): ...

    @registry.register("my_kernel", registry.INTERPRET,
                       tile_grid=({"bm": 64}, {"bm": 128}),
                       default_tiles={"bm": 128})
    def _my_kernel_interp(x, *, tiles=None): ...

Every impl of one logical kernel must share a signature and accept a
``tiles`` kwarg (a dict of block sizes, or None for defaults) so the
autotuner (``repro.kernels.autotune``) can drive any backend uniformly.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.kernels import compat
from repro.runtime import faults

_LOG = logging.getLogger(__name__)

DENSE = "dense"
INTERPRET = "pallas-interpret"
TPU = "pallas-tpu"
GPU = "pallas-gpu"
BACKENDS = (DENSE, INTERPRET, TPU, GPU)

# Degradation order per chosen backend: quarantine or failure walks DOWN
# the capability ladder (gpu → tpu → dense) instead of jumping straight to
# the oracle, so a machine with both accelerator tiers keeps its second
# fastest path. Entries are filtered against the kernel's impls and this
# process's available backends at dispatch time.
_FALLBACK_ORDER = {
    GPU: (TPU, DENSE),
    TPU: (DENSE,),
    INTERPRET: (DENSE,),
}

_BACKEND_ENV = "REPRO_KERNEL_BACKEND"
_BACKEND_CHOICE_ENV = "REPRO_BACKEND_CHOICE"
_AUTOTUNE_ENV = "REPRO_AUTOTUNE"
_BREAKER_THRESHOLD_ENV = "REPRO_BREAKER_THRESHOLD"
_BREAKER_COOLDOWN_ENV = "REPRO_BREAKER_COOLDOWN"

# Mosaic tiles f32 blocks in multiples of (8, 128): a kernel whose square
# block is ``block_size`` on a side compiles only for multiples of 128 (the
# MXU width).
TPU_BLOCK_MULTIPLE = 128


def accepts(backend: str, block_size: Optional[int],
            n_workers: int = 1) -> bool:
    """Whether ``backend`` can run a dispatch at ``block_size`` (None:
    the kernel takes no block size) inside a plan over ``n_workers``
    workers. Only ``pallas-tpu`` is constrained: by Mosaic's tiling, and
    to single-worker plans, because XLA's SPMD partitioner cannot split a
    Mosaic kernel across a mesh. Resolution passes over a backend that
    does not accept, at plan time and at dispatch alike, instead of
    letting it fail and fall back."""
    if backend != TPU:
        return True
    return n_workers == 1 and (block_size is None
                               or block_size % TPU_BLOCK_MULTIPLE == 0)


@dataclasses.dataclass
class KernelSpec:
    """One logical kernel: its per-backend impls and autotune metadata."""
    name: str
    impls: Dict[str, Callable] = dataclasses.field(default_factory=dict)
    tile_grid: Tuple[Dict[str, int], ...] = ()
    default_tiles: Optional[Dict[str, int]] = None

    def backends(self) -> Tuple[str, ...]:
        return tuple(b for b in BACKENDS if b in self.impls)


_REGISTRY: Dict[str, KernelSpec] = {}
_BUILTINS_LOADED = False


def _ensure_builtins() -> None:
    """Importing ``repro.kernels.ops`` registers the built-in kernels."""
    global _BUILTINS_LOADED
    if not _BUILTINS_LOADED:
        import repro.kernels.ops  # noqa: F401  (side effect: registration)
        # only after success: a failed import is removed from sys.modules,
        # so the next call retries (and re-raises the real error) instead
        # of reporting a misleading empty registry
        _BUILTINS_LOADED = True


def register(name: str, backend: str, *,
             tile_grid: Tuple[Dict[str, int], ...] = (),
             default_tiles: Optional[Dict[str, int]] = None):
    """Decorator: register ``fn`` as the ``backend`` impl of kernel ``name``.

    ``tile_grid``/``default_tiles`` attach autotune metadata to the spec;
    the first registration to provide them wins (they describe the kernel,
    not the backend).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")

    def deco(fn: Callable) -> Callable:
        spec = _REGISTRY.setdefault(name, KernelSpec(name=name))
        spec.impls[backend] = fn
        if tile_grid and not spec.tile_grid:
            spec.tile_grid = tuple(dict(t) for t in tile_grid)
        if default_tiles and not spec.default_tiles:
            spec.default_tiles = dict(default_tiles)
        return fn

    return deco


def get(name: str) -> KernelSpec:
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no kernel {name!r} registered; have {sorted(_REGISTRY)}"
        ) from None


def kernels() -> Tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def available_backends() -> Tuple[str, ...]:
    """Backends runnable on THIS process, by runtime capability detection.

    ``pallas-gpu`` requires all three of: Pallas importing, the Triton
    lowering importing (GPU-enabled jaxlibs only — see ``compat``), and
    the default JAX backend actually being a GPU. On CPU/TPU machines the
    tier simply never appears here, so it registers everywhere yet can
    never be dispatched to by accident.
    """
    out = [DENSE, INTERPRET]
    if jax.default_backend() == "tpu":
        out.append(TPU)
    if compat.has_triton() and jax.default_backend() == "gpu":
        out.append(GPU)
    return tuple(out)


def resolve_backend(name: str, backend: Optional[str] = None,
                    block_size: Optional[int] = None,
                    n_workers: int = 1) -> str:
    """Pick the physical backend for one dispatch of kernel ``name``.

    ``block_size`` is the dispatch's tile granularity, for kernels that
    take one, and ``n_workers`` the worker count of the plan it runs in: a
    native impl that does not accept them is passed over (the dense oracle
    runs instead), and an explicit pin to such an impl is an error.
    """
    spec = get(name)
    avail = available_backends()
    choice = backend or os.environ.get(_BACKEND_ENV) or None
    if choice is not None:
        if choice not in BACKENDS:
            raise ValueError(
                f"unknown backend {choice!r}; expected one of {BACKENDS}")
        if choice not in avail:
            raise RuntimeError(
                f"backend {choice!r} unavailable here (have {avail})")
        if choice not in spec.impls:
            raise KeyError(
                f"kernel {name!r} has no {choice!r} impl "
                f"(has {spec.backends()})")
        if not accepts(choice, block_size, n_workers):
            raise ValueError(
                f"kernel {name!r} on {choice!r} does not run at block size "
                f"{block_size} in a {n_workers}-worker plan")
        return choice
    for native in (TPU, GPU):  # at most one can be available
        if (native in avail and native in spec.impls
                and accepts(native, block_size, n_workers)):
            return native
    if DENSE not in spec.impls:
        raise KeyError(
            f"kernel {name!r} has no {DENSE!r} impl (has {spec.backends()});"
            " every kernel must register a dense oracle")
    return DENSE


def planned_backend(name: str, backend: Optional[str] = None, *,
                    block_size: Optional[int] = None, n_workers: int = 1,
                    cost_model=None, features=None) -> str:
    """Resolve kernel ``name``'s backend at *plan time*.

    The physical planner (``repro.plan.builder``) annotates each
    kernel-dispatching DAG node with the backend it will run on. The base
    policy is exactly what ``dispatch`` applies at call time (explicit
    arg > ``REPRO_KERNEL_BACKEND`` > native accelerator capability >
    dense), so plan annotations and runtime dispatch can never disagree.

    On top of that, when a calibrated ``cost_model``
    (``repro.core.calibrate.CostModel``) is supplied — and neither an
    explicit backend nor the env pin forces the choice — the candidate
    backends this process can actually run are *priced*: each available
    impl's predicted wall time comes from the coefficients fitted for its
    ``calibrate.device_key(backend=...)`` key (the same per-backend keys
    ``physical_cost`` blends), and the cheapest wins. The comparison only
    engages when at least two candidates have fitted models — a one-sided
    fit falls back to the static policy rather than letting an unpriced
    backend win by default. ``REPRO_BACKEND_CHOICE=static`` is the kill
    switch: cost-based choice is disabled fleet-wide, static policy only.

    ``features`` is the per-node feature dict (``calibrate.FEATURES``
    keys) describing the work the kernel will do; the builder supplies it
    from the node's flop/byte annotations. ``block_size`` and
    ``n_workers`` are the plan's tile granularity and worker count: an
    impl that does not accept them is never a candidate.
    """
    static = resolve_backend(name, backend, block_size, n_workers)
    if backend or os.environ.get(_BACKEND_ENV):
        return static  # an explicit pin always wins
    if os.environ.get(_BACKEND_CHOICE_ENV, "").lower() == "static":
        return static
    if cost_model is None or features is None:
        return static
    spec = get(name)
    avail = available_backends()
    cands = [b for b in spec.backends()
             if b in avail and b != INTERPRET   # interpret is never a plan
             and accepts(b, block_size, n_workers)]
    if len(cands) < 2:
        return static
    from repro.core import calibrate
    priced = []
    for b in cands:
        dev = calibrate.device_key(backend=b)
        if cost_model.model_for(dev) is None:
            continue
        priced.append((float(cost_model.predict(features, device=dev)), b))
    if len(priced) < 2:
        return static
    return min(priced)[1]


class CircuitBreaker:
    """Per-backend dispatch circuit breaker (closed → open → half-open).

    ``record_failure`` counts *consecutive* dispatch failures per
    non-dense backend; at ``threshold`` the backend is quarantined
    (``open``): ``quarantined()`` turns true and dispatch degrades to the
    dense oracle without attempting the backend at all. After
    ``cooldown_s`` the breaker goes half-open — exactly one in-flight
    probe dispatch is re-admitted; its success closes the breaker, its
    failure re-opens it (fresh cooldown). Every transition feeds the
    metrics registry (``kernel_breaker_*{backend=...}``), so the serving
    tier's snapshot shows quarantines as they happen.

    The dense backend is never quarantined: it is the semantic oracle and
    the fallback target — its failures always propagate.
    """

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 clock: Callable[[], float] = time.monotonic,
                 registry=None):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self.clock = clock
        self._lock = threading.Lock()
        # backend → [consecutive_failures, opened_at|None, probing]
        self._state: Dict[str, list] = {}
        if registry is None:
            from repro.obs.metrics import REGISTRY as registry
        self._registry = registry

    def _entry(self, backend: str) -> list:
        return self._state.setdefault(backend, [0, None, False])

    def state(self, backend: str) -> str:
        with self._lock:
            ent = self._entry(backend)
            if ent[1] is None:
                return "closed"
            if self.clock() - ent[1] >= self.cooldown_s:
                return "half-open"
            return "open"

    def quarantined(self, backend: str) -> bool:
        """True when dispatch must skip ``backend`` right now. In the
        half-open window the first caller is admitted as the probe and
        subsequent callers stay quarantined until the probe resolves."""
        if backend == DENSE:
            return False
        with self._lock:
            ent = self._entry(backend)
            if ent[1] is None:
                return False
            if self.clock() - ent[1] < self.cooldown_s:
                return True
            if ent[2]:                  # a probe is already in flight
                return True
            ent[2] = True               # this caller becomes the probe
            self._gauge(backend, 0.5)
            return False

    def record_success(self, backend: str) -> None:
        with self._lock:
            ent = self._entry(backend)
            reopened = ent[1] is not None
            ent[0] = 0
            ent[1] = None
            ent[2] = False
        if reopened:
            self._registry.counter("kernel_breaker_closes",
                                   backend=backend).inc()
            self._gauge(backend, 0.0)

    def record_failure(self, backend: str) -> None:
        self._registry.counter("kernel_dispatch_failures",
                               backend=backend).inc()
        with self._lock:
            ent = self._entry(backend)
            ent[0] += 1
            tripped = ent[0] >= self.threshold or ent[2]
            if tripped:
                ent[1] = self.clock()   # open (or re-open after probe)
                ent[2] = False
        if tripped:
            self._registry.counter("kernel_breaker_trips",
                                   backend=backend).inc()
            self._gauge(backend, 1.0)

    def _gauge(self, backend: str, v: float) -> None:
        self._registry.gauge("kernel_breaker_open", backend=backend).set(v)

    def reset(self) -> None:
        with self._lock:
            self._state.clear()


def _breaker_config() -> Tuple[int, float]:
    return (int(os.environ.get(_BREAKER_THRESHOLD_ENV, "3")),
            float(os.environ.get(_BREAKER_COOLDOWN_ENV, "30.0")))


BREAKER = CircuitBreaker(*_breaker_config())


def dispatch(name: str, *args: Any, backend: Optional[str] = None,
             tiles: Optional[Dict[str, int]] = None, **kw: Any):
    """Run kernel ``name`` on the resolved backend.

    When ``tiles`` is None and ``REPRO_AUTOTUNE`` is set, previously-tuned
    tile sizes are looked up from the autotune cache (cache-only — dispatch
    never times; populating the cache is ``autotune.best_tiles``'s job).

    Degradation: a non-dense backend that fails (or is fault-injected via
    the ``kernel_dispatch`` scope) falls back to the dense oracle for this
    call and feeds the circuit breaker; a quarantined backend is skipped
    outright until its half-open probe re-admits it. Failures of the dense
    oracle itself always propagate — there is nothing left to degrade to.
    The first failure of each (kernel, backend) pair is logged as a
    warning with its exception, so a hop never goes unseen.
    """
    spec = get(name)
    block_size = kw.get("block_size")
    chosen = resolve_backend(name, backend, block_size)
    avail = available_backends()
    # the degradation chain: chosen backend first, then its capability-
    # ordered fallbacks (gpu → tpu → dense) restricted to impls this
    # kernel has, backends this process can run and tilings that accept
    # this block size
    chain = [chosen] + [fb for fb in _FALLBACK_ORDER.get(chosen, ())
                        if fb in spec.impls and (fb == DENSE or fb in avail)
                        and accepts(fb, block_size)]
    # a quarantined head is skipped outright — but never the last resort:
    # with nothing left to degrade to, the quarantined backend still runs
    while len(chain) > 1 and chain[0] != DENSE \
            and BREAKER.quarantined(chain[0]):
        from repro.obs.metrics import REGISTRY
        REGISTRY.counter("kernel_dispatch_quarantined",
                         backend=chain[0]).inc()
        chain = chain[1:]
    if tiles is None and _autotune_enabled():
        from repro.kernels import autotune
        tiles = autotune.cached_tiles(
            name, _arg_shapes(args), _arg_dtype(args), chain[0])
    for pos, b in enumerate(chain):
        last = pos == len(chain) - 1
        fallback = pos > 0
        if fallback and not last and b != DENSE and BREAKER.quarantined(b):
            from repro.obs.metrics import REGISTRY
            REGISTRY.counter("kernel_dispatch_quarantined",
                             backend=b).inc()
            continue
        # fault injection applies to the *chosen* dispatch only: the
        # fallback hops are the containment path chaos runs exist to
        # exercise, so they run clean (and with default tiles)
        run_tiles = tiles if not fallback else None
        if b == DENSE:
            if not fallback:
                faults.check("kernel_dispatch", kernel=name, backend=b)
            out = spec.impls[b](*args, tiles=run_tiles, **kw)
            _count_dispatch(name, b)
            return out
        try:
            if not fallback:
                faults.check("kernel_dispatch", kernel=name, backend=b)
            out = spec.impls[b](*args, tiles=run_tiles, **kw)
        except Exception as exc:
            # deliberate containment, not a swallow: the failure is
            # counted, logged, feeds the breaker, and execution degrades
            # one hop down the chain (FaultInjected included — that is how
            # chaos runs drive the quarantine path)
            BREAKER.record_failure(b)
            _warn_once(name, b, exc)
            if last:
                raise
            from repro.obs.metrics import REGISTRY
            REGISTRY.counter("kernel_dispatch_fallbacks",
                             backend=b).inc()
            continue
        BREAKER.record_success(b)
        _count_dispatch(name, b)
        return out
    raise RuntimeError(  # pragma: no cover - chain always ends in a run
        f"kernel {name!r}: no runnable backend in {chain}")


def _count_dispatch(name: str, backend: str) -> None:
    """``kernel_dispatches{kernel,backend}``: which impl actually served
    each dispatch (inside a staged plan, dispatch runs once per trace)."""
    from repro.obs.metrics import REGISTRY
    REGISTRY.counter("kernel_dispatches", kernel=name, backend=backend).inc()


_WARNED: set = set()
_WARNED_LOCK = threading.Lock()


def _warn_once(name: str, backend: str, exc: BaseException) -> None:
    with _WARNED_LOCK:
        if (name, backend) in _WARNED:
            return
        _WARNED.add((name, backend))
    _LOG.warning("kernel %r failed on backend %r and fell back: %s: %s",
                 name, backend, type(exc).__name__, exc)


def _autotune_enabled() -> bool:
    val = os.environ.get(_AUTOTUNE_ENV, "")
    return val.lower() not in ("", "0", "false", "no", "off")


def _arg_shapes(args: Tuple[Any, ...]) -> Tuple[Tuple[int, ...], ...]:
    return tuple(tuple(a.shape) for a in args if hasattr(a, "shape"))


def _arg_dtype(args: Tuple[Any, ...]) -> str:
    # key by the first floating payload dtype, not auxiliary integer args
    # (bloom_probe's leading words arg is uint32; its values are float)
    import jax.numpy as jnp
    first = None
    for a in args:
        dt = getattr(a, "dtype", None)
        if dt is None:
            continue
        if first is None:
            first = str(dt)
        if jnp.issubdtype(dt, jnp.floating):
            return str(dt)
    return first or "float32"
