"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``: (FLOP/s in bfloat16, HBM bytes/s).

TPU v5e: 197 TFLOP/s bf16 and 819 GB/s (Google Cloud documentation,
"TPU v5e"). A chip that is not here is an error, not a default."""
from __future__ import annotations

from typing import Optional, Tuple

PEAKS = {"TPU v5 lite": (197e12, 819e9)}


def for_device(kind: Optional[str] = None) -> Tuple[float, float]:
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for {kind!r}; have "
                       f"{sorted(PEAKS)}")
    return PEAKS[kind]
