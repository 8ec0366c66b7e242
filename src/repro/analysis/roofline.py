"""Three-term roofline model from the compiled dry-run artifact.

Per (arch × shape × mesh), using the per-device optimized HLO (already
SPMD-partitioned, so every number is per-chip):

    compute    = HLO_FLOPs / peak_FLOP/s
    memory     = HLO_bytes / HBM_bw
    collective = collective_bytes / ICI_bw

The peaks come from ``PEAKS``, keyed by the ``device_kind`` JAX reports
for the chip the program was compiled for; a kind missing from the table
is an error, never a default. MODEL_FLOPS = 6·N·D (dense train) /
6·N_active·D (MoE) / 2·N·D (inference) is reported alongside as the
usefulness ratio.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.analysis.hlo import HloStats


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float          # bf16 FLOP/s per chip
    hbm_bw: float         # HBM bytes/s per chip
    ici_bw: float         # interconnect bytes/s per link


# Source: Google Cloud documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of inter-chip interconnect
# per chip over 4 links (50 GB/s per link).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; KeyError when unlisted."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; have {sorted(PEAKS)}") from None


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops_per_chip: float
    usefulness: float          # MODEL_FLOPS / HLO_FLOPs (per chip)
    dominant: str
    step_time_s: float         # max of the three terms (no overlap model)
    mfu: float                 # model_flops / (step_time × peak)

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def analyze(stats: HloStats, model_flops_total: float, n_chips: int, *,
            device_kind: str) -> Roofline:
    pk = peaks_for(device_kind)
    peak = pk.flops
    compute = stats.flops / peak
    memory = stats.bytes_accessed / pk.hbm_bw
    collective = stats.collective_bytes / pk.ici_bw
    terms = {"compute": compute, "memory": memory, "collective": collective}
    dominant = max(terms, key=terms.get)
    model_pc = model_flops_total / max(1, n_chips)
    step = max(compute, memory, collective)
    return Roofline(
        compute_s=compute, memory_s=memory, collective_s=collective,
        hlo_flops=stats.flops, hlo_bytes=stats.bytes_accessed,
        collective_bytes=stats.collective_bytes,
        model_flops_per_chip=model_pc,
        usefulness=model_pc / max(stats.flops, 1.0),
        dominant=dominant,
        step_time_s=step,
        mfu=model_pc / max(step, 1e-12) / peak,
    )


def model_flops(param_count: int, active_param_count: int, tokens: int,
                kind: str) -> float:
    """6·N·D train / 2·N·D inference (N = active params for MoE)."""
    n = active_param_count
    if kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


def active_params(spec_tree) -> int:
    """Parameter count with MoE expert tensors scaled by top_k/E."""
    import math

    import jax

    from repro.models.module import ParamSpec, is_param_spec

    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            spec_tree, is_leaf=is_param_spec)[0]:
        assert isinstance(leaf, ParamSpec)
        n = int(math.prod(leaf.shape))
        if "experts" in (leaf.axes or ()):
            # scale by routed fraction later (caller passes top_k/E)
            pass
        total += n
    return total


def active_param_count(spec_tree, top_k: Optional[int] = None,
                       n_experts: Optional[int] = None) -> int:
    import math

    import jax

    from repro.models.module import ParamSpec, is_param_spec

    total = 0
    for _, leaf in jax.tree_util.tree_flatten_with_path(
            spec_tree, is_leaf=is_param_spec)[0]:
        n = int(math.prod(leaf.shape))
        if top_k and n_experts and "experts" in (leaf.axes or ()):
            n = int(n * top_k / n_experts)
        total += n
    return total
