"""Kernels: the fused block-skipping product's share of its roofline, in
percent. The least time the chip could take for the window's
``sddmm_agg`` dispatches (per dispatch the larger of its flops over the
chip's peak FLOP/s and its bytes over its peak bytes/s, ``lib.peaks``;
flops and bytes counted from the catalog by the traffic's shape,
``shapes/queries.py``), over the time in the ``execute`` spans whose
``kernels`` attribute names ``sddmm_agg`` (under a trace each staged
call waits for the device). Nothing where no span names the kernel or
the shape counted no work.

The divisor is the whole staged call, not the kernel alone: it holds
the program's bfloat16 casts of the operands and its padding too. The
floor counts live block triples at 128, the chip's matrix-unit width,
not at the 256 blocks the kernel walks, so the share stays below 100%
by the dead 128-panels inside live 256-panels as well."""
from lib import peaks, spec as specmod


def read(ctx):
    spans = [s for s in ctx.root.walk() if s.name == "execute"
             and "sddmm_agg" in s.attrs.get("kernels", "").split(",")]
    work = specmod.shape("queries").KERNEL_WORK
    if not spans or not work:
        return None
    flops_s, bytes_s = peaks.for_device()
    floor_s = max(work["flops"] / flops_s, work["bytes"] / bytes_s)
    return 100.0 * len(spans) * floor_s / sum(s.duration for s in spans)
