"""Host scans (``plan.masks``' nnz and side-capacity counts,
``joins_device.exact_capacity``): milliseconds per step in
``host_scan`` spans, each one pass over a leaf already on the host."""
from lib.spans import per_step_ms


def read(ctx):
    return per_step_ms(ctx, ["host_scan"])
