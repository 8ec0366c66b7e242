"""Host transfer (``plan.masks`` leaf and mask views, ``Session.load``'s
nnz, ``joins_device.coo_to_host``): milliseconds per step in ``d2h``
spans, each a host view of a device array: it waits for the program
that made the array, and copies the array device→host unless the array
holds a host copy already."""
from lib.spans import per_step_ms


def read(ctx):
    return per_step_ms(ctx, ["d2h"])
