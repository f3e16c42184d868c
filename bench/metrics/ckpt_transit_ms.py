"""ckpt_transit_ms.off (ms): device wait time per optimizer step during
which no spill-store code runs on the host: the union of the device's
wait ops (``*-done``) less its overlap with the union of the
``obs:spill/*`` callback spans (``bench/lib/spans.py``).  That is the
time the device waits on the runtime's transfers and the callback
hand-off, beside ``ckpt_host_ms``'s time inside the store's code."""
from bench.lib import spans


def read(ctx):
    s = spans.exposed_wait_s(ctx.trace)
    return None if s is None else 1e3 * s / ctx.steps
