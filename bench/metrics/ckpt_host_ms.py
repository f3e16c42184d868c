"""ckpt_host_ms.off (ms): host time per optimizer step inside the spill
store's callback annotations (``obs:spill/*``, ``mem/offload.py``)."""
PREFIX = "obs:spill/"


def read(ctx):
    s = ctx.trace.host_time_s(PREFIX)
    return 1e3 * s / ctx.steps if s > 0 else None
