"""device_idle_pct.{dev,off} (%): the share of the traced window in which
no compute op ran on the device (waits on transfers and host callbacks
count as idle; ``bench/lib/trace.py``)."""


def read(ctx):
    w = ctx.trace.window_s()
    if w <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / w)
