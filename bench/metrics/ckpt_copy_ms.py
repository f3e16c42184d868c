"""ckpt_copy_ms.off (ms): device time per optimizer step of the copies
between device memory and pinned host memory (the host tier)."""


def read(ctx):
    s = ctx.trace.host_copy_time_s()
    return 1e3 * s / ctx.steps if s > 0 else None
