"""step_mfu_pct.{dev,off} (%): model FLOPs per optimizer step (from
shapes, ``bench/models/<config>.py``; recomputation not counted) over the
traced step time times the chip's bf16 peak (``bench/lib/peaks.py``)."""


def read(ctx):
    w = ctx.trace.window_s()
    if w <= 0 or ctx.peak is None or not ctx.trace.ops:
        return None
    return 100.0 * ctx.flops_per_step * ctx.steps / (w * ctx.peak.flops_bf16)
