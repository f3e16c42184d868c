"""ckpt_callbacks.off (count): spill-store callbacks per optimizer step,
counted as the host spans named exactly ``obs:spill/{write, write_batch,
read, prefetch, dispatch, free}`` (``mem/offload.py``) that start inside
the window (``bench/lib/spans.py``)."""
from bench.lib import spans


def read(ctx):
    n = spans.callback_count(ctx.trace)
    return n / ctx.steps if n else None
