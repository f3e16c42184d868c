"""Selections of the checkpoint store's marks in a reduced trace
(``bench/lib/trace.py``), for its per-layer metrics.

The spill store (``mem/offload.py``) opens one host span per callback
body, named ``obs:spill/<callback>``; ``CALLBACKS`` are those names.  A
device wait is a leaf op whose category ends in ``-done``
(``trace.is_wait``).  Times are in seconds, clipped to the window.
"""
from __future__ import annotations

from bench.lib.trace import _union, is_wait

SPILL = "obs:spill/"
CALLBACKS = frozenset(SPILL + n for n in ("write", "write_batch", "read",
                                          "prefetch", "dispatch", "free"))


def callback_count(trace) -> int:
    """Spill callback spans that start inside the window, by exact name,
    so that a child span added later inside a callback does not count."""
    lo, hi = trace.window
    return sum(1 for h in trace.host
               if h["name"] in CALLBACKS and lo <= h["span"][0] < hi)


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of the unions of ``a`` and ``b``."""
    return _union(a) + _union(b) - _union(a + b)


def exposed_wait_s(trace, prefix: str = SPILL) -> float | None:
    """Device wait time under no host span named ``prefix*``: the device
    waits on a transfer or a callback while no store code runs (the
    runtime's copies and hand-off).  None where no such span is in the
    window."""
    host = trace._clip(h["span"] for h in trace.host
                       if h["name"].startswith(prefix))
    if not host:
        return None
    waits = trace._clip(o["span"] for o in trace._leaf()
                        if is_wait(o["cat"]))
    return (_union(waits) - _overlap(waits, host)) * 1e-6
