"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes, beside its ``.xplane.pb``, the same trace as
Chrome-trace JSON (``*.trace.json.gz``).  That file is read here because
its device events carry the op's name stack (``tf_op``, where the
program's ``obs:<policy>/fwd|bwd`` named scopes appear) and its HLO text
(``long_name``, where a pinned-host buffer shows as memory space S(5));
the events that ``jax.profiler.ProfileData`` exposes in jax 0.9 carry
neither.

All times are in microseconds on the trace's one clock, clipped to the
window the harness marks with a host annotation (``bench/window``).

Device ops are the events of the first TPU's "XLA Ops" line.  Three kinds
are told apart by ``hlo_category``:
  containers  ``while``, ``conditional``, ``call``: they span their
              bodies' ops and are never counted themselves;
  waits       the ``*-done`` ops (``host recv-done``, ``copy-done``, ...):
              the core waits on a transfer or a host callback;
  compute     everything else.
``busy`` is the union of compute intervals; a scope's time is the union of
its compute and wait intervals (the time the device timeline spends in
that part of the program).
"""
from __future__ import annotations

import collections
import gzip
import json
import re
from pathlib import Path

WINDOW = "bench/window"
CONTAINERS = {"while", "conditional", "call"}
HOST_MEMORY = "S(5)"


def _union(intervals) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _gaps(intervals, lo, hi):
    """Maximal sub-intervals of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def is_wait(category: str) -> bool:
    return category.endswith("-done")


class Trace:
    """Device ops and host spans of one traced window."""

    def __init__(self, events: list, window: tuple | None = None):
        procs, threads = {}, {}
        for e in events:
            if e.get("ph") != "M":
                continue
            if e.get("name") == "process_name":
                procs[e["pid"]] = e["args"]["name"]
            elif e.get("name") == "thread_name":
                threads[(e["pid"], e["tid"])] = e["args"]["name"]
        tpus = sorted(p for p, n in procs.items()
                      if n.startswith("/device:TPU:"))
        device = tpus[0] if tpus else None
        self.ops, self.host = [], []
        for e in events:
            if e.get("ph") != "X":
                continue
            span = (e["ts"], e["ts"] + e.get("dur", 0.0))
            line = threads.get((e["pid"], e["tid"]), "")
            if e["pid"] == device and line == "XLA Ops":
                a = e.get("args", {})
                self.ops.append({"name": e["name"], "span": span,
                                 "cat": a.get("hlo_category", ""),
                                 "scope": a.get("tf_op", ""),
                                 "hlo": a.get("long_name", "")})
            elif procs.get(e["pid"], "").startswith("/host:"):
                self.host.append({"name": e["name"], "span": span,
                                  "thread": line})
        if window is None:
            marks = [h["span"] for h in self.host if h["name"] == WINDOW]
            if not marks:
                raise ValueError(f"no {WINDOW!r} annotation in the trace")
            window = marks[0]
        self.window = window

    @classmethod
    def from_dir(cls, directory, window=None) -> "Trace":
        files = sorted(Path(directory).glob("**/*.trace.json.gz"))
        if not files:
            raise FileNotFoundError(f"no *.trace.json.gz under {directory}")
        with gzip.open(files[-1], "rt") as fh:
            return cls(json.load(fh)["traceEvents"], window)

    # --- selections -------------------------------------------------------

    def _clip(self, spans):
        lo, hi = self.window
        return [(max(a, lo), min(b, hi)) for a, b in spans
                if b > lo and a < hi]

    def _leaf(self):
        return [o for o in self.ops if o["cat"] not in CONTAINERS]

    def _compute(self):
        return [o for o in self._leaf() if not is_wait(o["cat"])]

    # --- numbers (seconds) ------------------------------------------------

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-6

    def busy_s(self) -> float:
        return _union(self._clip(o["span"] for o in self._compute())) * 1e-6

    def scope_time_s(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return _union(self._clip(o["span"] for o in self._leaf()
                                 if rx.search(o["scope"]))) * 1e-6

    def host_copy_time_s(self) -> float:
        """Device time in copies to or from pinned host memory."""
        return _union(self._clip(o["span"] for o in self._leaf()
                                 if o["cat"].startswith("copy")
                                 and HOST_MEMORY in o["hlo"])) * 1e-6

    def host_time_s(self, prefix: str) -> float:
        return _union(self._clip(h["span"] for h in self.host
                                 if h["name"].startswith(prefix))) * 1e-6

    # --- what the ledger keeps ----------------------------------------------

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (by name, waits included),
        and the longest stretches with no compute on the device, each
        named by the wait op the device sat in, or else by the innermost
        host annotation (``bench/*``, ``obs:*``) open at its middle."""
        per_op = collections.defaultdict(float)
        for o in self._leaf():
            for a, b in self._clip([o["span"]]):
                per_op[o["name"]] += (b - a) * 1e-6
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]

        lo, hi = self.window
        gaps = sorted(_gaps(self._clip(o["span"] for o in self._compute()),
                            lo, hi), key=lambda g: g[0] - g[1])[:top]
        waits = [o for o in self._leaf() if is_wait(o["cat"])]
        marks = [h for h in self.host
                 if h["name"].startswith(("bench/", "obs:"))
                 and h["name"] != WINDOW]
        named = []
        for a, b in gaps:
            mid = 0.5 * (a + b)
            label = next((f"device wait: {o['cat']}" for o in waits
                          if o["span"][0] <= mid <= o["span"][1]), None)
            if label is None:
                open_ = [h for h in marks
                         if h["span"][0] <= mid <= h["span"][1]]
                label = (min(open_, key=lambda h: h["span"][1]
                             - h["span"][0])["name"] if open_
                         else "host: no annotation")
            named.append([label, (b - a) * 1e-6])
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}


def reduce_dir(directory) -> Trace:
    return Trace.from_dir(directory)
