"""Solver flight recorder: a structured, append-only trace of what a solve
actually did — per-attempt adaptive step decisions, per-step Newton health,
checkpoint-store traffic with segment ids and payload bytes — attached to a
solve with the ``obs=`` knob (``odeint`` / ``odeint_implicit`` /
``odeint_adaptive``) and **zero-overhead when off**: with ``obs=None`` not a
single extra op is traced.

Two event classes, honestly labelled by when they are recorded:

  trace-time   configuration and schedule events (``odeint.solve``, the
               revolve checkpoint put/get/free/recompute schedule, the
               planner's decision).  Emitted while jax traces the program —
               ONCE per compilation.  A cached jit re-execution emits no new
               trace-time events (they describe the program, not the run).
  runtime      events carrying runtime values (``adaptive.step`` with
               dt/error-norm/accept, ``implicit.steps`` with stacked
               per-step Newton iterations/residuals,
               ``spill.write``/``spill.read`` with payload bytes).
               Emitted from inside the compiled program via
               ``jax.debug.callback`` (traced sites) or directly from
               the spill store's host callbacks — once per EXECUTION.

jax-0.4.37 caveat (why implicit events are STACKED): a
``jax.debug.callback`` issued inside a ``lax.scan`` body within a
``custom_vjp`` *fwd* rule is silently dropped under ``jit(grad(...))``
(while_loop bodies and bwd-rule scans are fine).  The implicit sweeps
therefore thread per-step ``StepInfo`` out of the scan as stacked ys and
issue ONE top-level tap per sweep; ``implicit_steps()`` expands those
stacked events back into per-step records.

``jax.debug.callback`` is unordered, so runtime events may interleave
across concurrent solves; every emitter therefore includes enough state to
reconstruct order (the adaptive tap carries the attempt counter
``n_accepted + n_rejected``, spill events carry slot bases).  The
reconstruction helpers (``adaptive_steps``, ``spill_traffic``) sort on
those fields, not on arrival order.

Numerics: debug callbacks only add an effect, never an op that feeds the
computation — gradients with a recorder attached are bitwise-identical to
the unobserved solve (tests/test_obs.py locks this across
policy x offload-tier x (eager|jit)).

Lifecycle: a recorder is baked into the traced program as a static
argument, so use ONE recorder per jitted solve (a fresh recorder forces a
retrace) and ``clear()`` between measured runs (compile/warmup executions
emit events too).  Host-side mutation is lock-guarded; events carry a
monotonically increasing ``seq``.
"""
from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np


def _pyval(x):
    """Host-side: numpy/array scalar -> plain python (JSON-ready)."""
    a = np.asarray(x)
    if a.ndim == 0:
        v = a.item()
        return v
    return a.tolist()


@dataclass(frozen=True)
class TraceEvent:
    kind: str
    data: Dict[str, Any]
    seq: int
    runtime: bool  # True: emitted during execution; False: during tracing
    #: host wall clock at record time (time.time()).  Host-side metadata
    #: only — nothing traced reads it, so numerics stay untouched.  It is
    #: not the profiler's clock: timelines come from ``jax.profiler.trace``.
    ts: float = 0.0

    def to_json(self) -> Dict[str, Any]:
        return {"kind": self.kind, "seq": self.seq,
                "runtime": self.runtime, "ts": self.ts, **self.data}


class FlightRecorder:
    """Append-only structured solver trace (see module docstring)."""

    def __init__(self, registry=None):
        self._lock = threading.RLock()
        self._events: List[TraceEvent] = []
        self._seq = 0
        #: optional MetricsRegistry mirror: every event also bumps the
        #: counter ``trace.<kind>``
        self.registry = registry

    # -- host-side recording (trace-time events, store callbacks) ----------
    def record(self, kind: str, *, _runtime: bool = False, **data) -> None:
        with self._lock:
            self._events.append(TraceEvent(kind, data, self._seq, _runtime,
                                           time.time()))
            self._seq += 1
        if self.registry is not None:
            self.registry.inc(f"trace.{kind}")

    # -- traced-side recording (runtime events) -----------------------------
    def emit(self, kind: str, **traced_fields) -> None:
        """Call from inside traced code: schedules a ``jax.debug.callback``
        that records the runtime values of ``traced_fields`` on execution.
        Adds only a debug effect to the program — no op feeds the
        computation, so numerics are untouched."""
        keys = tuple(traced_fields.keys())
        vals = tuple(traced_fields.values())

        def cb(*host_vals):
            self.record(kind, _runtime=True,
                        **{k: _pyval(v) for k, v in zip(keys, host_vals)})

        jax.debug.callback(cb, *vals)

    # -- access --------------------------------------------------------------
    def sync(self) -> None:
        """Block until pending emits have landed.  ``jax.debug.callback``
        is asynchronous: reading the recorder right after a solve returns
        can miss late callbacks (the reverse sweep's recompute taps are
        the last to run).  Called automatically by ``events()`` — never
        call it from inside a callback body (it would wait on itself)."""
        barrier = getattr(jax, "effects_barrier", None)
        if barrier is not None:
            barrier()

    def events(self, kind: Optional[str] = None) -> List[TraceEvent]:
        self.sync()
        with self._lock:
            evs = list(self._events)
        if kind is None:
            return evs
        return [e for e in evs if e.kind == kind]

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    # -- reconstruction helpers ---------------------------------------------
    def adaptive_steps(self) -> List[Dict[str, Any]]:
        """The adaptive sweep's attempt sequence, ordered by the attempt
        counter each tap carried (immune to callback reordering): one dict
        per attempted step with t, h, err_norm, and accept."""
        evs = self.events("adaptive.step")
        return sorted((e.data for e in evs), key=lambda d: d["attempt"])

    def accepted_rejected(self) -> Tuple[int, int]:
        steps = self.adaptive_steps()
        acc = sum(1 for d in steps if d["accept"])
        return acc, len(steps) - acc

    def spill_traffic(self) -> Dict[str, Dict[str, Any]]:
        """Per-store, per-direction spill I/O: callbacks, slots, and payload
        bytes, plus the per-segment breakdown keyed by slot base and the
        per-MEDIUM byte split (``media``: "ram" vs "disk" — the multi-tier
        store tags every write/read event with where the payload landed).
        ``dispatch_cb`` counts the token-only async prefetch dispatches
        (``spill.dispatch`` events) separately from data-carrying reads."""
        out: Dict[str, Dict[str, Any]] = {}
        for e in self.events():
            if e.kind not in ("spill.write", "spill.read", "spill.free",
                              "spill.dispatch"):
                continue
            store = e.data.get("store", "?")
            s = out.setdefault(store, {
                "write_cb": 0, "read_cb": 0, "free_cb": 0, "dispatch_cb": 0,
                "write_slots": 0, "read_slots": 0,
                "write_bytes": 0, "read_bytes": 0,
                "segments": {}, "media": {}})
            if e.kind == "spill.dispatch":
                s["dispatch_cb"] += 1
                continue
            if e.kind == "spill.free":
                s["free_cb"] += 1
                continue
            medium = e.data.get("medium")
            if medium is not None:
                m = s["media"].setdefault(str(medium), {
                    "write_bytes": 0, "read_bytes": 0})
                key = ("write_bytes" if e.kind == "spill.write"
                       else "read_bytes")
                m[key] += int(e.data.get("bytes", 0))
            d = "write" if e.kind == "spill.write" else "read"
            s[f"{d}_cb"] += 1
            s[f"{d}_slots"] += int(e.data.get("slots", 1))
            s[f"{d}_bytes"] += int(e.data.get("bytes", 0))
            seg = s["segments"].setdefault(int(e.data.get("base", -1)), {
                "write_slots": 0, "read_slots": 0,
                "write_bytes": 0, "read_bytes": 0})
            seg[f"{d}_slots"] += int(e.data.get("slots", 1))
            seg[f"{d}_bytes"] += int(e.data.get("bytes", 0))
        return out

    @staticmethod
    def _expand_stacked(evs: List[TraceEvent]) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        for e in evs:
            base = int(e.data.get("base", 0))
            its = e.data["iters"]
            res = e.data["residual"]
            conv = e.data["converged"]
            if not isinstance(its, list):  # single-step sweep
                its, res, conv = [its], [res], [conv]
            for i in range(len(its)):
                out.append({"step": base + i, "iters": its[i],
                            "residual": res[i], "converged": conv[i]})
        return sorted(out, key=lambda d: d["step"])

    def implicit_steps(self) -> List[Dict[str, Any]]:
        """Forward-sweep Newton exit states, one dict per step ordered by
        step index — expanded from the stacked ``implicit.steps`` taps
        (one per scan; see module docstring)."""
        return self._expand_stacked(self.events("implicit.steps"))

    def implicit_recomputes(self) -> List[Dict[str, Any]]:
        """Reverse-sweep re-advance Newton exit states, per step."""
        return self._expand_stacked(self.events("implicit.recompute"))

    # -- export --------------------------------------------------------------
    def to_jsonl(self, path_or_sink) -> int:
        """Write every event as one JSON line; accepts a path or a
        ``MetricsSink``.  Returns the number of events written."""
        evs = self.events()
        emit = getattr(path_or_sink, "emit", None)
        if emit is not None:
            for e in evs:
                emit(f"trace.{e.kind}", **e.to_json())
            return len(evs)
        with open(path_or_sink, "a") as fh:
            for e in evs:
                fh.write(json.dumps(e.to_json()) + "\n")
        return len(evs)
