"""repro.obs — the observability layer: metrics registry, jit-safe
counters, the solver flight recorder, JSONL metrics sink, profiler
annotations, and the unified benchmark-baseline checker.

Every other layer reports through this package instead of inventing its
own dict: spill-store traffic (``repro.mem.offload``), Newton/GMRES
health (``repro.core.implicit``), adaptive accept/reject decisions
(``repro.core.adaptive``), planner decisions (``repro.mem.planner``
``explain=True``), and per-train-step records (``repro.launch``).

Attach a ``FlightRecorder`` to a solve with the ``obs=`` knob:

    rec = FlightRecorder()
    u = odeint(f, u0, theta, dt=..., n_steps=..., obs=rec)
    rec.events("spill.write"); rec.adaptive_steps(); rec.spill_traffic()

With ``obs=None`` (default) the knob is zero-overhead: no extra op, no
callback, nothing traced.
"""
from repro.obs.baseline import (BaselineRef, Gate, check_against_baseline,
                                lookup)
from repro.obs.registry import (DEFAULT_REGISTRY, FevalCounter, JitCounter,
                                MetricsRegistry, default_registry)
from repro.obs.sink import MetricsSink, StructuredLogger, read_jsonl
from repro.obs.trace import FlightRecorder, TraceEvent
from repro.obs.profile import host_annotation, scope

__all__ = [
    "BaselineRef", "Gate", "check_against_baseline", "lookup",
    "DEFAULT_REGISTRY", "FevalCounter", "JitCounter", "MetricsRegistry",
    "default_registry",
    "MetricsSink", "StructuredLogger", "read_jsonl",
    "FlightRecorder", "TraceEvent",
    "host_annotation", "scope",
]
