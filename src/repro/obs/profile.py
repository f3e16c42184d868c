"""Profiler annotation helpers.

Two mechanisms, matched to where code runs:

``scope(name)``
    ``jax.named_scope`` wrapper for *traced* code: stamps the name into
    the HLO metadata of every op traced inside it, so ``jax.profiler``
    traces and HLO dumps show ``obs:forward`` / ``obs:reverse/seg3`` /
    ``obs:spill`` frames.  Purely trace-time metadata — no runtime op is
    added and numerics are untouched (named_scope participates in CSE
    like any unannotated op).

``host_annotation(name)``
    ``jax.profiler.TraceAnnotation`` for *host* code: wraps the body of a
    spill-store callback (or any host-side work) in a named profiler
    activity so the time XLA spends blocked on host I/O is attributed in
    the trace viewer.
"""
from __future__ import annotations

import jax

PREFIX = "obs"


def scope(name: str):
    """Named scope for traced code: ``with scope("reverse/seg3"): ...``"""
    return jax.named_scope(f"{PREFIX}:{name}")


def host_annotation(name: str):
    """Profiler annotation for host-callback bodies."""
    return jax.profiler.TraceAnnotation(f"{PREFIX}:{name}")
