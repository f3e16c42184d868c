"""PNODE checkpointing applied over *depth*: the LM layer-stack scan.

A residual stack  u_{l+1} = u_l + F(u_l, theta_l)  is forward Euler with
h = 1 and a layer-indexed vector field — the ResNet<->ODE duality the paper
builds on.  This module provides ``checkpointed_scan``: a scan over stacked
per-layer parameters whose *gradient strategy* is selectable, mirroring the
paper's adjoint policies at the depth level:

  remat='none'     NODE-naive analogue — XLA stores every layer's residuals.
  remat='full'     ACA analogue — every layer recomputed in the reverse pass
                   (jax.checkpoint around the layer body).
  remat='sqrt'     two-level scan-of-scans: sqrt(N_l) segment boundaries live,
                   one recompute per layer — binomial checkpointing's sweet
                   spot for XLA (segment boundaries are the checkpoints).
  remat='revolve'  trace-time binomial schedule over layers (N_c slots); the
                   paper's Prop-2-optimal recompute at a given memory budget.
                   Implemented with jax.checkpoint on unrolled segments.

For true continuous-depth blocks (shared weights, arbitrary RK scheme) use
``ODEBlock`` which delegates to core.adjoint.odeint.
"""
from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import tree_util as jtu

from repro.core.adjoint import odeint
from repro.core.integrators import PyTree

LayerFn = Callable[[PyTree, PyTree], PyTree]  # (carry, layer_params) -> carry


def _plain_scan(layer_fn: LayerFn, u0: PyTree, stacked: PyTree) -> PyTree:
    def body(c, p):
        return layer_fn(c, p), None

    out, _ = jax.lax.scan(body, u0, stacked)
    return out


def checkpointed_scan(layer_fn: LayerFn, u0: PyTree, stacked_params: PyTree,
                      n_layers: int, remat: str = "sqrt",
                      ncheck: int | None = None) -> PyTree:
    """Run u <- layer_fn(u, params_l) for l = 0..n_layers-1 with the chosen
    depth-checkpointing policy.  ``stacked_params`` has a leading N_l axis."""
    if remat == "none":
        return _plain_scan(layer_fn, u0, stacked_params)

    if remat == "full":
        def body(c, p):
            return jax.checkpoint(layer_fn)(c, p), None

        out, _ = jax.lax.scan(body, u0, stacked_params)
        return out

    if remat == "sqrt":
        seg = max(1, int(math.sqrt(n_layers)))
        n_seg = math.ceil(n_layers / seg)
        if n_seg * seg != n_layers:
            # fall back to the largest divisor <= sqrt for clean reshapes
            seg = 1
            for d in range(int(math.sqrt(n_layers)), 0, -1):
                if n_layers % d == 0:
                    seg = d
                    break
            n_seg = n_layers // seg
        resh = jtu.tree_map(
            lambda p: p.reshape((n_seg, seg) + p.shape[1:]), stacked_params)

        @jax.checkpoint
        def segment(c, ps):
            return _plain_scan(layer_fn, c, ps)

        def outer(c, ps):
            return segment(c, ps), None

        out, _ = jax.lax.scan(outer, u0, resh)
        return out

    if remat == "revolve":
        if ncheck is None:
            raise ValueError("remat='revolve' requires ncheck")
        from repro.core.revolve import sweep_checkpoint_positions

        positions = [0] + sweep_checkpoint_positions(n_layers, ncheck) + [n_layers]
        u = u0
        for a, b in zip(positions[:-1], positions[1:]):
            seg_params = jtu.tree_map(lambda p: p[a:b], stacked_params)

            @jax.checkpoint
            def segment(c, ps):
                return _plain_scan(layer_fn, c, ps)

            u = segment(u, seg_params)
        return u

    raise ValueError(f"unknown remat policy {remat!r}")


class ODEBlock:
    """Continuous-depth block: integrates du/dt = F(u, theta, t) with any
    explicit method and any PNODE adjoint policy (shared weights over depth).
    Further keywords (``offload``, ``fused_stages``, ...) go to ``odeint``
    unchanged."""

    def __init__(self, vf, *, n_steps: int = 4, method: str = "rk4",
                 adjoint: str = "pnode", ncheck: int | None = None,
                 t0: float = 0.0, t1: float = 1.0, **odeint_kw):
        self.vf = vf
        self.n_steps = n_steps
        self.method = method
        self.adjoint = adjoint
        self.ncheck = ncheck
        self.t0 = t0
        self.dt = (t1 - t0) / n_steps
        self.odeint_kw = odeint_kw

    def __call__(self, u0: PyTree, theta: PyTree) -> PyTree:
        return odeint(self.vf, u0, theta, dt=self.dt, n_steps=self.n_steps,
                      t0=self.t0, method=self.method, adjoint=self.adjoint,
                      ncheck=self.ncheck, **self.odeint_kw)
