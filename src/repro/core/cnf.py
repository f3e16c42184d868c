"""Continuous normalizing flows (FFJORD) on top of the PNODE adjoint core.

The CNF ODE evolves (x, log p) jointly:

    d x / dt       = f(x, theta, t)
    d logdet / dt  = -tr( df/dx )

Trace estimation: exact (d jvps, for small d — the paper's tabular datasets
are 6/43/63-dim) or Hutchinson (one vjp with a fixed Rademacher probe).
The augmented system is just another vector field, so every adjoint policy
(pnode/pnode2/revolve/aca/anode/naive/continuous) applies unchanged — this is
what the paper's Tables 3-7 measure.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.adjoint import odeint
from repro.core.integrators import PyTree, VectorField
from repro.obs.profile import scope


def exact_trace_vf(f: VectorField, dim: int) -> VectorField:
    """Augmented vector field with exact trace (dim jvp probes)."""

    def aug(state, theta, t):
        x, _logdet = state
        fx = f(x, theta, t)

        def jac_diag_i(i):
            e = jnp.zeros((dim,)).at[i].set(1.0)
            e = jnp.broadcast_to(e, x.shape)
            _, jv = jax.jvp(lambda xx: f(xx, theta, t), (x,), (e,))
            return jv[..., i]

        with scope("cnf/trace"):
            diag = jnp.stack([jac_diag_i(i) for i in range(dim)], axis=-1)
            trace = jnp.sum(diag, axis=-1)
        return (fx, -trace)

    return aug


def hutchinson_trace_vf(f: VectorField, probe: jax.Array) -> VectorField:
    """Augmented vector field with a Hutchinson trace estimate.

    ``probe`` is a fixed Rademacher tensor shaped like x (drawn once per
    training iteration, as in FFJORD)."""

    def aug(state, theta, t):
        x, _logdet = state
        fx, vjp_fn = jax.vjp(lambda xx: f(xx, theta, t), x)
        with scope("cnf/trace"):
            (vjp_probe,) = vjp_fn(probe)
            trace_est = jnp.sum(vjp_probe * probe, axis=-1)
        return (fx, -trace_est)

    return aug


def change_of_variables(z: jax.Array, dlogdet: jax.Array) -> jax.Array:
    """log p(x) from the flow's end point ``z`` and ``dlogdet``, the
    integral of -tr(df/dx) that the augmented fields above accumulate:
    log p(x) = log N(z; 0, I) - dlogdet (FFJORD's logpx = logpz -
    delta_logp).  ``z`` is (..., dim), ``dlogdet`` (...)."""
    dim = z.shape[-1]
    base_logp = -0.5 * jnp.sum(z ** 2, axis=-1) - 0.5 * dim * jnp.log(2 * jnp.pi)
    return base_logp - dlogdet


def cnf_log_prob(f: VectorField, x: jax.Array, theta: PyTree, *,
                 dt: float, n_steps: int, method: str = "dopri5",
                 adjoint: str = "pnode", ncheck: int | None = None,
                 trace: str = "exact", probe: jax.Array | None = None,
                 t0: float = 0.0) -> jax.Array:
    """log p(x) under the CNF that flows data -> base N(0, I) over [t0, t1].

    Integrates the augmented ODE forward from the data points; returns the
    per-sample log-probability (batch,) — the training loss is its negative
    mean (Tables 3-7 of the paper).
    """
    dim = x.shape[-1]
    if trace == "exact":
        aug = exact_trace_vf(f, dim)
    elif trace == "hutchinson":
        if probe is None:
            raise ValueError("hutchinson trace needs a probe")
        aug = hutchinson_trace_vf(f, probe)
    else:
        raise ValueError(trace)

    logdet0 = jnp.zeros(x.shape[:-1], x.dtype)
    z, dlogdet = odeint(aug, (x, logdet0), theta, dt=dt, n_steps=n_steps,
                        t0=t0, method=method, adjoint=adjoint, ncheck=ncheck)
    return change_of_variables(z, dlogdet)


def cnf_sample(f: VectorField, z: jax.Array, theta: PyTree, *, dt: float,
               n_steps: int, method: str = "dopri5", t0: float = 0.0):
    """Sample by integrating base noise backward through the flow."""
    t1 = t0 + dt * n_steps

    def neg_f(x, th, t):
        return -f(x, th, t1 + t0 - t)

    logdet0 = jnp.zeros(z.shape[:-1], z.dtype)
    aug = exact_trace_vf(neg_f, z.shape[-1])
    x, _ = odeint(aug, (z, logdet0), theta, dt=dt, n_steps=n_steps, t0=t0,
                  method=method, adjoint="naive")
    return x
