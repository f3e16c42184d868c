"""Explicit Runge-Kutta stepping on pytrees + fixed-step forward solves.

The vector field signature everywhere in this framework is

    f(u, theta, t) -> du/dt

with ``u`` and ``theta`` arbitrary pytrees and ``t`` a scalar.

``rk_step`` computes one step and returns the stage derivatives so that the
high-level discrete adjoint (``core/adjoint.py``) can reconstruct stage
inputs without re-evaluating ``f`` — this is the paper's "checkpoint the
states *and stage values*" design (PNODE).  ``rk_adjoint_step`` implements
the discrete adjoint recursion (eq. 7 of the paper, in the standard RK
adjoint form of Hager/Sandu): one transposed JVP of ``f`` per stage, so the
backpropagation graph depth is O(N_l), independent of N_t.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import tree_util as jtu

from repro.core.tableaus import ButcherTableau, get_tableau
from repro.obs.profile import scope

PyTree = Any
VectorField = Callable[[PyTree, PyTree, jax.Array], PyTree]


# ---------------------------------------------------------------------------
# pytree arithmetic helpers
# ---------------------------------------------------------------------------

def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jtu.tree_map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jtu.tree_map(jnp.subtract, a, b)


def tree_scale(s, a: PyTree) -> PyTree:
    return jtu.tree_map(lambda x: s * x, a)


def tree_axpy(s, x: PyTree, y: PyTree) -> PyTree:
    """y + s * x elementwise over the pytree."""
    return jtu.tree_map(lambda xi, yi: yi + s * xi, x, y)


def tree_zeros_like(a: PyTree) -> PyTree:
    return jtu.tree_map(jnp.zeros_like, a)


def tree_lincomb(coeffs, trees) -> PyTree:
    """sum_i coeffs[i] * trees[i]; skips zero coefficients (trace-time)."""
    acc = None
    for c, tr in zip(coeffs, trees):
        if isinstance(c, float) and c == 0.0:
            continue
        term = tree_scale(c, tr)
        acc = term if acc is None else tree_add(acc, term)
    if acc is None:
        acc = tree_zeros_like(trees[0])
    return acc


def tree_stage_lincomb(base: PyTree, pairs, scale=None,
                       base_coeff: float | None = None,
                       fused: bool = False) -> PyTree:
    """``base_coeff*base + sum (scale*w_i) * tree_i`` over (w_i, tree_i)
    ``pairs`` — the RK stage-update / stage-adjoint primitive.

    ``fused=False`` is the seed path: one ``tree_axpy`` per pair, exactly
    the historical accumulation order.  ``fused=True`` lowers the whole
    combination to ONE Pallas kernel per leaf (``kernels.ops.fused_lincomb``,
    interpret-mode on CPU) with the same accumulation order inside the
    kernel, so results are bitwise-identical under jit.  Callers must
    already have dropped zero-weight pairs (both paths assume it).
    """
    if not fused:
        out = base if base_coeff is None else tree_scale(base_coeff, base)
        for w, tr in pairs:
            out = tree_axpy(w if scale is None else scale * w, tr, out)
        return out
    from repro.kernels.ops import fused_lincomb  # deferred: keep core light
    weights = [w for w, _ in pairs]
    terms = [t for _, t in pairs]
    if not terms:
        return base if base_coeff is None else tree_scale(base_coeff, base)

    def leaf(b, *ts):
        if b.size == 0:  # degenerate leaf: nothing to fuse
            out = b if base_coeff is None else base_coeff * b
            for w, t in zip(weights, ts):
                out = out + (w if scale is None else scale * w) * t
            return out
        return fused_lincomb(b, ts, weights, scale, base_coeff)

    return jtu.tree_map(leaf, base, *terms)


def tree_stack(trees) -> PyTree:
    return jtu.tree_map(lambda *xs: jnp.stack(xs), *trees)


def tree_unstack(tree, n) -> list:
    return [jtu.tree_map(lambda x: x[i], tree) for i in range(n)]


def tree_dot(a: PyTree, b: PyTree) -> jax.Array:
    leaves = jtu.tree_map(lambda x, y: jnp.sum(x * y), a, b)
    return jtu.tree_reduce(jnp.add, leaves)


def tree_norm(a: PyTree) -> jax.Array:
    return jnp.sqrt(tree_dot(a, a))


def tree_cast(a: PyTree, dtype) -> PyTree:
    return jtu.tree_map(lambda x: x.astype(dtype), a)


# ---------------------------------------------------------------------------
# explicit RK stepping
# ---------------------------------------------------------------------------

def rk_stages(f: VectorField, tab: ButcherTableau, u: PyTree, theta: PyTree,
              t, h, fused: bool = False) -> list:
    """Compute the stage derivatives k_1..k_s (list of pytrees).
    ``fused=True`` builds each stage input with one Pallas lincomb kernel
    per leaf instead of a tree_axpy chain (bitwise-identical under jit)."""
    ks: list = []
    for i in range(tab.num_stages):
        pairs = [(float(tab.a[i, j]), ks[j]) for j in range(i)
                 if float(tab.a[i, j]) != 0.0]
        xi = tree_stage_lincomb(u, pairs, scale=h, fused=fused)
        with scope("vf"):
            ks.append(f(xi, theta, t + float(tab.c[i]) * h))
    return ks


def rk_combine(tab: ButcherTableau, u: PyTree, ks, h,
               fused: bool = False) -> PyTree:
    """u + h * sum_i b_i k_i."""
    pairs = [(float(tab.b[i]), ks[i]) for i in range(tab.num_stages)
             if float(tab.b[i]) != 0.0]
    return tree_stage_lincomb(u, pairs, scale=h, fused=fused)


def rk_step(f: VectorField, tab: ButcherTableau, u: PyTree, theta: PyTree,
            t, h, fused: bool = False) -> Tuple[PyTree, PyTree]:
    """One explicit RK step.  Returns (u_next, stages) with stages stacked
    along a new leading axis of size N_s (so it scans cleanly)."""
    ks = rk_stages(f, tab, u, theta, t, h, fused=fused)
    u_next = rk_combine(tab, u, ks, h, fused=fused)
    return u_next, tree_stack(ks)


def rk_stage_inputs(tab: ButcherTableau, u: PyTree, stages: PyTree, h,
                    fused: bool = False) -> list:
    """Reconstruct the stage inputs x_i = u + h*sum_j a_ij k_j from stored
    stage derivatives — no f evaluations (the PNODE trick)."""
    ks = tree_unstack(stages, tab.num_stages)
    xs = []
    for i in range(tab.num_stages):
        pairs = [(float(tab.a[i, j]), ks[j]) for j in range(i)
                 if float(tab.a[i, j]) != 0.0]
        xs.append(tree_stage_lincomb(u, pairs, scale=h, fused=fused))
    return xs


def _vf_at(f: VectorField, t):
    """``f`` at time ``t`` under the ``obs:vf`` scope, so that the ops of
    its linearisation carry ``jvp(obs:vf)`` and their transposes
    ``transpose(jvp(obs:vf))`` in their name stack."""
    def f_t(u, theta):
        with scope("vf"):
            return f(u, theta, t)
    return f_t


def rk_adjoint_step(f: VectorField, tab: ButcherTableau, u: PyTree,
                    stages: PyTree, theta: PyTree, t, h,
                    lam: PyTree, fused: bool = False) -> Tuple[PyTree, PyTree]:
    """Discrete adjoint of one explicit RK step (the paper's eq. 7).

    Given the step's initial state ``u``, its stored stage derivatives, and
    the incoming adjoint ``lam`` (= lambda_{n+1}), returns

        lam_prev  = (d u_{n+1} / d u_n)^T lam
        theta_bar = (d u_{n+1} / d theta)^T lam     (increment for mu)

    Implementation: reverse stage recursion
        v_i     = b_i * lam + sum_{j>i} a_ji * w_j
        (w_i, g_i) = vjp(f, x_i)(h * v_i)        # one transposed JVP per stage
        lam_prev = lam + sum_i w_i
        theta_bar = sum_i g_i
    """
    s = tab.num_stages
    xs = rk_stage_inputs(tab, u, stages, h, fused=fused)
    ws: list = [None] * s
    lam_prev = lam
    theta_bar = None
    for i in reversed(range(s)):
        if float(tab.b[i]) == 0.0 and all(
            float(tab.a[j, i]) == 0.0 for j in range(i + 1, s)
        ):
            ws[i] = None
            continue
        pairs = [(float(tab.a[j, i]), ws[j]) for j in range(i + 1, s)
                 if float(tab.a[j, i]) != 0.0 and ws[j] is not None]
        vi = tree_stage_lincomb(lam, pairs, base_coeff=float(tab.b[i]),
                                fused=fused)
        ti = t + float(tab.c[i]) * h
        _, vjp_fn = jax.vjp(_vf_at(f, ti), xs[i], theta)
        wi, gi = vjp_fn(tree_scale(h, vi))
        ws[i] = wi
        lam_prev = tree_add(lam_prev, wi)
        theta_bar = gi if theta_bar is None else tree_add(theta_bar, gi)
    if theta_bar is None:
        theta_bar = tree_zeros_like(theta)
    return lam_prev, theta_bar


# ---------------------------------------------------------------------------
# fixed-step forward solves
# ---------------------------------------------------------------------------

def solve_fixed(f: VectorField, method: str, u0: PyTree, theta: PyTree,
                t0: float, h: float, n_steps: int,
                save_states: bool = False,
                save_stages: bool = False,
                fused: bool = False):
    """Integrate n_steps of size h with a fixed-step explicit RK method.

    Returns (u_final, saved) where ``saved`` is a dict possibly containing
    'states' (the N_t *pre-step* states u_0..u_{N_t-1}) and 'stages'
    (N_t stacked stage pytrees).
    """
    tab = get_tableau(method)

    def body(carry, n):
        u = carry
        t = t0 + n.astype(jnp.result_type(float)) * h
        u_next, stages = rk_step(f, tab, u, theta, t, h, fused=fused)
        out = {}
        if save_states:
            out["states"] = u
        if save_stages:
            out["stages"] = stages
        return u_next, out

    u_final, saved = jax.lax.scan(body, u0, jnp.arange(n_steps))
    return u_final, saved


def solve_fixed_trajectory(f: VectorField, method: str, u0: PyTree,
                           theta: PyTree, t0: float, h: float, n_steps: int):
    """Like solve_fixed but returns the full trajectory u_1..u_{N_t}
    (stacked along a new leading axis), for plotting / loss-over-trajectory."""
    tab = get_tableau(method)

    def body(carry, n):
        u = carry
        t = t0 + n.astype(jnp.result_type(float)) * h
        u_next, _ = rk_step(f, tab, u, theta, t, h)
        return u_next, u_next

    u_final, traj = jax.lax.scan(body, u0, jnp.arange(n_steps))
    return u_final, traj
