"""High-level discrete adjoint ODE solves with checkpointing (the paper's core).

``odeint(f, u0, theta, ...)`` integrates du/dt = f(u, theta, t) for a fixed
number of steps and differentiates with a selectable *adjoint policy*.  Every
baseline of the paper's Table 2 is implemented:

  naive       NODE-naive: differentiate straight through the `lax.scan`
              (deepest graph; XLA stores per-step residuals: O(N_t N_s N_l)).
  continuous  NODE-cont (vanilla neural ODE): integrate the continuous
              adjoint ODE backward in time, re-solving the state backward.
              NOT reverse-accurate (O(h^2) per-step discrepancy, Prop. 1).
  anode       ANODE: checkpoint only the block input; in the reverse pass,
              recompute the whole forward and backprop through it.
  aca         ACA: checkpoint the state at every step; reverse pass
              re-executes each step under low-level AD (jax.vjp of the step).
  pnode       the paper's method: checkpoint states AND stage values at every
              step; reverse pass uses the high-level per-stage adjoint
              (rk_adjoint_step) — no recomputation, graph depth O(N_l).
  pnode2      PNODE2 variant: checkpoint solutions only; one step recompute
              per reverse step.
  revolve     PNODE with the binomial checkpointing schedule of Prop. 2
              (`ncheck` slots), trading recomputation for memory;
              ``revolve2`` is its two-level scanned variant.

Gradients are returned w.r.t. ``u0`` and ``theta``.  ``t0``/``dt`` are static.

mem — Table-2 cost model and budget planning
--------------------------------------------
Each policy is one point on the paper's memory/recompute curve; the mapping
to Table 2 (checkpoint storage in state-vectors, NFE-B in f evaluations) is
implemented analytically by ``checkpoint_floats`` / ``nfe_backward`` below
and, in byte units with working-set terms, by ``repro.mem.model``.  Two
knobs select the point automatically instead of by hand:

  ``adjoint="auto", mem_budget=B``  the ``repro.mem.planner`` solves for
      the cheapest reverse-accurate policy (and the minimal-recompute
      ``ncheck`` via Prop. 2) whose reverse pass fits in B bytes, verifying
      the choice against the lowered HLO by default (``mem_verify``).
  ``offload="host" | "spill" | "disk"``  checkpoints go through a
      ``repro.mem.offload`` store instead of riding the custom_vjp
      residuals, so device-live memory is O(ncheck) (revolve) or O(segment)
      (pnode) for any N_t; gradients are bitwise the in-device ones.  Each
      placement is one sweep in ``repro.core.sweeps``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.obs.profile import scope
from repro.core import revolve as revolve_mod
from repro.core import sweeps
from repro.core.integrators import (
    PyTree,
    VectorField,
    rk_adjoint_step,
    rk_combine,
    rk_stages,
    rk_step,
    solve_fixed,
    tree_add,
    tree_scale,
    tree_stack,
    tree_unstack,
    tree_zeros_like,
)
from repro.core.tableaus import get_tableau

POLICIES = ("naive", "continuous", "anode", "aca", "pnode", "pnode2",
            "revolve", "revolve2")


def _t_of(t0: float, dt: float, n) -> Any:
    return t0 + dt * n


#: policies whose reverse pass never differentiates *through* a step graph
#: (states/stages are checkpointed, the adjoint is the explicit per-stage
#: recursion) — the only ones the fused Pallas stage kernels apply to:
#: Pallas calls have no AD rules, so policies that jax.vjp through the
#: step (naive/continuous/anode/aca) must keep the unfused chain.
_FUSED_POLICIES = ("pnode", "pnode2", "revolve", "revolve2")


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def odeint(f: VectorField, u0: PyTree, theta: PyTree, *, dt: float,
           n_steps: int, t0: float = 0.0, method: str = "rk4",
           adjoint: str = "pnode", ncheck: int | None = None,
           offload: str | None = None, offload_segment: int | None = None,
           snaps_in_ram: int | None = None,
           offload_dir: str | None = None,
           offload_store=None,
           mem_budget: int | None = None,
           ram_budget: int | None = None,
           disk_budget: int | None = None,
           mem_verify: str = "measure",
           fused_stages: bool = False,
           obs=None) -> PyTree:
    """Fixed-step ODE solve, differentiable with the selected adjoint policy.

    ``adjoint="auto"`` with ``mem_budget=<bytes>`` delegates the policy (and
    ``ncheck``/``offload``) choice to ``repro.mem.planner``; ``mem_verify``
    selects how the planner checks the budget ("measure": against the
    lowered HLO's peak live bytes, compiled once and cached; "model": the
    analytic Table-2 model only, no compilation); ``ram_budget``/
    ``disk_budget`` bound the spill fallback's footprints (the planner
    solves the ``snaps_in_ram`` split).  ``offload`` routes the policy's
    checkpoints through a ``repro.mem.offload`` store tier ("disk" is the
    file-backed spill tier: same callbacks and bitwise contract);
    ``offload_segment`` sets the spill/disk tiers' segment length (one host
    callback per segment; default ceil(sqrt(n_steps))).  ``snaps_in_ram``
    caps the spill tier's RAM-resident slots (overflow sinks to disk
    files); ``offload_dir`` pins the disk tier's files to a caller-owned
    directory.  ``offload_store`` (scanned pnode spill/disk only) supplies
    a caller-OWNED ``SpillStore``/``DiskStore``: the serving engine keys
    its slots per request (``store.lane_keys``) and frees them as requests
    leave (``store.free_request``); the caller owns its lifetime and must
    not share it between concurrently traced solves.

    ``fused_stages=True`` lowers the RK stage-update chain (forward) and
    the per-stage adjoint recursion (reverse) to single Pallas
    linear-combination kernels (``kernels.ops.fused_lincomb``;
    interpret-mode on CPU, like the other kernels).  Gradients are
    bitwise-identical to the unfused path under jit.  Only the
    checkpointing policies (pnode/pnode2/revolve/revolve2) support it —
    the low-level-AD policies differentiate through the step graph and
    Pallas calls have no AD rules; ``adjoint="auto"`` drops the flag
    silently if the planner picks such a policy.

    ``obs=`` attaches a ``repro.obs.FlightRecorder``: the solve records a
    trace-time ``odeint.solve`` configuration event and binds the
    checkpoint store to the recorder, so every store put/get/free
    (trace-time schedule, device/host tiers) and every spill callback
    (runtime, with payload bytes) lands in the trace.  ``obs=None``
    (default) is zero-overhead — the traced program is identical, so
    gradients with a recorder attached are bitwise-identical to the
    unobserved solve.
    """
    n_steps = int(n_steps)
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    from_auto = adjoint == "auto"
    if from_auto:
        from repro.mem.planner import plan_odeint  # deferred: import cycle
        plan = plan_odeint(f, u0, theta, dt=float(dt), n_steps=n_steps,
                           t0=float(t0), method=method,
                           mem_budget=mem_budget, ram_budget=ram_budget,
                           disk_budget=disk_budget, verify=mem_verify)
        adjoint, ncheck = plan.policy, plan.ncheck
        offload = plan.offload if plan.offload is not None else offload
        if plan.snaps_in_ram is not None and snaps_in_ram is None:
            snaps_in_ram = plan.snaps_in_ram
    elif mem_budget is not None:
        raise ValueError(
            "mem_budget is only meaningful with adjoint='auto' (the planner "
            f"chooses the policy); got adjoint={adjoint!r}")
    elif ram_budget is not None or disk_budget is not None:
        raise ValueError(
            "ram_budget/disk_budget are only meaningful with adjoint='auto' "
            "(the planner solves the snaps_in_ram split); with an explicit "
            "policy pass offload='spill'/'disk' and snaps_in_ram directly; "
            f"got adjoint={adjoint!r}")
    if adjoint not in POLICIES:
        raise ValueError(f"unknown adjoint policy {adjoint!r}; one of "
                         f"{POLICIES} (or 'auto' with mem_budget)")
    if fused_stages and adjoint not in _FUSED_POLICIES:
        if from_auto:
            fused_stages = False
        else:
            raise ValueError(
                f"fused_stages=True is not supported for "
                f"adjoint={adjoint!r}: that policy differentiates through "
                "the step graph and the Pallas stage kernels have no AD "
                f"rules; use one of {_FUSED_POLICIES}")
    fused = bool(fused_stages)
    if adjoint in ("revolve", "revolve2"):
        ncheck = sweeps.validate_ncheck(adjoint, ncheck, n_steps)
    store, segment, offload = sweeps.resolve_offload(
        "odeint", u0, theta, tier=offload, policy=adjoint, n_steps=n_steps,
        segment=offload_segment, snaps_in_ram=snaps_in_ram,
        offload_dir=offload_dir, store=offload_store, obs=obs)
    if obs is not None:
        obs.record("odeint.solve", method=method, adjoint=adjoint,
                   n_steps=n_steps, dt=float(dt), t0=float(t0),
                   ncheck=None if ncheck is None else int(ncheck),
                   offload=offload, fused=fused,
                   planned=from_auto)
    if adjoint == "naive":
        u_final, _ = solve_fixed(f, method, u0, theta, t0, dt, n_steps)
        return u_final
    if adjoint in ("pnode", "revolve", "revolve2"):
        return sweeps.solve(RKStepper(f, float(t0), float(dt), method, fused),
                            adjoint, n_steps, u0, theta, ncheck=ncheck,
                            store=store, segment=segment)
    return _odeint_cv(f, method, float(t0), float(dt), int(n_steps),
                      adjoint, fused, u0, theta)


def nfe_forward(method: str, n_steps: int) -> int:
    return get_tableau(method).num_stages * n_steps


def adjoint_stages(method: str) -> int:
    """Stages the discrete adjoint actually linearizes: stage i is skipped
    when b_i == 0 and no later stage depends on it (e.g. dopri5's 7th/FSAL
    stage), so NFE-B can be below N_s per step."""
    tab = get_tableau(method)
    s = tab.num_stages
    return sum(
        1 for i in range(s)
        if float(tab.b[i]) != 0.0
        or any(float(tab.a[j, i]) != 0.0 for j in range(i + 1, s)))


def nfe_backward(method: str, n_steps: int, adjoint: str,
                 ncheck: int | None = None) -> int:
    """Analytic NFE-B (f evaluations in the reverse pass), Table-2 accounting.

    A transposed JVP of f costs one f evaluation (linearization); a recomputed
    step costs N_s evaluations.
    """
    s = get_tableau(method).num_stages
    sa = adjoint_stages(method)
    if adjoint == "naive":
        return 0
    if adjoint == "continuous":
        # backward solve of the augmented system: one f linearization per stage
        return s * n_steps
    if adjoint in ("anode", "aca"):
        # anode: full forward recompute + backprop through it; aca:
        # re-execute each step (s evals) + backprop its graph (s evals)
        return 2 * s * n_steps
    if adjoint == "pnode":
        return sa * n_steps
    if adjoint == "pnode2":
        # recompute stages of each step + per-stage vjps
        return s * n_steps + sa * n_steps
    if adjoint == "revolve":
        extra = revolve_mod.optimal_extra_steps(n_steps, ncheck)
        return s * extra + sa * n_steps
    if adjoint == "revolve2":
        # each non-boundary step re-advanced exactly once
        n_bound = len(sweeps.segment_bounds(n_steps, ncheck))
        return s * (n_steps - n_bound) + sa * n_steps
    raise ValueError(adjoint)


def checkpoint_floats(method: str, n_steps: int, adjoint: str, state_size: int,
                      ncheck: int | None = None) -> int:
    """Analytic checkpoint storage (in state-vector units x state_size)."""
    s = get_tableau(method).num_stages
    if adjoint in ("naive", "continuous"):
        return 0
    if adjoint == "anode":
        return state_size
    if adjoint == "aca":
        return n_steps * state_size
    if adjoint == "pnode":
        return n_steps * (s + 1) * state_size
    if adjoint == "pnode2":
        return n_steps * state_size
    if adjoint == "revolve":
        return (ncheck + 1) * (s + 1) * state_size  # +1: segment boundary
    if adjoint == "revolve2":
        # boundary states + one in-flight segment of states+stages
        bounds = sweeps.segment_bounds(n_steps, ncheck)
        seg = max(b - a for a, b in bounds)
        return (len(bounds) + seg * (s + 1)) * state_size
    raise ValueError(adjoint)


# ---------------------------------------------------------------------------
# custom_vjp core (continuous / anode / aca / pnode2)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6))
def _odeint_cv(f, method, t0, dt, n_steps, policy, fused, u0, theta):
    u_final, _ = solve_fixed(f, method, u0, theta, t0, dt, n_steps,
                             fused=fused)
    return u_final


@scope("adjoint/fwd")
def _odeint_cv_fwd(f, method, t0, dt, n_steps, policy, fused, u0, theta):
    if policy == "continuous":
        u_final, _ = solve_fixed(f, method, u0, theta, t0, dt, n_steps)
        return u_final, (u_final, theta)
    if policy == "anode":
        u_final, _ = solve_fixed(f, method, u0, theta, t0, dt, n_steps)
        return u_final, (u0, theta)
    if policy == "aca" or policy == "pnode2":
        u_final, saved = solve_fixed(f, method, u0, theta, t0, dt, n_steps,
                                     save_states=True, fused=fused)
        return u_final, (saved["states"], theta)
    raise ValueError(policy)


@scope("adjoint/bwd")
def _odeint_cv_bwd(f, method, t0, dt, n_steps, policy, fused, res, g):
    tab = get_tableau(method)

    if policy == "continuous":
        u_final, theta = res
        lam0 = g
        mu0 = tree_zeros_like(theta)

        def aug_f(state, th, t):
            u, lam, _ = state
            fval, vjp_fn = jax.vjp(lambda uu, tt: f(uu, tt, t), u, th)
            u_bar, th_bar = vjp_fn(lam)
            # integrated backward in time with negative dt below, so signs
            # follow d(lam)/dt = -f_u^T lam, d(mu)/dt = -f_th^T lam
            return (fval, tree_scale(-1.0, u_bar), tree_scale(-1.0, th_bar))

        state0 = (u_final, lam0, mu0)
        tF = t0 + dt * n_steps
        state_final, _ = solve_fixed(aug_f, method, state0, theta, tF, -dt,
                                     n_steps)
        _, lam, mu = state_final
        return lam, mu

    if policy == "anode":
        u0, theta = res

        def full(u0_, th_):
            uf, _ = solve_fixed(f, method, u0_, th_, t0, dt, n_steps)
            return uf

        _, vjp_fn = jax.vjp(full, u0, theta)
        return vjp_fn(g)

    if policy == "aca":
        states, theta = res  # states: pre-step states u_0..u_{N-1}, stacked

        def step_fn(u, th, t):
            u_next, _ = rk_step(f, tab, u, th, t, dt)
            return u_next

        def body(carry, inp):
            lam, mu = carry
            u_n, n = inp
            t_n = _t_of(t0, dt, n)
            _, vjp_fn = jax.vjp(lambda uu, th: step_fn(uu, th, t_n), u_n, theta)
            lam, th_bar = vjp_fn(lam)
            return (lam, tree_add(mu, th_bar)), None

        (lam, mu), _ = jax.lax.scan(
            body, (g, tree_zeros_like(theta)),
            (states, jnp.arange(n_steps)), reverse=True)
        return lam, mu

    if policy == "pnode2":
        states, theta = res

        def body(carry, inp):
            lam, mu = carry
            u_n, n = inp
            t_n = _t_of(t0, dt, n)
            ks = rk_stages(f, tab, u_n, theta, t_n, dt,  # recompute stages
                           fused=fused)
            lam, th_bar = rk_adjoint_step(f, tab, u_n, tree_stack(ks), theta,
                                          t_n, dt, lam, fused=fused)
            return (lam, tree_add(mu, th_bar)), None

        (lam, mu), _ = jax.lax.scan(
            body, (g, tree_zeros_like(theta)),
            (states, jnp.arange(n_steps)), reverse=True)
        return lam, mu

    raise ValueError(policy)


_odeint_cv.defvjp(_odeint_cv_fwd, _odeint_cv_bwd)


# ---------------------------------------------------------------------------
# the explicit RK stepper of the checkpointed sweeps (core.sweeps)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class RKStepper(sweeps.Stepper):
    """Explicit RK steps of size ``dt`` from ``t0``: a checkpoint is a
    step's state and its stacked stages, so the adjoint step needs no f
    evaluation to rebuild them and a revolve restart none to re-take the
    step (``rk_combine``)."""

    f: Callable
    t0: float
    dt: float
    method: str
    fused: bool = False

    scopes = {"dense": "adjoint", "revolve": "revolve",
              "revolve2": "revolve2", "segmented": "pnode_spill"}
    ckpt_steps = 1

    @property
    def tab(self):
        return get_tableau(self.method)

    def _rk(self, u, theta, t):
        return rk_step(self.f, self.tab, u, theta, t, self.dt,
                       fused=self.fused)

    def solve(self, u, theta, base, m, save=False, recompute=False):
        u, saved = solve_fixed(self.f, self.method, u, theta,
                               self.t0 + self.dt * base, self.dt, m,
                               save_states=save, save_stages=save,
                               fused=self.fused)
        return u, None, (saved["states"], saved["stages"]) if save else None

    def step(self, u, aux, theta, base, i):
        n = base + i
        # t as solve_fixed forms it, so the states are bitwise dense pnode's
        t = self.t0 + n.astype(jnp.result_type(float)) * self.dt
        u_next, stages = self._rk(u, theta, t)
        return u_next, aux, (u, stages), None

    def advance(self, u, aux, theta, start, m, recompute=False):
        if m <= 0:
            return u, aux

        def body(carry, k):
            return self._rk(carry, theta, _t_of(self.t0, self.dt,
                                                start + k))[0], None

        return jax.lax.scan(body, u, jnp.arange(m))[0], aux

    def capture(self, u, theta, n):
        u_next, stages = self._rk(u, theta, _t_of(self.t0, self.dt, n))
        return (u, stages), u_next

    def restart(self, ckpt):
        u, stages = ckpt
        return rk_combine(self.tab, u, tree_unstack(stages,
                                                     self.tab.num_stages),
                          self.dt, fused=self.fused)

    def adjoint(self, ckpt, u_next, theta, n, lam):
        u, stages = ckpt
        return rk_adjoint_step(self.f, self.tab, u, stages, theta,
                               _t_of(self.t0, self.dt, n), self.dt, lam,
                               fused=self.fused)


# ---------------------------------------------------------------------------
# trajectory-loss support (the paper's eq. 2 integral term)
# ---------------------------------------------------------------------------

def odeint_with_quadrature(f: VectorField, q, u0: PyTree, theta: PyTree, *,
                           dt: float, n_steps: int, t0: float = 0.0,
                           method: str = "rk4", adjoint: str = "pnode",
                           ncheck: int | None = None,
                           offload: str | None = None,
                           fused_stages: bool = False):
    """Integrate du/dt = f AND the loss quadrature dQ/dt = q(u, theta, t)
    jointly (eq. 2's integral term: running costs / Tikhonov / kinetic
    regularizers a la Finlay et al.).  Returns (u_final, Q).

    The augmented system is just another vector field, so every adjoint
    policy — including revolve checkpointing — applies unchanged, and the
    gradient of any function of (u_final, Q) is reverse-accurate."""
    def aug(state, th, t):
        u, _ = state
        return (f(u, th, t), q(u, th, t))

    q0 = jnp.zeros((), jnp.result_type(float))
    return odeint(aug, (u0, q0), theta, dt=dt, n_steps=n_steps, t0=t0,
                  method=method, adjoint=adjoint, ncheck=ncheck,
                  offload=offload, fused_stages=fused_stages)
