"""Implicit time integration with discrete adjoints (paper §3.3) under the
memory-plan / checkpoint-offload stack.

Theta-method family:  u_{n+1} = u_n + h [ (1-theta) f(u_n) + theta f(u_{n+1}) ]
  theta = 1.0  -> backward Euler   (paper eq. 12)
  theta = 0.5  -> Crank-Nicolson   (used for the stiff Robertson system, §5.3)

Forward pass: Newton iterations; each Newton step solves the linear system
(I - h*theta*J) dv = -r with matrix-free GMRES, the action of J = df/du
supplied by ``jax.jvp`` — exactly the paper's "matrix-free iterative method
whose matrix action comes from AD" design.

Reverse pass (discrete adjoint, paper eq. 13 generalized to theta-methods):
    (I - h*theta*f_u(u_{n+1}))^T lam_s = lam_{n+1}          (transposed GMRES,
                                                             action by jax.vjp)
    lam_n  = (I + h*(1-theta)*f_u(u_n))^T lam_s
    mu_n  += h * [ (1-theta) f_th(u_n) + theta f_th(u_{n+1}) ]^T lam_s

The nonlinear/linear solvers never enter the backpropagation graph — only
``f`` is differentiated (one vjp per GMRES/adjoint application), which is
the paper's key memory argument for implicit schemes AND what makes
checkpoint spacing cheap here: a checkpoint is one *converged state*
vector, the Newton/GMRES iterates are never stored.

Checkpoint policies (``adjoint=``), mirroring ``core/adjoint.py`` and run
by the same sweeps (``repro.core.sweeps``, with ``ThetaStepper``):

  pnode     every converged state; one transposed linear solve per reverse
            step, no recomputation.  ``offload="spill"``/``"disk"``
            streams them through the segment-batched store, and that path
            composes with ``jax.vmap``: one host round-trip per segment
            carries the whole batch, each element in its own block of the
            slot (see ``repro.mem.offload``).
  revolve   the Prop. 2 schedule over states (a slot costs S bytes, as an
            explicit RK slot does); segments are re-advanced by re-running
            Newton.
  revolve2  boundary states, each segment re-advanced once under a scan.
  auto      ``repro.mem.planner.plan_odeint`` picks (policy, ncheck,
            offload) under ``mem_budget=<bytes>`` with the implicit cost
            model (recompute newton_iters*(gmres_iters+2)+1 f evaluations
            a step, NFE-B gmres_iters+2 per adjoint solve).

``adjoint="naive"`` (AD through the solver) is impossible by construction:
Newton/GMRES run in ``while_loop``s that have no reverse rule — the
paper's motivating limitation.  The AD-through-a-dense-unrolled-Newton
oracle in tests/test_reverse_accuracy_implicit.py is the exactness
reference.

Convergence reporting: every path threads a converged flag and the final
Newton residual out of the step loop into ``ImplicitStats``
(``return_stats=True``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import tree_util as jtu
from jax.scipy.sparse.linalg import gmres

from repro.core import revolve as revolve_mod
from repro.core import sweeps
from repro.core.integrators import (
    PyTree,
    VectorField,
    tree_add,
    tree_axpy,
    tree_norm,
    tree_scale,
    tree_sub,
)

IMPLICIT_METHODS = ("beuler", "cn")
IMPLICIT_POLICIES = ("pnode", "revolve", "revolve2")


def _mass_apply(mass, transpose=False):
    if mass is None:
        return lambda u: u
    if callable(mass):  # caller supplies a self-adjoint / explicit transpose
        return mass
    return lambda u: jtu.tree_map(
        lambda x: (mass.T if transpose else mass) @ x, u)


def _theta_of(method: str) -> float:
    if method == "beuler":
        return 1.0
    if method == "cn":
        return 0.5
    raise ValueError(f"unknown implicit method {method!r}; use 'beuler' or "
                     "'cn'")


def is_implicit_method(method: str) -> bool:
    return method in IMPLICIT_METHODS


class StepInfo(NamedTuple):
    """Per-step Newton exit state (threaded out of the solve scan)."""
    iters: jax.Array      # Newton iterations taken
    residual: jax.Array   # final ||residual|| at exit
    converged: jax.Array  # residual <= newton_tol at exit


class ImplicitStats(NamedTuple):
    """Solve-level convergence report (see ``return_stats=``)."""
    diverged: jax.Array      # any step exited on newton_iters with r > tol
    max_residual: jax.Array  # worst final Newton residual across steps
    newton_iters: jax.Array  # total Newton iterations over the solve
    rescued: jax.Array       # steps recovered by a rescue retry (PR 8)


class RescueConfig(NamedTuple):
    """Divergence-rescue knobs (``odeint_implicit(rescue=...)``).

    On a failed step (Newton exhausted its iteration cap, or a non-finite
    state — e.g. an injected NaN f-eval), the step is retried with an
    ESCALATED iteration cap: retry r gets ``newton_iters * escalate**r``
    iterations.  Key property: the Newton ``while_loop`` exits dynamically
    on ``residual <= tol``, so a retry that converges where the fault-free
    run would have converged produces **bit-identical** values — the
    escalated cap only matters when it binds.  ``dt_halving`` adds a last
    resort after all retries: two h/2 sub-steps (theta-method order is
    preserved; values are NOT bitwise the single-step ones, so it only
    runs when everything bitwise-preserving already failed)."""
    max_retries: int = 1
    escalate: int = 4
    dt_halving: bool = True


class _SolverConfig(NamedTuple):
    """Static solver knobs.  ``rescue``/``fault``/``resilient`` default
    off: then ``_step`` stages no gates and the spill residuals carry no
    boundary states."""
    theta: float
    newton_iters: int
    newton_tol: float
    gmres_iters: int
    gmres_tol: float
    rescue: Any = None       # RescueConfig | None
    fault: Any = None        # repro.ft.FaultPlan | None
    resilient: bool = False  # checked prefetch + recompute fallback


def _stats_zero() -> ImplicitStats:
    return ImplicitStats(jnp.zeros((), jnp.bool_),
                         jnp.zeros((), jnp.result_type(float)),
                         jnp.zeros((), jnp.int32),
                         jnp.zeros((), jnp.int32))


def _stats_merge(stats: ImplicitStats, info: StepInfo,
                 rescued=None) -> ImplicitStats:
    return ImplicitStats(
        jnp.logical_or(stats.diverged, jnp.logical_not(info.converged)),
        jnp.maximum(stats.max_residual, info.residual),
        stats.newton_iters + info.iters.astype(jnp.int32),
        stats.rescued if rescued is None else stats.rescued + rescued)


# ---------------------------------------------------------------------------
# one implicit step (forward) and its discrete adjoint
# ---------------------------------------------------------------------------

def implicit_step(f: VectorField, u_n: PyTree, theta_p: PyTree, t_n, h,
                  theta: float, newton_iters: int = 10,
                  newton_tol: float = 1e-9, gmres_iters: int = 20,
                  gmres_tol: float = 1e-10, mass=None):
    """Solve M u_{n+1} = M u_n + h[(1-theta) f(u_n, t_n) + theta f(u_{n+1},
    t_{n+1})] (eq. 12 generalized; mass=None means M = I).

    Returns ``(u_{n+1}, StepInfo)`` — the converged flag is the Newton exit
    condition ``residual <= newton_tol``; callers that loop steps aggregate
    it into ``ImplicitStats`` instead of silently dropping non-convergence.
    """
    t_next = t_n + h
    f_n = f(u_n, theta_p, t_n)
    apply_m = _mass_apply(mass)
    # constant part g = M u_n + h (1-theta) f_n
    g_const = tree_axpy(h * (1.0 - theta), f_n, apply_m(u_n))

    def residual(v):
        return tree_sub(tree_axpy(-h * theta, f(v, theta_p, t_next),
                                  apply_m(v)), g_const)

    def newton_body(carry):
        v, it, _ = carry
        r = residual(v)

        def jv(w):
            # (M - h*theta*J) w, J = df/du at v — matrix-free via jvp
            _, jw = jax.jvp(lambda uu: f(uu, theta_p, t_next), (v,), (w,))
            return tree_axpy(-h * theta, jw, apply_m(w))

        dv, _ = gmres(jv, tree_scale(-1.0, r), tol=gmres_tol,
                      maxiter=gmres_iters, solve_method="incremental")
        v_new = tree_add(v, dv)
        return (v_new, it + 1, tree_norm(residual(v_new)))

    def newton_cond(carry):
        _, it, rnorm = carry
        return jnp.logical_and(it < newton_iters, rnorm > newton_tol)

    # predictor: explicit Euler
    v0 = tree_axpy(h, f_n, u_n)
    carry0 = (v0, jnp.array(0, jnp.int32), tree_norm(residual(v0)))
    v_final, iters, rnorm = jax.lax.while_loop(newton_cond, newton_body,
                                               carry0)
    return v_final, StepInfo(iters, rnorm, rnorm <= newton_tol)


def implicit_adjoint_step(f: VectorField, u_n: PyTree, u_next: PyTree,
                          theta_p: PyTree, t_n, h, theta: float,
                          lam: PyTree, gmres_iters: int = 20,
                          gmres_tol: float = 1e-10, mass=None):
    """One reverse step of the theta-method discrete adjoint (eq. 13)."""
    t_next = t_n + h
    apply_mt = _mass_apply(mass, transpose=True)

    # transposed linear solve: (M - h*theta*f_u(u_next))^T lam_s = lam
    _, vjp_next = jax.vjp(lambda uu, th: f(uu, th, t_next), u_next, theta_p)

    def jtv(w):
        u_bar, _ = vjp_next(w)
        return tree_axpy(-h * theta, u_bar, apply_mt(w))

    lam_s, _ = gmres(jtv, lam, tol=gmres_tol, maxiter=gmres_iters,
                     solve_method="incremental")

    # lam_n = M^T lam_s + h(1-theta) f_u(u_n)^T lam_s
    _, vjp_n = jax.vjp(lambda uu, th: f(uu, th, t_n), u_n, theta_p)
    u_bar_n, th_bar_n = vjp_n(tree_scale(h * (1.0 - theta), lam_s))
    lam_prev = tree_add(apply_mt(lam_s), u_bar_n)

    # mu increment
    _, th_bar_next = vjp_next(tree_scale(h * theta, lam_s))
    th_bar = tree_add(th_bar_n, th_bar_next)
    return lam_prev, th_bar


def _tree_allfinite(tree):
    fin = jnp.ones((), jnp.bool_)
    for x in jtu.tree_leaves(tree):
        fin = jnp.logical_and(fin, jnp.all(jnp.isfinite(x)))
    return fin


def _rescued_step(f, cfg: _SolverConfig, u, theta_p, t_n, h, idx):
    """One implicit step under fault injection and/or divergence rescue.

    Attempt 0 runs at the configured iteration cap; planned faults (keyed
    by the traced step index ``idx``, so they re-fire identically on
    adjoint recomputes) poison its *exit state* — NaN/Inf ``u1`` or a
    forced non-converged flag.  Poisoning the result rather than wrapping
    ``f`` keeps attempt 0's Newton loop HLO identical to the fault-free
    step at every clean index: a wrapped ``f`` inserts a select into the
    loop body, which perturbs XLA fusion under vmap and costs bitwise
    equality at sub-ulp level.  A failed attempt (not converged, or
    non-finite state) falls through a ``lax.cond`` chain: ``max_retries``
    clean retries at escalated Newton caps — bit-identical to the
    fault-free step whenever they converge, because the Newton while_loop
    exits dynamically on residual <= tol — then optionally two clean h/2
    sub-steps as a non-bitwise last resort.  Returns
    ``(u_next, StepInfo, rescued)`` with ``rescued`` an int32 flag: the
    accepted result came from a retry/halving branch.
    """
    rescue = cfg.rescue if cfg.rescue is not None else \
        RescueConfig(max_retries=0, escalate=1, dt_halving=False)
    fault = cfg.fault

    # attempt-0 fault gates (Python False when the plan has none)
    bad_nan = bad_inf = forced = False
    if fault is not None:
        bad_nan = fault.traced_gate("newton", "nan", idx)
        bad_inf = fault.traced_gate("newton", "inf", idx)
        forced = fault.traced_gate("newton", "diverge", idx)

    def poison(x):
        if bad_nan is not False:
            x = jnp.where(bad_nan, jnp.full_like(x, jnp.nan), x)
        if bad_inf is not False:
            x = jnp.where(bad_inf, jnp.full_like(x, jnp.inf), x)
        return x

    def attempt(iters, uu, tt, hh):
        return implicit_step(f, uu, theta_p, tt, hh, cfg.theta, int(iters),
                             cfg.newton_tol, cfg.gmres_iters, cfg.gmres_tol)

    def halved():
        cap = cfg.newton_iters * (rescue.escalate ** max(rescue.max_retries,
                                                         1))
        u_half, ia = attempt(cap, u, t_n, h * 0.5)
        u_full, ib = attempt(cap, u_half, t_n + h * 0.5, h * 0.5)
        info = StepInfo(ia.iters + ib.iters,
                        jnp.maximum(ia.residual, ib.residual),
                        jnp.logical_and(ia.converged, ib.converged))
        return u_full, info

    makers = [lambda: attempt(cfg.newton_iters, u, t_n, h)]
    for r in range(1, rescue.max_retries + 1):
        cap = cfg.newton_iters * (rescue.escalate ** r)
        makers.append(lambda cap=cap: attempt(cap, u, t_n, h))
    if rescue.dt_halving:
        makers.append(halved)

    def chain(i):
        u1, info = makers[i]()
        if i == 0:
            if bad_nan is not False or bad_inf is not False:
                u1 = jtu.tree_map(poison, u1)
                info = info._replace(residual=poison(info.residual))
            if forced is not False:
                info = info._replace(converged=jnp.logical_and(
                    info.converged, jnp.logical_not(forced)))
        ok = jnp.logical_and(info.converged, _tree_allfinite(u1))
        resc = jnp.asarray(1 if i > 0 else 0, jnp.int32)
        if i == len(makers) - 1:
            return u1, info, jnp.where(ok, resc, jnp.int32(0))
        return jax.lax.cond(ok,
                            lambda _: (u1, info, resc),
                            lambda _: chain(i + 1), None)

    return chain(0)


def _step(f, cfg: _SolverConfig, u, theta_p, t_n, h, idx=None):
    """Returns ``(u_next, StepInfo, rescued)``.  Dormant configs (no rescue,
    no fault plan) take the plain path with a constant-folded zero rescue
    count — the staged HLO is identical to the pre-rescue build."""
    if cfg.rescue is None and cfg.fault is None:
        u_next, info = implicit_step(f, u, theta_p, t_n, h, cfg.theta,
                                     cfg.newton_iters, cfg.newton_tol,
                                     cfg.gmres_iters, cfg.gmres_tol)
        return u_next, info, jnp.zeros((), jnp.int32)
    return _rescued_step(f, cfg, u, theta_p, t_n, h,
                         jnp.asarray(0 if idx is None else idx))


# ---------------------------------------------------------------------------
# Table-2-style accounting for the implicit family (the planner's model)
# ---------------------------------------------------------------------------

def implicit_step_fevals(newton_iters: int = 10,
                         gmres_iters: int = 20) -> int:
    """f evaluations one implicit step costs (the recompute unit): the
    predictor's f, plus per Newton iteration one residual f, one f
    linearization per GMRES iteration (the jvp matrix action), and the
    exit-residual f."""
    return int(newton_iters) * (int(gmres_iters) + 2) + 1


def implicit_adjoint_fevals(gmres_iters: int = 20) -> int:
    """f linearizations one discrete-adjoint step costs (NFE-B unit): one
    vjp application per transposed-GMRES iteration plus the two explicit
    vjps (lam_n and the theta increment)."""
    return int(gmres_iters) + 2


def implicit_nfe_forward(n_steps: int, newton_iters: int = 10,
                         gmres_iters: int = 20) -> int:
    return n_steps * implicit_step_fevals(newton_iters, gmres_iters)


def implicit_nfe_backward(n_steps: int, adjoint: str,
                          ncheck: int | None = None,
                          newton_iters: int = 10,
                          gmres_iters: int = 20) -> int:
    """Analytic NFE-B for the implicit policies: every policy pays one
    transposed-GMRES adjoint solve per step; revolve/revolve2 additionally
    re-run the Newton solve for recomputed steps."""
    adj = n_steps * implicit_adjoint_fevals(gmres_iters)
    stepc = implicit_step_fevals(newton_iters, gmres_iters)
    if adjoint == "pnode":
        return adj
    if adjoint == "revolve":
        return revolve_mod.optimal_extra_steps(n_steps, ncheck) * stepc + adj
    if adjoint == "revolve2":
        n_bound = len(sweeps.segment_bounds(n_steps, ncheck))
        return (n_steps - n_bound) * stepc + adj
    raise ValueError(adjoint)


def implicit_checkpoint_floats(n_steps: int, adjoint: str, state_size: int,
                               ncheck: int | None = None) -> int:
    """Checkpoint storage in floats: ONLY converged states are stored (the
    Newton/GMRES iterates never enter the graph), so a slot costs S."""
    if adjoint == "pnode":
        return (n_steps + 1) * state_size
    if adjoint == "revolve":
        return (ncheck + 1) * state_size
    if adjoint == "revolve2":
        bounds = sweeps.segment_bounds(n_steps, ncheck)
        seg = max(b - a for a, b in bounds)
        return (len(bounds) + seg + 1) * state_size
    raise ValueError(adjoint)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def odeint_implicit(f: VectorField, u0: PyTree, theta_p: PyTree, *, dt: float,
                    n_steps: int, t0: float = 0.0, method: str = "cn",
                    adjoint: str = "pnode", ncheck: int | None = None,
                    offload: str | None = None,
                    offload_segment: int | None = None,
                    snaps_in_ram: int | None = None,
                    offload_dir: str | None = None,
                    mem_budget: int | None = None,
                    mem_verify: str = "measure",
                    newton_iters: int = 10, newton_tol: float = 1e-9,
                    gmres_iters: int = 20, gmres_tol: float = 1e-10,
                    mass=None, return_stats: bool = False,
                    obs=None, rescue=None, fault_plan=None,
                    resilient: bool = False) -> PyTree:
    """Fixed-step implicit theta-method solve with a discrete adjoint.

    ``adjoint`` selects the checkpoint policy (module docstring; ``auto``
    + ``mem_budget=<bytes>`` delegates to the ``repro.mem`` planner).
    ``offload``, ``offload_segment``, ``snaps_in_ram`` and
    ``offload_dir`` route checkpoints through a ``repro.mem.offload``
    store exactly as in ``odeint``; gradients are bitwise-identical across
    tiers, and the scanned pnode spill/disk path composes with
    ``jax.vmap`` (slot-addressed revolve tiers reject it up front).
    ``return_stats=True`` returns ``(u_final, ImplicitStats)`` so
    Newton/GMRES non-convergence surfaces as ``stats.diverged`` instead of
    silently wrong states/gradients.

    ``obs=`` attaches a ``repro.obs.FlightRecorder``: every sweep emits
    a runtime ``implicit.steps`` event carrying the stacked per-step
    Newton exit states (iterations, residual, converged — one tap per
    scan, expanded back to per-step records by
    ``FlightRecorder.implicit_steps()``), reverse-pass re-advances emit
    ``implicit.recompute``, and the checkpoint store records its
    traffic.  Debug-effect taps only — gradients are bitwise-identical
    to ``obs=None``, which traces nothing extra (zero overhead off).

    Fault tolerance (all three knobs default OFF and stage zero extra ops
    when off):

    ``rescue=`` a ``RescueConfig`` (or ``True`` for the defaults) turns on
    in-step divergence rescue: a failed step (Newton cap exhausted, or a
    non-finite state) is retried at escalated iteration caps — bitwise the
    fault-free step when the retry converges, since the Newton while_loop
    exits dynamically — with an optional two-half-step (non-bitwise) last
    resort.  Rescued-step counts surface as ``stats.rescued`` and
    ``implicit.rescue`` obs events.

    ``fault_plan=`` a ``repro.ft.FaultPlan`` injects deterministic faults:
    traced ``newton`` nan/inf/diverge gates keyed by absolute step index
    (they re-fire identically on adjoint recomputes — required for bitwise
    recovery), host-side spill callback drops/corruption/flakes, and tier
    outages that degrade ``offload`` down the spill→disk→host→device
    ladder before the store is built.

    ``resilient=True`` (scanned pnode+spill path only) checksums spilled
    segments and, when the bwd prefetch fails verification, re-integrates
    the segment forward from its entry state carried in the residuals —
    reusing the recompute machinery, so recovered gradients stay bitwise
    the fault-free ones.
    """
    n_steps = int(n_steps)
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    theta = _theta_of(method)

    if mass is not None:
        if (adjoint != "pnode" or offload is not None
                or mem_budget is not None or rescue is not None
                or fault_plan is not None or resilient):
            raise ValueError(
                "mass-matrix solves support only the default dense path "
                "(adjoint='pnode', no offload/mem_budget and no "
                "rescue/fault_plan/resilient): the mass operator is closed "
                "over statically and the solve is forward-only (see "
                "_odeint_implicit_mass)")
        return _odeint_implicit_mass(f, mass, float(t0), float(dt), n_steps,
                                     theta, int(newton_iters),
                                     float(newton_tol), int(gmres_iters),
                                     float(gmres_tol), u0, theta_p,
                                     return_stats)

    from_auto = adjoint == "auto"
    if from_auto:
        from repro.mem.planner import plan_odeint  # deferred: import cycle
        plan = plan_odeint(
            f, u0, theta_p, dt=float(dt), n_steps=n_steps, t0=float(t0),
            method=method, mem_budget=mem_budget, verify=mem_verify,
            solver_opts=dict(newton_iters=int(newton_iters),
                             newton_tol=float(newton_tol),
                             gmres_iters=int(gmres_iters),
                             gmres_tol=float(gmres_tol)))
        adjoint, ncheck = plan.policy, plan.ncheck
        offload = plan.offload if plan.offload is not None else offload
        if plan.snaps_in_ram is not None and snaps_in_ram is None:
            snaps_in_ram = plan.snaps_in_ram
    elif mem_budget is not None:
        raise ValueError(
            "mem_budget is only meaningful with adjoint='auto' (the planner "
            f"chooses the policy); got adjoint={adjoint!r}")
    if adjoint == "naive":
        raise ValueError(
            "adjoint='naive' (AD through the solver) is impossible for "
            "implicit methods: Newton/GMRES run in while_loops with no "
            "reverse rule — the paper's motivating limitation; use one of "
            f"{IMPLICIT_POLICIES} (or 'auto' with mem_budget)")
    if adjoint not in IMPLICIT_POLICIES:
        raise ValueError(f"unknown implicit adjoint policy {adjoint!r}; one "
                         f"of {IMPLICIT_POLICIES} (or 'auto' with "
                         "mem_budget)")
    if rescue is True:
        rescue = RescueConfig()
    if rescue is not None and not isinstance(rescue, RescueConfig):
        raise ValueError(f"rescue must be a RescueConfig, True, or None; "
                         f"got {rescue!r}")
    if resilient and not (adjoint == "pnode"
                          and offload in ("spill", "disk")):
        raise ValueError(
            "resilient=True (checked prefetch + recompute fallback) applies "
            "to the scanned spill paths (adjoint='pnode', "
            f"offload='spill'/'disk'); got adjoint={adjoint!r}, "
            f"offload={offload!r}")
    if adjoint in ("revolve", "revolve2"):
        ncheck = sweeps.validate_ncheck(adjoint, ncheck, n_steps)
    store, segment, offload = sweeps.resolve_offload(
        f"odeint_implicit(adjoint={adjoint!r})", u0, theta_p, tier=offload,
        policy=adjoint, n_steps=n_steps, segment=offload_segment,
        snaps_in_ram=snaps_in_ram, offload_dir=offload_dir,
        fault_plan=fault_plan, integrity=bool(resilient), obs=obs)
    resilient = bool(resilient) and offload in ("spill", "disk")

    cfg = _SolverConfig(theta, int(newton_iters), float(newton_tol),
                        int(gmres_iters), float(gmres_tol),
                        rescue=rescue, fault=fault_plan,
                        resilient=resilient)
    t0, dt = float(t0), float(dt)
    if obs is not None:
        extra = {k: True for k, on in (("rescue", rescue is not None),
                                       ("faulted", fault_plan is not None),
                                       ("resilient", resilient)) if on}
        obs.record("implicit.solve", method=method, adjoint=adjoint,
                   n_steps=n_steps, dt=dt, t0=t0,
                   ncheck=None if ncheck is None else int(ncheck),
                   offload=offload, newton_iters=cfg.newton_iters,
                   gmres_iters=cfg.gmres_iters, planned=from_auto, **extra)

    u_final, stats = sweeps.solve(ThetaStepper(f, cfg, t0, dt, obs),
                                  adjoint, n_steps, u0, theta_p,
                                  ncheck=ncheck, store=store,
                                  segment=segment)
    return (u_final, stats) if return_stats else u_final


# ---------------------------------------------------------------------------
# mass-matrix path (forward-only; kept from the pre-offload implementation)
# ---------------------------------------------------------------------------

def _odeint_implicit_mass(f, mass, t0, dt, n_steps, theta, newton_iters,
                          newton_tol, gmres_iters, gmres_tol, u0, theta_p,
                          return_stats):
    """Mass-matrix path (no custom_vjp shortcut: the mass operator is
    closed over statically; forward-only use)."""
    def body(carry, n):
        u, stats = carry
        t_n = t0 + dt * n
        u_next, info = implicit_step(f, u, theta_p, t_n, dt, theta,
                                     newton_iters, newton_tol, gmres_iters,
                                     gmres_tol, mass=mass)
        return (u_next, _stats_merge(stats, info)), None

    (u_final, stats), _ = jax.lax.scan(body, (u0, _stats_zero()),
                                       jnp.arange(n_steps))
    return (u_final, stats) if return_stats else u_final


# ---------------------------------------------------------------------------
# the implicit stepper of the checkpointed sweeps (core.sweeps)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class ThetaStepper(sweeps.Stepper):
    """Theta-method steps: a checkpoint is one converged state, the
    adjoint step reads u_{n+1}, and a re-advance re-runs Newton — bitwise
    the forward's states, faults and rescues included, since every step's
    time is t0 + dt*(base+n) and its fault gates key on that index.  With
    ``obs``, a sweep emits its stacked ``StepInfo``s as one top-level
    ``implicit.steps`` (re-advances: ``implicit.recompute``) tap: a
    per-step tap in a scan is dropped in custom_vjp fwd rules on jax
    0.4.37 (``repro.obs.trace``).  Taps feed nothing."""

    f: Callable
    cfg: _SolverConfig
    t0: float
    dt: float
    obs: Any = None

    scopes = {"dense": "implicit", "revolve": "imp_revolve",
              "revolve2": "imp_revolve2", "segmented": "imp_spill"}
    needs_next = True

    @property
    def resilient(self):
        return self.cfg.resilient

    def _step(self, u, theta, base, n):
        return _step(self.f, self.cfg, u, theta,
                     self.t0 + self.dt * (base + n), self.dt, base + n)

    def shifted(self, states, u_end):
        return jtu.tree_map(
            lambda s, ue: jnp.concatenate([s[1:], ue[None]], axis=0),
            states, u_end)

    def _emit(self, kind, base, infos):
        self.obs.emit(kind, base=jnp.asarray(base), iters=infos.iters,
                      residual=infos.residual, converged=infos.converged)

    def aux0(self):
        return _stats_zero()

    def out(self, u, aux):
        return u, aux

    def cotangent(self, ct):
        return ct[0]  # the stats output is non-differentiable

    def solve(self, u, theta, base, m, save=False, recompute=False):
        track_rescue = (self.cfg.rescue is not None
                        or self.cfg.fault is not None)

        def body(carry, n):
            u, stats = carry
            u_next, info, resc = self._step(u, theta, base, n)
            ys = u if save else None
            if self.obs is not None:
                ys = (ys, info, resc if track_rescue else None)
            return (u_next, _stats_merge(stats, info, resc)), ys

        (u, stats), ys = jax.lax.scan(body, (u, _stats_zero()),
                                      jnp.arange(m))
        if self.obs is None:
            return u, stats, ys
        states, infos, rescs = ys
        self._emit("implicit.recompute" if recompute else "implicit.steps",
                   base, infos)
        if track_rescue:  # separate stream: dormant event logs unchanged
            self.obs.emit("implicit.rescue", base=jnp.asarray(base),
                          rescued=rescs)
        return u, stats, states

    def step(self, u, aux, theta, base, i):
        u_next, info, resc = self._step(u, theta, base, i)
        return (u_next, _stats_merge(aux, info, resc), u,
                info if self.obs is not None else None)

    def advance(self, u, aux, theta, start, m, recompute=False):
        if m <= 0:
            return u, aux

        def body(carry, k):
            u, st = carry
            u, info, resc = self._step(u, theta, start, k)
            return (u, st if st is None else _stats_merge(st, info, resc)), \
                (info if self.obs is not None else None)

        (u, aux), infos = jax.lax.scan(body, (u, aux), jnp.arange(m))
        if self.obs is not None:
            self._emit("implicit.recompute" if recompute else
                       "implicit.steps", start, infos)
        return u, aux

    def adjoint(self, ckpt, u_next, theta, n, lam):
        return implicit_adjoint_step(
            self.f, ckpt, u_next, theta, self.t0 + self.dt * n, self.dt,
            self.cfg.theta, lam, self.cfg.gmres_iters, self.cfg.gmres_tol)

    def recompute(self, u, theta, base, m):
        def body(u, i):
            return self._step(u, theta, base, i)[0], u

        return jax.lax.scan(body, u, jnp.arange(m))[1]

    def tap(self, seg_infos, rem_infos, rem_base, aux):
        if self.obs is None:
            return
        if seg_infos is not None:
            self._emit("implicit.steps", 0, jtu.tree_map(
                lambda a: a.reshape((-1,) + a.shape[2:]), seg_infos))
        if rem_infos is not None:
            self._emit("implicit.steps", rem_base, rem_infos)
        if self.cfg.rescue is not None or self.cfg.fault is not None:
            self.obs.emit("implicit.rescue", base=jnp.asarray(0),
                          rescued=aux.rescued)
