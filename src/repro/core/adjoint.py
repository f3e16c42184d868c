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
              (`ncheck` slots), trading recomputation for memory.

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
  ``offload="host" | "spill"``      checkpoints are written through a
      ``repro.mem.offload`` store instead of riding the custom_vjp
      residuals: "host" moves revolve's trace-time checkpoints to
      pinned-host memory, "spill" streams scanned pnode / revolve
      checkpoints into a host-side callback store so device-live memory is
      O(ncheck) (revolve) or O(1) state copies (pnode) regardless of N_t.
      Gradients are bitwise-identical to the in-device policies.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import tree_util as jtu

from repro.obs.profile import scope
from repro.core import revolve as revolve_mod
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


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

_OFFLOAD_TIERS = (None, "device", "host", "spill", "disk")


def _validate_ncheck(adjoint: str, ncheck, n_steps: int) -> int:
    if ncheck is None:
        raise ValueError(
            f"adjoint={adjoint!r} requires ncheck (the number of checkpoint "
            "slots); pass it explicitly, or use adjoint='auto' with "
            "mem_budget=<bytes> and the planner will pick the minimal-"
            "recompute ncheck for the budget (Prop. 2)")
    ncheck = int(ncheck)
    if ncheck <= 0:
        raise ValueError(
            f"ncheck must be a positive number of checkpoint slots, got "
            f"{ncheck} (the reverse sweep needs at least one free slot to "
            "re-advance a segment)")
    if ncheck >= n_steps:
        raise ValueError(
            f"ncheck={ncheck} must be < n_steps={n_steps}: with a slot for "
            "every step there is nothing to recompute — that point of the "
            "memory/compute curve is adjoint='pnode' (or let "
            "adjoint='auto' choose)")
    return ncheck


#: policies whose reverse pass never differentiates *through* a step graph
#: (states/stages are checkpointed, the adjoint is the explicit per-stage
#: recursion) — the only ones the fused Pallas stage kernels apply to:
#: Pallas calls have no AD rules, so policies that jax.vjp through the
#: step (naive/continuous/anode/aca) must keep the unfused chain.
_FUSED_POLICIES = ("pnode", "pnode2", "revolve", "revolve2")


def _reject_vmap_offload(u0: PyTree, theta: PyTree, where: str) -> None:
    """vmap over a SLOT-ADDRESSED offload path fails deep inside the
    callback machinery with an opaque trace error (or, worse, aliases
    host-dict slots and returns wrong gradients); detect it up front.
    Only the trace-time slot-addressed paths (revolve/revolve2, and the
    host tier they imply) still reject: the scanned pnode spill/disk path
    composes with vmap — its segment-batched callbacks broadcast the
    mapped axes and each slot stores the full batch block (see the vmap
    notes in ``repro.mem.offload``).

    Leaves may be BatchTracers directly (vmap(odeint)) or wrap one deeper
    in the tracer stack (vmap(grad(...)): JVPTracers whose primals are
    BatchTracers), so unwrap nested tracers before testing.
    """
    from repro.mem.offload import is_batch_tracer  # deferred: import cycle

    def has_batch_tracer(x, depth=0) -> bool:
        if is_batch_tracer(x):
            return True
        if isinstance(x, jax.core.Tracer) and depth < 8:
            return any(
                sub is not None and has_batch_tracer(sub, depth + 1)
                for sub in (getattr(x, "primal", None),
                            getattr(x, "tangent", None),
                            getattr(x, "val", None)))
        return False

    if any(has_batch_tracer(x) for x in jtu.tree_leaves((u0, theta))):
        raise NotImplementedError(
            f"vmap over {where} with a slot-addressed offload store is not "
            "supported: the store's host-side dict sees one logical slot "
            "index for the entire batch, so per-example checkpoints would "
            "alias.  Workarounds: adjoint='pnode' with offload='spill'/"
            "'disk' (the scanned segment-batched path composes with vmap), "
            "offload='device' (checkpoints ride the residual pytree, which "
            "vmap understands), or fold the mapped axis into u0's leading "
            "batch dimension instead of vmapping.")


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
    analytic Table-2 model only, no compilation).  ``offload`` routes the
    policy's checkpoints through a ``repro.mem.offload`` store tier
    ("disk" is the file-backed spill tier — same callbacks and bitwise
    contract, payloads in segment files); ``offload_segment`` sets the
    spill/disk tiers' checkpoint-segment length (one host callback per
    segment; default ceil(sqrt(n_steps)) — see
    ``repro.mem.offload.default_segment``).  ``snaps_in_ram`` caps the
    spill tier's RAM-resident slot count (overflow sinks to disk files —
    the dolfin-adjoint multistage split, applying to scanned pnode
    segments and revolve slots alike); ``offload_dir`` pins the disk
    tier's segment files to a caller-owned directory (stale files swept
    on store init).  ``offload_store`` (advanced; scanned pnode
    spill/disk only) supplies a caller-OWNED ``SpillStore``/``DiskStore``
    instead of the per-call store ``odeint`` would build: the serving
    engine uses this to key checkpoint slots per request
    (``store.lane_keys``) and free them as requests leave the batch
    (``store.free_request``) — the caller then owns the store's lifetime
    and must not share it between concurrently traced solves.  With
    ``adjoint="auto"``, ``ram_budget``/
    ``disk_budget`` bound the spill fallback's RAM and disk footprints
    (the planner solves the ``snaps_in_ram`` split; see
    ``repro.mem.planner``).

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
    if offload not in _OFFLOAD_TIERS:
        raise ValueError(f"unknown offload tier {offload!r}; one of "
                         f"{_OFFLOAD_TIERS}")
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
    offloaded = offload in ("host", "spill", "disk")
    if offloaded and adjoint not in ("pnode", "revolve", "revolve2"):
        raise ValueError(
            f"offload={offload!r} is not supported for adjoint={adjoint!r}: "
            "only policies with explicit per-step checkpoints (pnode, "
            "revolve, revolve2) write through the store")
    if offload_segment is not None:
        if offload not in ("spill", "disk"):
            raise ValueError(
                "offload_segment only applies to the callback spill/disk "
                f"tiers; got offload={offload!r}")
        if adjoint != "pnode":
            raise ValueError(
                "offload_segment only applies to the scanned pnode sweep "
                f"(adjoint='pnode'); adjoint={adjoint!r} checkpoints are "
                "slot-addressed at trace time and already pay one callback "
                "per checkpoint-schedule action, so the knob would be "
                "silently ignored")
        offload_segment = int(offload_segment)
        if offload_segment < 1:
            raise ValueError(
                f"offload_segment must be >= 1, got {offload_segment}")
    if snaps_in_ram is not None:
        if offload != "spill":
            raise ValueError(
                "snaps_in_ram is the spill tier's RAM/disk split "
                "(offload='spill'; offload='disk' is already the "
                f"snaps_in_ram=0 corner); got offload={offload!r}")
        snaps_in_ram = int(snaps_in_ram)
        if snaps_in_ram < 0:
            raise ValueError(
                f"snaps_in_ram must be >= 0, got {snaps_in_ram}")
    if offload_dir is not None and offload not in ("spill", "disk"):
        raise ValueError(
            "offload_dir pins the disk tier's segment files "
            f"(offload='spill'/'disk'); got offload={offload!r}")
    if offload_store is not None and not (
            adjoint == "pnode" and offload in ("spill", "disk")):
        raise ValueError(
            "offload_store supplies a caller-owned store to the scanned "
            "pnode spill/disk path only (adjoint='pnode', "
            f"offload='spill'/'disk'); got adjoint={adjoint!r}, "
            f"offload={offload!r}")
    if offloaded and (adjoint in ("revolve", "revolve2")
                      or offload == "host"):
        # slot-addressed stores see one logical slot for the whole batch —
        # vmap would alias per-example checkpoints.  The scanned pnode
        # spill/disk path below composes with vmap: its segment-batched
        # callbacks broadcast the mapped axes, so each slot stores the
        # full batch block (or per-lane keyed rows under lane_keys).
        _reject_vmap_offload(u0, theta, "odeint")
    if obs is not None:
        obs.record("odeint.solve", method=method, adjoint=adjoint,
                   n_steps=n_steps, dt=float(dt), t0=float(t0),
                   ncheck=None if ncheck is None else int(ncheck),
                   offload=offload, fused=fused,
                   planned=from_auto)
    if adjoint == "naive":
        u_final, _ = solve_fixed(f, method, u0, theta, t0, dt, n_steps)
        return u_final
    if adjoint in ("revolve", "revolve2"):
        ncheck = _validate_ncheck(adjoint, ncheck, n_steps)
        from repro.mem.offload import make_store  # deferred: import cycle
        store = make_store(offload, snaps_in_ram=snaps_in_ram,
                           disk_dir=offload_dir)
        if obs is not None:
            store.bind_obs(obs)
        impl = _odeint_revolve if adjoint == "revolve" else _odeint_revolve2
        return impl(f, method, float(t0), float(dt), n_steps, ncheck,
                    store, fused, u0, theta)
    if adjoint == "pnode" and offloaded:
        if offload == "host":
            raise ValueError(
                "offload='host' applies to trace-time checkpoint sites "
                "(revolve/revolve2); the scanned pnode sweep offloads "
                "through offload='spill' or 'disk'")
        from repro.mem.offload import (batch_scale, default_segment,
                                       make_store)
        segment = (offload_segment if offload_segment is not None
                   else default_segment(n_steps))
        if offload_store is not None:
            store = offload_store
            if getattr(store, "tier", None) not in ("spill", "disk"):
                raise ValueError(
                    "offload_store must be a spill/disk-tier store "
                    f"(make_store('spill'|'disk')); got "
                    f"{type(store).__name__}")
        else:
            store = make_store(offload, snaps_in_ram=snaps_in_ram,
                               disk_dir=offload_dir)
        if obs is not None:
            store.bind_obs(obs)
        # mapped axes are only visible HERE (as BatchTracers on the args);
        # the custom_vjp fwd is retraced at logical shapes, so the store's
        # payload-cap chunking needs the batch factor handed to it
        store.payload_scale = batch_scale((u0, theta))
        return _odeint_pnode_spill(f, method, float(t0), float(dt), n_steps,
                                   store, min(segment, n_steps),
                                   fused, u0, theta)
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
    if adjoint == "anode":
        # full forward recompute + backprop through it
        return 2 * s * n_steps
    if adjoint == "aca":
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
        n_bound = len(revolve_mod.sweep_checkpoint_positions(n_steps,
                                                             ncheck)) + 1
        return s * (n_steps - n_bound) + sa * n_steps
    raise ValueError(adjoint)


def checkpoint_floats(method: str, n_steps: int, adjoint: str, state_size: int,
                      ncheck: int | None = None) -> int:
    """Analytic checkpoint storage (in state-vector units x state_size)."""
    s = get_tableau(method).num_stages
    if adjoint in ("naive",):
        return 0
    if adjoint == "continuous":
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
        bounds = [0] + revolve_mod.sweep_checkpoint_positions(n_steps, ncheck)
        seg = max(b - a for a, b in zip(bounds, bounds[1:] + [n_steps]))
        return (len(bounds) + seg * (s + 1)) * state_size
    raise ValueError(adjoint)


# ---------------------------------------------------------------------------
# custom_vjp core (continuous / anode / aca / pnode / pnode2)
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
    if policy == "pnode":
        u_final, saved = solve_fixed(f, method, u0, theta, t0, dt, n_steps,
                                     save_states=True, save_stages=True,
                                     fused=fused)
        return u_final, (saved["states"], saved["stages"], theta)
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

    if policy == "pnode":
        states, stages, theta = res

        def body(carry, inp):
            lam, mu = carry
            u_n, k_n, n = inp
            t_n = _t_of(t0, dt, n)
            lam, th_bar = rk_adjoint_step(f, tab, u_n, k_n, theta, t_n, dt,
                                          lam, fused=fused)
            return (lam, tree_add(mu, th_bar)), None

        (lam, mu), _ = jax.lax.scan(
            body, (g, tree_zeros_like(theta)),
            (states, stages, jnp.arange(n_steps)), reverse=True)
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
# revolve policy (binomial checkpointing, trace-time schedule)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def _odeint_revolve(f, method, t0, dt, n_steps, ncheck, store, fused, u0,
                    theta):
    u_final, _ = solve_fixed(f, method, u0, theta, t0, dt, n_steps,
                             fused=fused)
    return u_final


def _advance_segment(f, tab, u, theta, t_start_idx, n, t0, dt, fused=False):
    """Run n plain RK steps from u starting at step index t_start_idx."""
    if n <= 0:
        return u

    def body(carry, k):
        t = _t_of(t0, dt, t_start_idx + k)
        u_next, _ = rk_step(f, tab, carry, theta, t, dt, fused=fused)
        return u_next, None

    u_out, _ = jax.lax.scan(body, u, jnp.arange(n))
    return u_out


@scope("revolve/fwd")
def _odeint_revolve_fwd(f, method, t0, dt, n_steps, ncheck, store, fused, u0,
                        theta):
    tab = get_tableau(method)
    positions = [0] + revolve_mod.sweep_checkpoint_positions(n_steps, ncheck)
    u = u0
    bounds = positions + [n_steps]
    for a, b in zip(bounds[:-1], bounds[1:]):
        # execute step a explicitly to capture its stages for the checkpoint
        t_a = _t_of(t0, dt, a)
        u_next, stages_a = rk_step(f, tab, u, theta, t_a, dt, fused=fused)
        store.put(a, (u, stages_a))
        u = _advance_segment(f, tab, u_next, theta, a + 1, b - a - 1, t0, dt,
                             fused=fused)
    return u, (store.pack(), theta)


@scope("revolve/bwd")
def _odeint_revolve_bwd(f, method, t0, dt, n_steps, ncheck, store, fused, res,
                        g):
    tab = get_tableau(method)
    ckpt_res, theta = res
    positions = [0] + revolve_mod.sweep_checkpoint_positions(n_steps, ncheck)
    store.unpack(ckpt_res, positions)

    lam = g
    mu = tree_zeros_like(theta)
    for act in revolve_mod.reverse_schedule(n_steps, ncheck):
        kind = act[0]
        if kind == "advance":
            _, start, m = act
            u_s, st_s = store.get(start)
            # stage-combine restart: u_{start+1} with zero f evaluations
            u = rk_combine(tab, u_s, tree_unstack(st_s, tab.num_stages), dt,
                           fused=fused)
            u = _advance_segment(f, tab, u, theta, start + 1, m - 1, t0, dt,
                                 fused=fused)
            t_tgt = _t_of(t0, dt, start + m)
            _, stages_tgt = rk_step(f, tab, u, theta, t_tgt, dt, fused=fused)
            store.put(start + m, (u, stages_tgt))
        elif kind == "adjoint":
            _, idx = act
            u_i, st_i = store.get(idx)
            store.free(idx)
            t_i = _t_of(t0, dt, idx)
            lam, th_bar = rk_adjoint_step(f, tab, u_i, st_i, theta, t_i, dt,
                                          lam, fused=fused)
            mu = tree_add(mu, th_bar)
            # the schedule is unrolled at trace time; without a barrier XLA
            # may hoist every step's theta-sized stage gradients and keep
            # them live simultaneously (O(N_t N_s |theta|) temp instead of
            # O(|theta|)).  Serialize the chain explicitly.
            lam, mu = jax.lax.optimization_barrier((lam, mu))
        elif kind == "free":
            store.free(act[1])
        else:  # pragma: no cover
            raise ValueError(act)
    return lam, mu


_odeint_revolve.defvjp(_odeint_revolve_fwd, _odeint_revolve_bwd)


# ---------------------------------------------------------------------------
# revolve2: two-level binomial checkpointing with SCANNED per-segment adjoint
#
# The recursive `revolve` schedule above achieves the exact Prop-2 recompute
# optimum but unrolls one subgraph per action; XLA:CPU's parallel scheduler
# then refuses to overlap the per-step theta-gradient buffers, inflating
# compiled temp memory to O(N_t |theta|) even though true liveness is O(1)
# (see EXPERIMENTS.md SPerf).  revolve2 trades a small amount of recompute
# optimality for a *scanned* executor whose compiled liveness is bounded on
# every backend: the forward sweep stores only the `ncheck` boundary states
# chosen by the optimal sweep placement; the reverse pass re-advances each
# segment once (saving its states+stages inside a scan) and then scans the
# high-level stage adjoint backward over it.  Memory: ncheck states +
# max_segment*(N_s+1) states + O(|theta|).  Recompute: N_t - ncheck - 1
# steps (the t<=2 regime of Prop. 2, where it matches the optimum up to one
# step per segment).  This is the production default for LM-scale training.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def _odeint_revolve2(f, method, t0, dt, n_steps, ncheck, store, fused, u0,
                     theta):
    u_final, _ = solve_fixed(f, method, u0, theta, t0, dt, n_steps,
                             fused=fused)
    return u_final


def _segment_bounds(n_steps: int, ncheck: int):
    positions = [0] + revolve_mod.sweep_checkpoint_positions(n_steps, ncheck)
    return list(zip(positions, positions[1:] + [n_steps]))


@scope("revolve2/fwd")
def _odeint_revolve2_fwd(f, method, t0, dt, n_steps, ncheck, store, fused, u0,
                         theta):
    bounds = _segment_bounds(n_steps, ncheck)
    u = u0
    for a, b in bounds:
        store.put(a, u)
        u = _advance_segment(f, get_tableau(method), u, theta, a, b - a,
                             t0, dt, fused=fused)
    return u, (store.pack(), theta)


@scope("revolve2/bwd")
def _odeint_revolve2_bwd(f, method, t0, dt, n_steps, ncheck, store, fused,
                         res, g):
    tab = get_tableau(method)
    ckpt_res, theta = res
    bounds = _segment_bounds(n_steps, ncheck)
    store.unpack(ckpt_res, [a for a, _ in bounds])

    lam = g
    mu = tree_zeros_like(theta)
    for a, b in reversed(bounds):
        m = b - a
        u_a = store.get(a)
        store.free(a)
        # re-advance the segment, saving states and stages (scan)
        _, saved = solve_fixed(f, method, u_a, theta, t0 + dt * a, dt, m,
                               save_states=True, save_stages=True,
                               fused=fused)

        def body(carry, inp):
            lam_, mu_ = carry
            u_n, k_n, n = inp
            t_n = t0 + dt * (a + n)
            lam_, th_bar = rk_adjoint_step(f, tab, u_n, k_n, theta, t_n, dt,
                                           lam_, fused=fused)
            return (lam_, tree_add(mu_, th_bar)), None

        (lam, mu), _ = jax.lax.scan(
            body, (lam, mu),
            (saved["states"], saved["stages"], jnp.arange(m)), reverse=True)
    return lam, mu


_odeint_revolve2.defvjp(_odeint_revolve2_fwd, _odeint_revolve2_bwd)


# ---------------------------------------------------------------------------
# pnode with spill offload: the scanned forward sweep streams (state, stages)
# checkpoints into the host-side store instead of stacking them in device
# residual buffers; the reverse scan streams them back.  The residual is a
# single token scalar, so compiled device-live memory is O(segment) state
# copies regardless of N_t while the adjoint math — and therefore the
# gradients, bitwise — is exactly pnode's (tests/test_mem.py).
#
# I/O is SEGMENT-BATCHED: an inner scan stages `segment` consecutive steps'
# checkpoints in a small device buffer, then one `write_batch` callback
# ships the whole segment; the reverse sweep mirrors it with one `prefetch`
# callback per segment.  Host round-trips per reverse pass drop from
# 2*N_t to 2*ceil(N_t/segment) (BENCH_3), at a device cost of
# segment*(N_s+1) staged state vectors — sublinear with the default
# segment = ceil(sqrt(N_t)) (repro.mem.offload.default_segment).
#
# The reverse sweep is additionally SOFTWARE-PIPELINED: right after waiting
# on segment k's prefetch it issues the background gather of segment k-1
# (`prefetch_issue` — a token-only callback that queues the host/disk read
# on the store's executor), so segment I/O overlaps the adjoint compute of
# the segment in hand.  Works for the RAM dict and the disk tier alike;
# `prefetch_hit_cb` counts how many waits were actually served from the
# pipeline.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def _odeint_pnode_spill(f, method, t0, dt, n_steps, store, segment, fused,
                        u0, theta):
    u_final, _ = solve_fixed(f, method, u0, theta, t0, dt, n_steps,
                             fused=fused)
    return u_final


@scope("pnode_spill/fwd")
def _odeint_pnode_spill_fwd(f, method, t0, dt, n_steps, store, segment,
                            fused, u0, theta):
    tab = get_tableau(method)
    n_full, rem = divmod(n_steps, segment)

    def run_segment(u, tok, base, m):
        # base: first step index of the segment (traced or static); m static
        def step(carry, i):
            u = carry
            n = base + i
            t = t0 + n.astype(jnp.result_type(float)) * dt  # = solve_fixed
            u_next, stages = rk_step(f, tab, u, theta, t, dt, fused=fused)
            return u_next, (u, stages)

        u, staged = jax.lax.scan(step, u, jnp.arange(m))
        tok = store.write_batch(tok, base, staged)  # ONE callback, m slots
        return u, tok

    u, tok = u0, store.init_token()
    if n_full:
        def seg_body(carry, s_idx):
            u, tok = carry
            u, tok = run_segment(u, tok, s_idx * segment, segment)
            return (u, tok), None

        (u, tok), _ = jax.lax.scan(seg_body, (u, tok), jnp.arange(n_full))
    if rem:
        u, tok = run_segment(u, tok, jnp.asarray(n_full * segment), rem)
    return u, (tok, theta)


@scope("pnode_spill/bwd")
def _odeint_pnode_spill_bwd(f, method, t0, dt, n_steps, store, segment,
                            fused, res, g):
    tab = get_tableau(method)
    tok, theta = res
    n_full, rem = divmod(n_steps, segment)

    def run_segment_bwd(lam, mu, tok, base, m):
        tok, staged = store.prefetch(tok, base, m)  # ONE callback, m slots
        # software pipelining: with this segment's data in hand, dispatch
        # the background gather of the NEXT segment to be consumed (the
        # earlier one — the sweep runs in reverse), so its host/disk I/O
        # overlaps the adjoint compute below.  The issue rides the token
        # chain, so it cannot reorder around the read it follows.
        nb = base - segment
        tok = jax.lax.cond(
            nb >= 0,
            lambda t: store.prefetch_issue(t, jnp.maximum(nb, 0), segment),
            lambda t: t, tok)

        def step(carry, i):
            lam, mu = carry
            u_n, k_n = jtu.tree_map(lambda b: b[i], staged)
            t_n = _t_of(t0, dt, base + i)
            lam, th_bar = rk_adjoint_step(f, tab, u_n, k_n, theta, t_n, dt,
                                          lam, fused=fused)
            return (lam, tree_add(mu, th_bar)), None

        (lam, mu), _ = jax.lax.scan(step, (lam, mu), jnp.arange(m),
                                    reverse=True)
        return lam, mu, tok

    lam, mu = g, tree_zeros_like(theta)
    if rem:  # the trailing partial segment is adjointed first
        lam, mu, tok = run_segment_bwd(lam, mu, tok,
                                       jnp.asarray(n_full * segment), rem)
    elif n_full:  # no remainder: warm the pipeline for the first read
        tok = store.prefetch_issue(tok, jnp.asarray((n_full - 1) * segment),
                                   segment)
    if n_full:
        def seg_body(carry, s_idx):
            lam, mu, tok = carry
            lam, mu, tok = run_segment_bwd(lam, mu, tok, s_idx * segment,
                                           segment)
            return (lam, mu, tok), None

        (lam, mu, tok), _ = jax.lax.scan(seg_body, (lam, mu, tok),
                                         jnp.arange(n_full), reverse=True)
    return lam, mu


_odeint_pnode_spill.defvjp(_odeint_pnode_spill_fwd, _odeint_pnode_spill_bwd)


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
    u_final, Q = odeint(aug, (u0, q0), theta, dt=dt, n_steps=n_steps, t0=t0,
                        method=method, adjoint=adjoint, ncheck=ncheck,
                        offload=offload, fused_stages=fused_stages)
    return u_final, Q
