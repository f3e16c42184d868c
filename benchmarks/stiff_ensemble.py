"""Benchmark 4: a stiff Robertson-style ensemble trained under a byte budget.

The workload the implicit memory stack exists for: >= 1000 vmapped
Robertson-type kinetics systems (per-element rate multipliers as the
learnable parameters), integrated with the theta-method family and trained
through the implicit discrete adjoint while the planner holds the
checkpoint set under a device-byte budget.

The budget is set just below the cheapest in-device candidate's peak, so
``plan_odeint`` must fall back to the segment-batched spill tier — the one
offload tier that composes with vmap (per-batch-element checkpoints ride
inside the batched host callbacks; one callback per segment serves the
whole ensemble).  What BENCH_4.json locks down:

  * callbacks_per_grad   2*ceil(n_steps/segment), independent of ensemble
                         size — regressions here mean per-element host
                         round-trips crept in;
  * nfe_backward         the plan's predicted NFE-B (pnode's implicit
                         optimum: n_steps extra transposed-GMRES solves,
                         no Newton recompute);
  * grads_bitwise        spill gradients == in-device gradients, bit for
                         bit, under jit+vmap;
  * diverged_fraction    0.0 — every Newton solve in the ensemble
                         converged (the stats plumbing would catch a
                         silently-diverging stiff element);
  * training             the loss actually decreases over the AdamW steps.

Counter reads sit behind ``jax.block_until_ready``: jitted calls return
before the host callbacks run, so an eager read undercounts.

The run is in float64 (``run_ensemble`` turns x64 on for its own scope
only; importing this module leaves the process precision alone).
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.implicit import odeint_implicit
from repro.mem.model import tree_bytes
from repro.mem.offload import default_segment, reset_spill_stats, spill_stats
from repro.mem.planner import candidate_costs, plan_odeint
from repro.obs import (DEFAULT_REGISTRY, BaselineRef, Gate,
                       check_against_baseline as _obs_check)
from repro.optim.adamw import AdamW

# Robertson kinetics: u1' = -k1 u1 + k3 u2 u3, u2' = k1 u1 - k3 u2 u3
# - k2 u2^2, u3' = k2 u2^2.  The classic stiffness ratio: k2/k1 ~ 1e9.
K_BASE = (0.04, 3.0e7, 1.0e4)
#: loss weights undo the ~1e-5 scale of the u2 component
LOSS_W = jnp.array([1.0, 1.0e4, 1.0])


def robertson_vf(u, c, t):
    """RHS with per-system log-multipliers c (shape (3,)) on the rates."""
    k1, k2, k3 = (b * jnp.exp(ci) for b, ci in zip(K_BASE, c))
    du1 = -k1 * u[0] + k3 * u[1] * u[2]
    du3 = k2 * u[1] ** 2
    return jnp.stack([du1, -du1 - du3, du3])


def _solve(u0, c, *, dt, n_steps, method, adjoint="pnode", offload=None,
           return_stats=False):
    return odeint_implicit(robertson_vf, u0, c, dt=dt, n_steps=n_steps,
                           method=method, adjoint=adjoint, offload=offload,
                           newton_iters=16, newton_tol=1e-10,
                           gmres_iters=5, gmres_tol=1e-12,
                           return_stats=return_stats)


def run_ensemble(batch=1024, n_steps=30, train_steps=5, dt=0.01, lr=0.05,
                 seed=0):
    """Train the ensemble under a spill-forcing budget; return the record."""
    with jax.enable_x64(True):
        return _run_ensemble(batch, n_steps, train_steps, dt, lr, seed)


def _run_ensemble(batch, n_steps, train_steps, dt, lr, seed):
    # 16 Newton iters: the stiffest sampled elements converge linearly
    # (GMRES inexactness) and need >12 to hit newton_tol across the batch
    solver_opts = dict(newton_iters=16, gmres_iters=5)
    u0s = jnp.tile(jnp.array([1.0, 0.0, 0.0]), (batch, 1))
    key = jax.random.PRNGKey(seed)
    c_true = 0.2 * jax.random.normal(key, (batch, 3))
    c0 = jnp.zeros((batch, 3))

    # -- truth: the stiffness-robust end of the family (beuler) ------------
    truth = jax.jit(jax.vmap(lambda u, c: _solve(
        u, c, dt=dt, n_steps=n_steps, method="beuler")))(u0s, c_true)

    # -- plan: budget one byte below the cheapest in-device candidate ------
    cands = candidate_costs(method="cn", n_steps=n_steps,
                            state_bytes=tree_bytes(u0s),
                            theta_bytes=tree_bytes(c0),
                            solver_opts=solver_opts)
    budget = int(min(c.peak_bytes for c in cands)) - 1
    f_fold = jax.vmap(robertson_vf, in_axes=(0, 0, None))
    plan = plan_odeint(f_fold, u0s, c0, dt=dt, n_steps=n_steps, method="cn",
                       mem_budget=budget, verify="model",
                       solver_opts=solver_opts)
    assert plan.offload == "spill", plan

    def loss_fn(c, offload):
        uf = jax.vmap(lambda u, ci: _solve(
            u, ci, dt=dt, n_steps=n_steps, method="cn",
            adjoint=plan.policy, offload=offload))(u0s, c)
        return jnp.mean(jnp.sum((LOSS_W * (uf - truth)) ** 2, axis=-1))

    vgrad = jax.jit(jax.value_and_grad(lambda c: loss_fn(c, plan.offload)))
    vgrad_dev = jax.jit(jax.value_and_grad(lambda c: loss_fn(c, None)))

    # -- one warm gradient: time it and count the spill traffic ------------
    jax.block_until_ready(vgrad(c0))          # compile + warm the store
    reset_spill_stats()
    t0 = time.perf_counter()
    _, g_spill = vgrad(c0)
    jax.block_until_ready(g_spill)
    grad_seconds = time.perf_counter() - t0
    io = spill_stats()

    _, g_dev = vgrad_dev(c0)
    bitwise = bool(np.array_equal(np.asarray(g_spill), np.asarray(g_dev)))

    # -- convergence audit over the ensemble -------------------------------
    _, stats = jax.jit(jax.vmap(lambda u, c: _solve(
        u, c, dt=dt, n_steps=n_steps, method="cn",
        return_stats=True)))(u0s, c_true)
    diverged_fraction = float(jnp.mean(stats.diverged.astype(jnp.float64)))

    # -- train the rate multipliers under the plan -------------------------
    opt = AdamW(lr=lr, weight_decay=0.0, warmup_steps=1,
                total_steps=max(train_steps, 2))
    state = opt.init(c0)
    c, losses = c0, []
    for _ in range(train_steps):
        val, g = vgrad(c)
        losses.append(float(val))
        c, state, _ = opt.update(g, state, c)
    losses.append(float(vgrad(c)[0]))

    seg = default_segment(n_steps)
    return {
        "ensemble": int(batch),
        "n_steps": int(n_steps),
        "dt": float(dt),
        "method": "cn",
        "train_steps": int(train_steps),
        "plan": {
            "policy": plan.policy,
            "ncheck": plan.ncheck,
            "offload": plan.offload,
            "fits": bool(plan.fits),
            "budget_bytes": int(budget),
            "predicted_peak_bytes": int(plan.predicted.peak_bytes),
            "nfe_backward": int(plan.predicted.extra_fevals),
        },
        "effective_tier": "spill" if io["write_cb"] else "device",
        "segment": int(seg),
        "callbacks_per_grad": int(io["write_cb"] + io["read_cb"]),
        "write_cb": int(io["write_cb"]),
        "read_cb": int(io["read_cb"]),
        "write_slots": int(io["write_slots"]),
        "read_slots": int(io["read_slots"]),
        "grads_bitwise_vs_device": bitwise,
        "diverged_fraction": diverged_fraction,
        "losses": losses,
        "grad_seconds": float(grad_seconds),
    }


#: BENCH_4 regression gates, declared as data and evaluated by the
#: unified ``repro.obs.baseline`` checker (same machinery as BENCH_3).
GATES = [
    Gate("ensemble_size", "ensemble", ">=", BaselineRef("min_ensemble"),
         message="ensemble shrank below the recorded minimum"),
    Gate("spill_callbacks", "callbacks_per_grad", "<=",
         BaselineRef("max_callbacks_per_grad"),
         message="host callbacks per grad regressed"),
    Gate("nfe_backward", "plan.nfe_backward", "<=",
         BaselineRef("max_nfe_backward"), message="NFE-B regressed"),
    Gate("plan_spill", "plan.offload", "==", "spill",
         message="planner stopped selecting spill under the budget"),
    Gate("effective_spill", "effective_tier", "==", "spill",
         message="spill tier planned but no spill callbacks executed"),
    Gate("grads_bitwise", "grads_bitwise_vs_device", "truthy",
         message="spill gradients are not bitwise-identical to the "
                 "in-device gradients"),
    Gate("newton_converged", "diverged_fraction", "<=", 0.0,
         message="some of the ensemble's Newton solves diverged"),
    Gate("training", "loss_decreased", "truthy",
         message="training loss did not decrease"),
]


def check_against_baseline(rec, baseline_path="benchmarks/"
                           "bench4_baseline.json"):
    """Regression gates for CI; returns a list of error strings."""
    # derived field the declarative gate reads (first vs final loss)
    rec = dict(rec,
               loss_decreased=bool(rec["losses"][-1] < rec["losses"][0]))
    return _obs_check(rec, GATES, baseline_path, bench="stiff_ensemble",
                      registry=DEFAULT_REGISTRY)


def main(smoke=False, out_path="BENCH_4.json", check=False):
    if smoke:
        rec = run_ensemble(batch=1024, n_steps=30, train_steps=5)
    else:
        rec = run_ensemble(batch=2048, n_steps=60, train_steps=8)
    rec["smoke"] = bool(smoke)
    with open(out_path, "w") as fh:
        json.dump(rec, fh, indent=2)
    print(json.dumps(rec, indent=2))
    if check:
        errs = check_against_baseline(rec)
        if errs:
            for e in errs:
                print(f"BENCH_4 REGRESSION: {e}", file=sys.stderr)
            raise SystemExit(1)
        print("BENCH_4: all regression gates passed")
    return rec


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv, check="--check" in sys.argv)
