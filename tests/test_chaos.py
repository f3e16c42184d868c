"""Fault injection + recovery (PR 8): the chaos harness itself, spill
integrity + recompute fallback, the tier-degradation ladder, Newton
divergence rescue, adaptive NaN survival, checkpoint crash recovery, and
the train-loop sentinel/rollback/preemption paths.

The load-bearing assertions are *bitwise*: recovery must reproduce the
fault-free bits, not merely something close (the paper's reproducibility
contract extends to recovered runs)."""
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import (CheckpointManager, CheckpointWriteError,
                        available_steps, load_checkpoint, save_checkpoint)
from repro.core.adaptive import odeint_adaptive
from repro.core.implicit import RescueConfig, odeint_implicit
from repro.ft import FaultPlan, FaultSpec, SimulatedPreemption
from repro.ft.watchdog import TrainSupervisor
from repro.mem.offload import (effective_tier, reset_spill_stats,
                               spill_stats)
from repro.models.ode_nets import cnf_vf, cnf_vf_init
from repro.obs import MetricsRegistry
from repro.serve import AdmissionError, BucketSpec, ODEEngine

jax.config.update("jax_enable_x64", True)

# -- the shared solver problem (linear, stiff enough to need Newton) --------

N_STEPS, SEG, DT = 16, 4, 0.05
U0 = jnp.ones(3)
TH = jnp.asarray(0.7)


def _f(u, th, t):
    return -th * u


def _grad(theta, plan=None, rescue=None, resilient=False, **kw):
    def loss(th):
        uf = odeint_implicit(_f, U0, th, dt=DT, n_steps=N_STEPS,
                             method="cn", adjoint="pnode", offload="spill",
                             offload_segment=SEG, newton_iters=8,
                             newton_tol=1e-12, fault_plan=plan,
                             rescue=rescue, resilient=resilient, **kw)
        return jnp.sum(uf ** 2)

    return jax.jit(jax.grad(loss))(theta)


@pytest.fixture(scope="module")
def g_clean():
    return np.asarray(_grad(TH))


# -- the plan itself --------------------------------------------------------

def test_faultplan_tick_windows():
    plan = FaultPlan([FaultSpec("s", 2, "x"), FaultSpec("s", 5, "y",
                                                        count=3)])
    kinds = [getattr(plan.tick("s"), "kind", None) for _ in range(9)]
    assert kinds == [None, None, "x", None, None, "y", "y", "y", None]
    assert plan.calls("s") == 9
    assert plan.fired_count("s") == 4
    assert plan.fired_count("s", kind="y") == 3
    plan.reset()
    assert plan.calls("s") == 0 and plan.fired_count() == 0


def test_faultplan_traced_gate_static_false():
    plan = FaultPlan([FaultSpec("newton", 3, "nan")])
    # no matching (site, kind) => the Python constant False: dormant
    # callers stage zero ops
    assert plan.traced_gate("newton", "diverge", 3) is False
    assert plan.traced_gate("adaptive", "nan", 3) is False
    hit = plan.traced_gate("newton", "nan", jnp.arange(6))
    assert np.array_equal(np.asarray(hit),
                          [False, False, False, True, False, False])


def test_corrupt_arrays_deterministic_and_detectable():
    plan = FaultPlan(seed=7)
    a = np.zeros(8)  # all-zero payloads must corrupt too
    (bad,), (bad2,) = plan.corrupt_arrays([a], 3), plan.corrupt_arrays([a],
                                                                       3)
    assert np.array_equal(bad, bad2) and not np.array_equal(bad, a)


# -- spill integrity + recompute fallback -----------------------------------

def test_spill_corrupt_recompute_bitwise(g_clean):
    plan = FaultPlan([FaultSpec("spill.write", 1, "corrupt")])
    reset_spill_stats()
    g = _grad(TH, plan=plan, resilient=True)
    assert np.array_equal(np.asarray(g), g_clean)
    assert spill_stats()["integrity_fail"] >= 1
    assert plan.fired_count("spill.write") == 1


def test_spill_drop_vmap_bitwise():
    ths = jnp.array([0.5, 0.9])

    def batch(plan=None, resilient=False):
        def loss(th):
            uf = odeint_implicit(_f, U0, th, dt=DT, n_steps=N_STEPS,
                                 method="cn", adjoint="pnode",
                                 offload="spill", offload_segment=SEG,
                                 newton_iters=8, newton_tol=1e-12,
                                 fault_plan=plan, resilient=resilient)
            return jnp.sum(uf ** 2)

        return jax.jit(jax.vmap(jax.grad(loss)))(ths)

    g0 = np.asarray(batch())
    g1 = np.asarray(batch(FaultPlan([FaultSpec("spill.write", 2, "drop")]),
                          resilient=True))
    assert np.array_equal(g0, g1)


def test_spill_read_flake_transient_retries(g_clean):
    plan = FaultPlan([FaultSpec("spill.read", 0, "flake")])  # one attempt
    reset_spill_stats()
    g = _grad(TH, plan=plan, resilient=True)
    assert np.array_equal(np.asarray(g), g_clean)
    assert spill_stats()["retry_cb"] >= 1


def test_spill_read_flake_persistent_raises():
    # resilient=False reads have no recompute fallback: a read that still
    # flakes after every retry must raise, not return zeros
    plan = FaultPlan([FaultSpec("spill.read", 0, "flake", count=10_000)])
    with pytest.raises(Exception, match="retries"):
        # callback failures surface when the result is materialized, not
        # at dispatch
        jax.block_until_ready(_grad(TH, plan=plan))


# -- tier-degradation ladder ------------------------------------------------

def test_effective_tier_ladder():
    assert effective_tier("spill", None) == "spill"
    # spill outage lands on the file-backed disk tier first (same
    # callback protocol, scanned-capable), host/device only after it
    down = FaultPlan([FaultSpec("tier.spill", 0, "down")])
    assert effective_tier("spill", down) == "disk"
    assert effective_tier("spill", down, scanned=True) == "disk"
    spill_disk = FaultPlan([FaultSpec("tier.spill", 0, "down"),
                            FaultSpec("tier.disk", 0, "down")])
    assert effective_tier("spill", spill_disk) == "host"
    # the scanned sweeps cannot use the slot-addressed host tier
    assert effective_tier("spill", spill_disk, scanned=True) == "device"
    all_down = FaultPlan([FaultSpec("tier.spill", 0, "down"),
                          FaultSpec("tier.disk", 0, "down"),
                          FaultSpec("tier.host", 0, "down")])
    assert effective_tier("spill", all_down) == "device"


def test_tier_degrade_revolve_bitwise():
    def g(plan):
        def loss(th):
            uf = odeint_implicit(_f, U0, th, dt=DT, n_steps=N_STEPS,
                                 method="cn", adjoint="revolve", ncheck=4,
                                 offload="spill", newton_iters=8,
                                 newton_tol=1e-12, fault_plan=plan)
            return jnp.sum(uf ** 2)

        return np.asarray(jax.jit(jax.grad(loss))(TH))

    down = FaultPlan([FaultSpec("tier.spill", 0, "down")])
    assert np.array_equal(g(None), g(down))
    assert ("tier.disabled", "spill") in down.notes("tier.disabled")


# -- Newton divergence rescue ----------------------------------------------

def test_newton_diverge_rescued_bitwise(g_clean):
    plan = FaultPlan([FaultSpec("newton", 5, "diverge")])
    g = _grad(TH, plan=plan, rescue=True)
    assert np.array_equal(np.asarray(g), g_clean)


def test_newton_nan_rescued_bitwise(g_clean):
    plan = FaultPlan([FaultSpec("newton", 3, "nan")])
    g = _grad(TH, plan=plan, rescue=True)
    assert np.array_equal(np.asarray(g), g_clean)


def test_newton_rescue_stats():
    def stats(plan, rescue):
        _, st = jax.jit(lambda th: odeint_implicit(
            _f, U0, th, dt=DT, n_steps=N_STEPS, method="cn",
            newton_iters=8, newton_tol=1e-12, fault_plan=plan,
            rescue=rescue, return_stats=True))(TH)
        return st

    st = stats(FaultPlan([FaultSpec("newton", 5, "diverge")]), True)
    assert int(st.rescued) == 1 and not bool(st.diverged)
    st_no = stats(FaultPlan([FaultSpec("newton", 5, "diverge")]), None)
    assert bool(st_no.diverged)  # unrescued: the divergence is reported


def test_dt_halving_last_resort():
    # no retries allowed: the only escape from a forced divergence is the
    # two-half-steps branch — convergent but legitimately different bits
    plan = FaultPlan([FaultSpec("newton", 5, "diverge")])
    cfg = RescueConfig(max_retries=0, escalate=1, dt_halving=True)
    uf, st = jax.jit(lambda th: odeint_implicit(
        _f, U0, th, dt=DT, n_steps=N_STEPS, method="cn", newton_iters=8,
        newton_tol=1e-12, fault_plan=plan, rescue=cfg,
        return_stats=True))(TH)
    uf_clean = jax.jit(lambda th: odeint_implicit(
        _f, U0, th, dt=DT, n_steps=N_STEPS, method="cn", newton_iters=8,
        newton_tol=1e-12))(TH)
    assert int(st.rescued) == 1 and not bool(st.diverged)
    assert np.all(np.isfinite(np.asarray(uf)))
    assert np.allclose(np.asarray(uf), np.asarray(uf_clean), rtol=1e-5)


def test_rescue_dormant_is_bitwise_noop(g_clean):
    # rescue enabled but nothing fails: attempt 0 always converges, so the
    # chain takes its first branch and the result is the fault-free bits
    assert np.array_equal(np.asarray(_grad(TH, rescue=True)), g_clean)


# -- adaptive under poisoned attempts ---------------------------------------

def test_adaptive_nan_rejected_and_survives():
    plan = FaultPlan([FaultSpec("adaptive", 2, "nan", count=2)])
    uf, info = odeint_adaptive(_f, U0, TH, t0=0.0, t1=1.0, max_steps=64,
                               fault_plan=plan)
    uf_clean, _ = odeint_adaptive(_f, U0, TH, t0=0.0, t1=1.0, max_steps=64)
    assert np.all(np.isfinite(np.asarray(uf)))
    assert int(info.n_rejected) >= 2
    assert np.allclose(np.asarray(uf), np.asarray(uf_clean), rtol=1e-5)


def test_adaptive_persistent_nan_hits_attempt_cap():
    # every attempt poisoned: the controller must terminate (total-attempt
    # cap), not shrink dt forever in an unbounded while loop
    plan = FaultPlan([FaultSpec("adaptive", 0, "nan", count=10_000_000)])
    _, info = odeint_adaptive(_f, U0, TH, t0=0.0, t1=1.0, max_steps=8,
                              fault_plan=plan)
    assert int(info.n_accepted) == 0
    assert int(info.n_rejected) == 8 * 8


# -- checkpoint crash recovery ----------------------------------------------

def _tree():
    return {"w": jnp.arange(4.0), "b": jnp.zeros(2)}


def test_ckpt_async_commit_error_surfaces(tmp_path):
    mgr = CheckpointManager(tmp_path, fault_plan=FaultPlan(
        [FaultSpec("ckpt.write", 0, "error")]))
    mgr.save(0, _tree())
    with pytest.raises(CheckpointWriteError, match="disk full"):
        mgr.wait()
    mgr.wait()  # errors are cleared once raised
    mgr.save(1, _tree())  # the next commit is clean
    mgr.wait()
    assert available_steps(tmp_path) == [1]


def test_ckpt_shape_mismatch_names_leaf(tmp_path):
    save_checkpoint(tmp_path, 0, _tree())
    bad = {"w": jnp.zeros(5), "b": jnp.zeros(2)}
    with pytest.raises(ValueError, match=r"'w' has shape \(4,\).*\(5,\)"):
        load_checkpoint(tmp_path, bad)


def test_ckpt_crash_mid_write_recovery(tmp_path):
    save_checkpoint(tmp_path, 0, _tree())
    plan = FaultPlan([FaultSpec("ckpt.write", 0, "preempt")])
    with pytest.raises(SimulatedPreemption):
        save_checkpoint(tmp_path, 1, _tree(), fault_plan=plan)
    # the kill left an uncommitted tmp dir behind; restore ignores it
    stale = [p for p in Path(tmp_path).iterdir()
             if p.name.startswith(".tmp_step_")]
    assert len(stale) == 1
    assert available_steps(tmp_path) == [0]
    restored, step = load_checkpoint(tmp_path, _tree())
    assert step == 0
    assert np.array_equal(np.asarray(restored["w"]), np.arange(4.0))
    # the next job's manager init sweeps the stale dir
    CheckpointManager(tmp_path)
    assert not any(p.name.startswith(".tmp_step_")
                   for p in Path(tmp_path).iterdir())


# -- watchdog ---------------------------------------------------------------

def test_watchdog_raises_for_stall_during_step():
    import time
    sup = TrainSupervisor(heartbeat_timeout_s=0.1)
    sup.heartbeat.poll_s = 0.02
    with sup:
        sup.step(lambda: None, 0)
        with pytest.raises(TimeoutError, match="during step 1"):
            sup.step(lambda: time.sleep(0.5), 1)


# -- the train loop under chaos ---------------------------------------------

STEPS, CKPT_EVERY = 8, 4


@pytest.fixture(scope="module")
def lm_setup():
    from repro.configs.base import ShapeCell, reduced
    from repro.configs.registry import get_arch
    cfg = reduced(get_arch("smollm-135m"), n_layers=2)
    return cfg, ShapeCell("chaos", 32, 2, "train")


def _train(lm_setup, tmp, name, **kw):
    from repro.launch.train import train
    cfg, cell = lm_setup
    kw.setdefault("ckpt_every", CKPT_EVERY)
    return train(cfg, cell, steps=STEPS, ckpt_dir=f"{tmp}/{name}",
                 log_fn=lambda *a, **k: None, **kw)


@pytest.fixture(scope="module")
def clean_losses(lm_setup, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("chaos_clean")
    return _train(lm_setup, tmp, "clean")["losses"]


def test_train_sentinel_skip_bitwise(lm_setup, clean_losses, tmp_path):
    out = _train(lm_setup, tmp_path, "skip", fault_plan=FaultPlan(
        [FaultSpec("train.step", 3, "nan")]))
    assert out["skipped_steps"] == 1 and out["rollbacks"] == 0
    assert out["losses"] == clean_losses


def test_train_rollback_replay_bitwise(lm_setup, clean_losses, tmp_path):
    out = _train(lm_setup, tmp_path, "roll", sentinel_bad_steps=3,
                 fault_plan=FaultPlan([FaultSpec(
                     "train.step", CKPT_EVERY + 1, "nan", count=3)]))
    assert out["rollbacks"] == 1 and out["skipped_steps"] == 3
    assert out["losses"] == clean_losses


def test_train_divergent_run_raises(lm_setup, tmp_path):
    # no checkpoint to roll back to: a persistently-bad run must raise,
    # not spin forever
    with pytest.raises(FloatingPointError):
        _train(lm_setup, tmp_path, "div", fault_plan=FaultPlan(
            [FaultSpec("train.step", 0, "nan", count=10_000)]))


def test_train_preempt_drains_and_resumes(lm_setup, clean_losses,
                                          tmp_path):
    out = _train(lm_setup, tmp_path, "pre", ckpt_every=100,
                 fault_plan=FaultPlan(
                     [FaultSpec("train.step", 2, "preempt")]))
    assert out["preempted"] and out["losses"] == clean_losses[:3]
    assert available_steps(f"{tmp_path}/pre") == [3]
    res = _train(lm_setup, tmp_path, "pre")  # same dir: auto-resume
    assert res["resumed_from"] == 3
    assert out["losses"] + res["losses"] == clean_losses


# -- serve fault sites (PR 10) ----------------------------------------------

SERVE_DIM = 3


@pytest.fixture(autouse=True)
def _serve_f32(request):
    # the serve stack targets the f32 regime; this module runs with the
    # global x64 flag on, so pin it off for the serve tests only
    if "serve" not in request.node.name:
        yield
        return
    with jax.enable_x64(False):
        yield


def _serve_engine(plan=None, registry=None):
    theta = cnf_vf_init(jax.random.PRNGKey(0), SERVE_DIM, hidden=(8, 8))
    return ODEEngine(cnf_vf, theta, dim=SERVE_DIM, dt=0.05, n_steps=8,
                     offload="spill", offload_segment=4,
                     buckets=BucketSpec((4,)), fault_plan=plan,
                     registry=registry)


def test_serve_request_injected_malformed_and_oversize():
    """``serve.request`` faults are stopped at admission: the injected
    malformed and oversized arrivals raise ``AdmissionError`` (and count
    as rejections) while the clean request in between is served."""
    plan = FaultPlan([FaultSpec("serve.request", 0, "malformed"),
                      FaultSpec("serve.request", 2, "oversize")])
    reg = MetricsRegistry()
    eng = _serve_engine(plan, reg)
    x = np.zeros(SERVE_DIM, np.float32)
    with pytest.raises(AdmissionError, match="malformed"):
        eng.submit("density", x)
    tk = eng.submit("density", x)  # arrival index 1: admitted cleanly
    with pytest.raises(AdmissionError, match="oversize"):
        eng.submit("density", x)
    eng.run()
    assert np.isfinite(tk.result(5)).all()
    assert reg.counter("serve.rejected") == 2
    assert reg.counter("serve.completed") == 1


def test_serve_decode_nan_poisons_one_lane_only():
    """An injected decode NaN is a *request-level* fault: the poisoned
    lane's ticket errors, its three batch-mates resolve bitwise equal to
    the fault-free run, and the engine keeps serving afterwards."""
    rng = np.random.default_rng(3)
    xs = rng.normal(size=(4, SERVE_DIM)).astype(np.float32)

    def run(plan):
        reg = MetricsRegistry()
        eng = _serve_engine(plan, reg)
        ts = [eng.submit("density", x) for x in xs]
        assert eng.step() == 4  # all four share one bucket-4 batch
        return eng, reg, ts

    _, _, clean = run(None)
    clean_vals = [tk.result(5) for tk in clean]

    eng, reg, ts = run(FaultPlan([FaultSpec("serve.decode", 0, "nan")]))
    with pytest.raises(RuntimeError, match="non-finite"):
        ts[0].result(5)
    for tk, want in zip(ts[1:], clean_vals[1:]):
        assert np.array_equal(tk.result(5), want)
    assert reg.counter("serve.errors") == 1
    assert reg.counter("serve.completed") == 3
    census = eng.slot_census()
    assert not any(census.values()), census

    # the batch program is not poisoned: the next quantum serves cleanly
    after = eng.submit("density", xs[1])
    assert eng.step() == 1
    assert np.array_equal(after.result(5), clean_vals[1])
