"""repro.mem: offload-store gradient identity, budget planner, cost model,
and the odeint(adjoint="auto", mem_budget=...) acceptance criterion.

Offload grads must be *bitwise* identical to the in-device policies: the
store only relocates checkpoints, the adjoint arithmetic (op sequence and
operand values) is unchanged.  Planner monotonicity and the auto-policy
budget check are deterministic parametrized cases (no hypothesis — the
offline stub has no shrinking to offer here anyway).
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adaptive import odeint_adaptive
from repro.core.adjoint import odeint
from repro.ft import FaultPlan, FaultSpec
from repro.mem import (DeviceStore, HostStore, SpillStore, candidate_costs,
                       host_memory_kind, measure_reverse_cost,
                       plan_depth_remat, plan_odeint, policy_cost,
                       tree_bytes)
from repro.mem import offload as offload_mod

jax.config.update("jax_enable_x64", True)

D = 6
N_STEPS = 12
DT = 0.05


def _vf():
    def f(u, th, t):
        return jnp.tanh(th["W"] @ u + th["b"]) + 0.1 * jnp.sin(t) * u
    return f


def _problem(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    u0 = jax.random.normal(ks[0], (D,))
    th = {"W": 0.3 * jax.random.normal(ks[1], (D, D)),
          "b": 0.1 * jax.random.normal(ks[2], (D,))}
    return u0, th


def _grads(policy, *, method="rk4", n_steps=N_STEPS, **kw):
    f = _vf()
    u0, th = _problem()

    def loss(u0_, th_):
        uf = odeint(f, u0_, th_, dt=DT, n_steps=n_steps, method=method,
                    adjoint=policy, **kw)
        return jnp.sum(uf ** 2)

    return jax.grad(loss, argnums=(0, 1))(u0, th)


def _assert_bitwise(g, g_ref):
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# offload stores: gradient identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,kw", [
    ("pnode", {}),
    ("revolve", {"ncheck": 3}),
    ("revolve2", {"ncheck": 3}),
])
def test_spill_grads_bitwise_identical(policy, kw):
    """Host-spilled checkpoints change WHERE data lives, not the math."""
    _assert_bitwise(_grads(policy, offload="spill", **kw),
                    _grads(policy, **kw))


@pytest.mark.parametrize("policy,kw", [("revolve", {"ncheck": 3}),
                                       ("revolve2", {"ncheck": 2})])
def test_host_offload_grads_bitwise_identical(policy, kw):
    """pinned-host tier (a memory-kind transfer; exact)."""
    _assert_bitwise(_grads(policy, offload="host", **kw),
                    _grads(policy, **kw))


def test_spill_grads_under_jit():
    f = _vf()
    u0, th = _problem()

    def gfn(offload):
        def L(u0_, th_):
            return jnp.sum(odeint(f, u0_, th_, dt=DT, n_steps=N_STEPS,
                                  adjoint="pnode", offload=offload) ** 2)
        return jax.jit(jax.grad(L, argnums=(0, 1)))(u0, th)

    _assert_bitwise(gfn("spill"), gfn(None))


def test_adaptive_spill_grads_bitwise_identical():
    f = _vf()
    u0, th = _problem()

    def gfn(offload):
        def L(u0_, th_):
            uf, _ = odeint_adaptive(f, u0_, th_, t0=0.0, t1=0.6,
                                    rtol=1e-6, atol=1e-6, max_steps=64,
                                    offload=offload)
            return jnp.sum(uf ** 2)
        return jax.grad(L, argnums=(0, 1))(u0, th)

    _assert_bitwise(gfn("spill"), gfn(None))


def test_host_store_degrades_on_cpu_and_reports(monkeypatch):
    """The host tier runs as ``host`` where pinned host memory exists
    (XLA:CPU exposes it too) and raises where it does not, instead of
    quietly keeping the checkpoints on the device."""
    assert host_memory_kind() == "pinned_host"
    assert HostStore().effective_tier == "host"
    monkeypatch.setattr(offload_mod, "host_memory_kind", lambda: None)
    with pytest.raises(RuntimeError, match="pinned_host"):
        HostStore()


def test_spill_store_roundtrip_and_free():
    st = SpillStore()
    tree = {"a": jnp.arange(4.0), "b": (jnp.ones((2, 3)),)}
    st.put(5, tree)
    jax.block_until_ready(st._tok)
    got = st.get(5)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st.free(5)
    jax.block_until_ready(st._tok)
    assert 5 not in st._host


def test_device_store_pack_order_matches_slots():
    st = DeviceStore()
    st.put(0, "x0")
    st.put(7, "x7")
    assert st.pack() == ("x0", "x7")
    st2 = DeviceStore()
    st2.unpack(("x0", "x7"), [0, 7])
    assert st2.get(7) == "x7"


# ---------------------------------------------------------------------------
# spill payload ownership: the store keeps the arrays jax hands its write
# callbacks and returns them as they are, copying only where it must
# ---------------------------------------------------------------------------

def _churn():
    # unrelated jitted work of the checkpoints' sizes, then a collection:
    # a kept view whose buffer had been released would now read garbage
    junk = [jax.jit(lambda z, k=k: z * 3.0 + k)(jnp.ones((4, 3)))
            for k in range(64)]
    jax.block_until_ready(junk)
    del junk
    gc.collect()


def test_spill_views_outlive_the_write_call():
    st = offload_mod.make_store("spill")
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 3))
    y = jax.random.normal(jax.random.PRNGKey(4), (3,))
    # doubling is exact, so the reference matches the written bytes
    tok = jax.jit(lambda t, x: st.write_batch(t, 0, 2.0 * x))(
        st.init_token(), x)
    tok = jax.jit(lambda t, y: st.write_at(t, jnp.int32(4), 2.0 * y))(tok, y)
    jax.block_until_ready(tok)
    # the store kept jax's own arrays, not copies of them
    assert all(isinstance(a, jax.Array)
               for kept, _ in st._host.values() for a in kept.arrays)
    _churn()
    # each read is of what one write stored, so it returns views too
    tok, seg = jax.jit(lambda t: st.prefetch(t, 0, 4))(tok)
    tok, row = jax.jit(lambda t: st.prefetch(t, 4, 1))(tok)
    np.testing.assert_array_equal(np.asarray(seg), np.asarray(2.0 * x))
    np.testing.assert_array_equal(np.asarray(row[0]), np.asarray(2.0 * y))
    assert st.stats["host_copy_bytes"] == 0


def test_pnode_spill_bitwise_over_consecutive_steps():
    f = _vf()

    def gfn(offload):
        def L(u0_, th_):
            return jnp.sum(odeint(f, u0_, th_, dt=DT, n_steps=N_STEPS,
                                  adjoint="pnode", offload=offload) ** 2)
        return jax.jit(jax.grad(L, argnums=(0, 1)))

    spill, ref = gfn("spill"), gfn(None)
    for seed in (0, 1):  # the second step overwrites the first's slots
        u0, th = _problem(seed)
        g = spill(u0, th)
        _churn()
        _assert_bitwise(g, ref(u0, th))


@pytest.mark.parametrize("method", ["rk4", "dopri5"])
def test_pnode_spill_moves_one_state_per_step(method):
    """A spilled pnode slot is the step's state, S bytes: the reverse step
    rebuilds the stages from their own linearisations (dopri5's FSAL
    stage, b_7 = 0, is neither stored nor rebuilt), so a gradient writes
    and reads N_t * S bytes, not N_t * (N_s + 1) * S."""
    u0, _ = _problem()
    offload_mod.reset_spill_stats()
    g = _grads("pnode", method=method, offload="spill", offload_segment=5)
    st = offload_mod.spill_stats()
    assert st["write_slots"] == st["read_slots"] == N_STEPS
    assert st["write_bytes"] == st["read_bytes"] == N_STEPS * tree_bytes(u0)
    _assert_bitwise(g, _grads("pnode", method=method))


@pytest.mark.parametrize("policy", ["revolve", "revolve2"])
def test_revolve_spill_slots_hold_one_state(policy):
    """Revolve's slots and revolve2's boundary slots hold a state each."""
    u0, _ = _problem()
    offload_mod.reset_spill_stats()
    _grads(policy, offload="spill", ncheck=3)
    st = offload_mod.spill_stats()
    assert st["write_slots"] > 0 and st["read_slots"] > 0
    assert st["write_bytes"] == st["write_slots"] * tree_bytes(u0)
    assert st["read_bytes"] == st["read_slots"] * tree_bytes(u0)


@pytest.mark.parametrize("method", ["rk4", "dopri5"])
def test_dense_pnode_residuals_hold_no_stages(method):
    """The dense pnode custom_vjp keeps theta and N_t states, no stacked
    stages: its residuals are N_t * S bytes beside theta's."""
    f = _vf()
    u0, th = _problem()
    _, vjp_fn = jax.vjp(
        lambda u, t: odeint(f, u, t, dt=DT, n_steps=N_STEPS, method=method,
                            adjoint="pnode"), u0, th)
    assert tree_bytes(jax.tree_util.tree_leaves(vjp_fn)) == \
        N_STEPS * tree_bytes(u0) + tree_bytes(th)


def _lanes(keys):
    # one vmapped write and read of 3 slots per lane under lane keys
    st = offload_mod.make_store("spill")
    st.lane_keys = keys
    xs = jnp.arange(18.0).reshape(2, 3, 3)
    tok = jax.jit(jax.vmap(lambda t, x: st.write_batch(t, 0, x)))(
        jnp.zeros(2, jnp.float32), xs)
    tok, seg = jax.jit(jax.vmap(lambda t: st.prefetch(t, 0, 3)))(tok)
    want = np.asarray(xs) * np.array([k is not None for k in keys])[:, None,
                                                                     None]
    np.testing.assert_array_equal(np.asarray(seg), want)
    return st.stats["host_copy_bytes"]


def _corrupt_write():
    st = offload_mod.make_store(
        "spill", integrity=True,
        fault_plan=FaultPlan([FaultSpec("spill.write", 0, "corrupt")]))
    tok = jax.jit(lambda t, x: st.write_batch(t, 0, x))(
        st.init_token(), jnp.arange(12.0).reshape(4, 3))
    jax.block_until_ready(tok)
    copied = st.stats["host_copy_bytes"]
    _, ok, _ = jax.jit(lambda t: st.prefetch_checked(t, 0, 4))(tok)
    assert not bool(ok)  # the read-side checksum still sees the damage
    return copied


def _missing_row():
    st = offload_mod.make_store("spill")
    tok = jax.jit(lambda t, x: st.write_batch(t, 0, x))(
        st.init_token(), jnp.arange(1.0, 7.0).reshape(2, 3))
    _, seg = jax.jit(lambda t: st.prefetch(t, 0, 4))(tok)
    assert np.all(np.asarray(seg)[2:] == 0.0)  # never written: zeros
    return st.stats["host_copy_bytes"]


def _sweep(**kw):
    offload_mod.reset_spill_stats()
    _assert_bitwise(_grads("pnode", offload="spill", **kw), _grads("pnode"))
    st = offload_mod.spill_stats()
    assert st["write_bytes"] > 0
    return st["host_copy_bytes"]


@pytest.mark.parametrize("case,copies", [
    ("ram_sweep", False),
    ("lanes_no_padding", False),
    ("disk_sweep", True),
    ("padding_lane", True),
    ("corrupt_write", True),
    ("missing_row", True),
])
def test_host_copy_bytes_by_path(case, copies):
    """``host_copy_bytes`` reads 0 where the store keeps and returns jax's
    arrays as they are, and counts the bytes on each path that copies."""
    copied = {
        "ram_sweep": lambda: _sweep(),
        "disk_sweep": lambda: _sweep(snaps_in_ram=0),
        "lanes_no_padding": lambda: _lanes(("r0", "r1")),
        "padding_lane": lambda: _lanes(("r0", None)),
        "corrupt_write": _corrupt_write,
        "missing_row": _missing_row,
    }[case]()
    assert (copied > 0) == copies, copied


def test_ram_bytes_count_each_shared_chunk_once():
    st = offload_mod.make_store("spill")
    x = jnp.arange(12.0).reshape(4, 3)  # 4 slots, one callback, one chunk
    tok = jax.jit(lambda t, x: st.write_batch(t, 0, x))(st.init_token(), x)
    jax.block_until_ready(tok)
    owners = {id(kept) for kept, _ in st._host.values()}
    assert len(owners) == 1  # every slot views the one chunk
    assert st.stats["ram_bytes_peak"] == st._ram_bytes == x.nbytes
    for slot in range(3):  # the last slot still pins the whole chunk
        st._drop_slot(slot)
        assert st._ram_bytes == x.nbytes
    st._drop_slot(3)
    assert st._ram_bytes == 0


# ---------------------------------------------------------------------------
# input validation (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ncheck", [0, -3])
def test_nonpositive_ncheck_rejected(ncheck):
    with pytest.raises(ValueError, match="positive"):
        _grads("revolve", ncheck=ncheck)


@pytest.mark.parametrize("ncheck", [N_STEPS, N_STEPS + 5])
def test_oversized_ncheck_rejected(ncheck):
    with pytest.raises(ValueError, match="n_steps"):
        _grads("revolve", ncheck=ncheck)


@pytest.mark.parametrize("policy", ["revolve", "revolve2"])
def test_revolve_without_ncheck_suggests_auto(policy):
    with pytest.raises(ValueError, match="auto"):
        _grads(policy)


def test_mem_budget_without_auto_rejected():
    with pytest.raises(ValueError, match="auto"):
        _grads("pnode", mem_budget=10 ** 9)


def test_bad_offload_tier_rejected():
    with pytest.raises(ValueError, match="offload"):
        _grads("pnode", offload="vram")
    with pytest.raises(ValueError, match="offload"):
        _grads("naive", offload="spill")


# ---------------------------------------------------------------------------
# planner (satellite: deterministic monotonicity; tentpole: budget solve)
# ---------------------------------------------------------------------------

def _rank(plan):
    # offloaded plans trade f-evals for transfer bytes the NFE metric does
    # not see; they are strictly worse than any fitting in-device plan
    return (0 if plan.offload is None else 1, plan.extra_fevals)


def test_planner_monotone_in_budget_model_mode():
    """Larger budget => never more extra f-evals (and never a forced
    offload when an in-device policy previously fit)."""
    f = _vf()
    u0, th = _problem()
    budgets = [1_000, 2_000, 3_000, 5_000, 8_000, 12_000, 20_000, 50_000,
               10 ** 6, 10 ** 9]
    prev = None
    for budget in budgets:
        plan = plan_odeint(f, u0, th, dt=DT, n_steps=N_STEPS, method="rk4",
                           mem_budget=budget, verify="model")
        rank = _rank(plan)
        if prev is not None:
            assert rank <= prev, (budget, rank, prev)
        prev = rank


def test_planner_unconstrained_is_pnode():
    f = _vf()
    u0, th = _problem()
    plan = plan_odeint(f, u0, th, dt=DT, n_steps=N_STEPS, method="rk4")
    assert plan.policy == "pnode" and plan.offload is None


def test_planner_huge_budget_is_naive():
    f = _vf()
    u0, th = _problem()
    plan = plan_odeint(f, u0, th, dt=DT, n_steps=N_STEPS, method="rk4",
                       mem_budget=10 ** 12, verify="model")
    assert plan.policy == "naive" and plan.extra_fevals == 0


def test_planner_tiny_budget_offloads():
    f = _vf()
    u0, th = _problem()
    plan = plan_odeint(f, u0, th, dt=DT, n_steps=N_STEPS, method="rk4",
                       mem_budget=1, verify="model")
    assert plan.offload == "spill" and plan.policy == "pnode"


def test_candidates_sorted_by_recompute():
    costs = candidate_costs(method="dopri5", n_steps=16, state_bytes=1024,
                            theta_bytes=4096, mem_budget=10 ** 6)
    extras = [c.extra_fevals for c in costs]
    assert extras == sorted(extras)
    assert costs[0].policy == "naive"


def test_plan_depth_remat_ladder():
    from repro.configs.base import ShapeCell
    from repro.configs.registry import get_arch
    cfg = get_arch("smollm-135m")
    cell = ShapeCell("t", 128, 8, "train")
    remats = [plan_depth_remat(cfg, cell, b)[0]
              for b in (10 ** 12, 10 ** 8, 10 ** 7, 10 ** 4)]
    # shrinking budget walks down the recompute ladder monotonically
    order = {"none": 0, "sqrt": 1, "full": 2, "revolve": 3}
    assert [order[r] for r in remats] == sorted(order[r] for r in remats)
    assert remats[0] == "none" and remats[-1] == "revolve"


# ---------------------------------------------------------------------------
# cost model vs lowered HLO (tentpole validation)
# ---------------------------------------------------------------------------

def test_model_ranks_policies_like_measurement():
    """The analytic model must order the Table-2 policies the same way the
    lowered HLO does — that ordering is what the planner relies on."""
    f = _vf()
    u0, th = _problem()
    from repro.mem import f_activation_bytes
    kw = dict(dt=DT, n_steps=N_STEPS, method="rk4")
    sb, tb = tree_bytes(u0), tree_bytes(th)
    fa = f_activation_bytes(f, u0, th)
    assert fa > sb  # the O(N_l) AD-residual term naive pays per stage
    order = [("naive", None), ("pnode", None), ("pnode2", None)]
    measured = [measure_reverse_cost(f, u0, th, policy=p, ncheck=k,
                                     **kw)["hlo_peak_bytes"]
                for p, k in order]
    predicted = [policy_cost(p, method="rk4", n_steps=N_STEPS,
                             state_bytes=sb, theta_bytes=tb, f_act_bytes=fa,
                             ncheck=k).peak_bytes
                 for p, k in order]
    assert measured == sorted(measured, reverse=True), measured
    assert predicted == sorted(predicted, reverse=True), predicted


def test_model_checkpoint_term_scales_with_n_steps():
    """Prediction and measurement must agree on the *slope* sign and rough
    magnitude of the pnode checkpoint growth (Fig. 3's claim)."""
    f = _vf()
    u0, th = _problem()
    sb, tb = tree_bytes(u0), tree_bytes(th)

    def both(n):
        m = measure_reverse_cost(f, u0, th, dt=DT, n_steps=n, method="rk4",
                                 policy="pnode")["hlo_peak_bytes"]
        p = policy_cost("pnode", method="rk4", n_steps=n, state_bytes=sb,
                        theta_bytes=tb).peak_bytes
        return m, p

    m8, p8 = both(8)
    m16, p16 = both(16)
    assert m16 > m8 and p16 > p8
    meas_slope = (m16 - m8) / 8
    pred_slope = (p16 - p8) / 8
    assert 0.2 < pred_slope / meas_slope < 5.0, (pred_slope, meas_slope)


def test_spill_shrinks_measured_residuals():
    """The offload claim, measured: spilling pnode checkpoints removes the
    O(N_t) term from the reverse pass's peak live bytes."""
    f = _vf()
    u0, th = _problem()
    kw = dict(dt=DT, method="rk4", policy="pnode")
    dev = [measure_reverse_cost(f, u0, th, n_steps=n, **kw)["hlo_peak_bytes"]
           for n in (8, 24)]
    spl = [measure_reverse_cost(f, u0, th, n_steps=n, offload="spill",
                                **kw)["hlo_peak_bytes"]
           for n in (8, 24)]
    dev_slope = (dev[1] - dev[0]) / 16
    spl_slope = (spl[1] - spl[0]) / 16
    assert spl[1] < dev[1]
    assert spl_slope < 0.25 * dev_slope, (dev, spl)


# ---------------------------------------------------------------------------
# acceptance: odeint(adjoint="auto", mem_budget=B)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["euler", "midpoint", "bosh3", "rk4",
                                    "dopri5"])
def test_auto_grads_match_naive_all_tableaus(method):
    """auto under a pnode-sized budget: grads == naive to the suite's
    existing tolerances, for every tableau."""
    f = _vf()
    u0, th = _problem()
    n = 8
    budget = int(measure_reverse_cost(
        f, u0, th, dt=DT, n_steps=n, method=method,
        policy="pnode")["hlo_peak_bytes"])

    def loss(policy):
        def L(u0_, th_):
            return jnp.sum(odeint(
                f, u0_, th_, dt=DT, n_steps=n, method=method,
                adjoint=policy,
                **({"mem_budget": budget} if policy == "auto" else {})) ** 2)
        return jax.grad(L, argnums=(0, 1))(u0, th)

    g = loss("auto")
    g_ref = loss("naive")
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-13)


@pytest.mark.parametrize("anchor,ncheck", [
    ("pnode", None), ("pnode2", None), ("revolve", 3)])
def test_auto_measured_peak_fits_budget(anchor, ncheck):
    """The acceptance criterion: when the budget equals a known policy's
    measured peak (so at least one policy fits), the planner's choice
    measures <= the budget on the lowered reverse pass."""
    f = _vf()
    u0, th = _problem()
    kw = dict(dt=DT, n_steps=N_STEPS, method="rk4")
    budget = int(measure_reverse_cost(f, u0, th, policy=anchor,
                                      ncheck=ncheck, **kw)["hlo_peak_bytes"])
    plan = plan_odeint(f, u0, th, mem_budget=budget, **kw)
    assert plan.fits
    chosen = measure_reverse_cost(f, u0, th, policy=plan.policy,
                                  ncheck=plan.ncheck, offload=plan.offload,
                                  **kw)["hlo_peak_bytes"]
    assert chosen <= budget, (plan.policy, plan.ncheck, chosen, budget)
    # and the choice is reverse-accurate
    g = _grads(plan.policy, ncheck=plan.ncheck, offload=plan.offload)
    g_ref = _grads("naive")
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-13)
