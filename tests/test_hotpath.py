"""PR-3 hot-path regression tests: segment-batched checkpoint I/O, the
masked adaptive reverse sweep, and the fused Pallas stage kernels.

Bitwise-grad tests run under jit: within one compiled program the fused
kernel's accumulation order matches the unfused tree_axpy chain exactly
(and XLA's FMA-contraction decisions are consistent), so gradients must be
*bitwise* identical — any drift means the kernel reordered the math.
Host-callback counts are asserted via the spill store's host-side
counters (``repro.mem.offload.spill_stats``), which count executions, not
traces.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adaptive import odeint_adaptive
from repro.core.adjoint import odeint
from repro.kernels.ops import fused_lincomb
from repro.kernels.ref import lincomb_ref
from repro.mem.offload import (SpillStore, default_segment,
                               reset_spill_stats, spill_stats)

jax.config.update("jax_enable_x64", True)

D = 5
N_STEPS = 12
DT = 0.05
TABLEAUS = ["euler", "midpoint", "bosh3", "rk4", "dopri5"]


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


def _jit_grads(policy, *, method="rk4", n_steps=N_STEPS, **kw):
    f = _vf()
    u0, th = _problem()

    def loss(u0_, th_):
        uf = odeint(f, u0_, th_, dt=DT, n_steps=n_steps, method=method,
                    adjoint=policy, **kw)
        return jnp.sum(uf ** 2)

    return jax.jit(jax.grad(loss, argnums=(0, 1)))(u0, th)


def _assert_bitwise(g, g_ref):
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# fused Pallas stage kernels: bitwise-grad regression vs the PR-2 paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", TABLEAUS)
def test_fused_stages_grads_bitwise_identical(method):
    """fused_stages=True only re-fuses the stage lincombs — same math,
    same order, bitwise-equal gradients, for every tableau."""
    _assert_bitwise(_jit_grads("pnode", method=method, fused_stages=True),
                    _jit_grads("pnode", method=method))


@pytest.mark.parametrize("policy,kw", [
    ("pnode2", {}),
    ("revolve", {"ncheck": 3}),
    ("revolve2", {"ncheck": 3}),
])
def test_fused_stages_grads_bitwise_other_policies(policy, kw):
    _assert_bitwise(_jit_grads(policy, fused_stages=True, **kw),
                    _jit_grads(policy, **kw))


def test_fused_stages_forward_bitwise_identical():
    f = _vf()
    u0, th = _problem()

    def run(fused):
        return jax.jit(lambda a, b: odeint(
            f, a, b, dt=DT, n_steps=N_STEPS, adjoint="pnode",
            fused_stages=fused))(u0, th)

    _assert_bitwise(run(True), run(False))


@pytest.mark.parametrize("policy", ["naive", "continuous", "anode", "aca"])
def test_fused_stages_rejected_for_lowlevel_policies(policy):
    """Policies that differentiate through the step graph cannot use the
    Pallas kernels (no AD rules) — loud error, not a crash mid-trace."""
    with pytest.raises(ValueError, match="fused_stages"):
        _jit_grads(policy, fused_stages=True)


def test_fused_with_spill_offload_composes():
    _assert_bitwise(
        _jit_grads("pnode", offload="spill", offload_segment=4,
                   fused_stages=True),
        _jit_grads("pnode"))


# ---------------------------------------------------------------------------
# fused_lincomb kernel vs oracle
# ---------------------------------------------------------------------------

# (3, 50000) exceeds one (1024, 128) block: the gridded, tail-padded view
@pytest.mark.parametrize("shape", [(7,), (4, 5), (2, 3, 4), (3, 50000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_lincomb_matches_oracle(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    base = jax.random.normal(ks[0], shape, dtype)
    terms = [jax.random.normal(k, shape, dtype) for k in ks[1:]]
    ws = [0.5, -0.25, 1 / 3, 2.0]

    def fused(b, *ts):
        return fused_lincomb(b, ts, ws, scale=0.1)

    def ref(b, *ts):
        return lincomb_ref(b, list(ts), ws, scale=0.1)

    np.testing.assert_array_equal(
        np.asarray(jax.jit(fused)(base, *terms)),
        np.asarray(jax.jit(ref)(base, *terms)))


def test_fused_lincomb_traced_scale_and_base_coeff():
    base = jax.random.normal(jax.random.PRNGKey(2), (6, 3))
    terms = [jax.random.normal(jax.random.PRNGKey(3 + i), (6, 3))
             for i in range(3)]
    ws = [0.3, 0.6, -1.2]

    def fused(b, h, *ts):
        return fused_lincomb(b, ts, ws, scale=h, base_coeff=0.25)

    def ref(b, h, *ts):
        return lincomb_ref(b, list(ts), ws, scale=h, base_coeff=0.25)

    h = jnp.asarray(0.05)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(fused)(base, h, *terms)),
        np.asarray(jax.jit(ref)(base, h, *terms)))


# ---------------------------------------------------------------------------
# segment-batched spill I/O: bitwise grads + one callback per segment
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", TABLEAUS)
def test_batched_spill_grads_bitwise_identical(method):
    """Batched write_batch/prefetch I/O relocates checkpoints in segments;
    the adjoint arithmetic (and so the grads, bitwise) is unchanged.
    segment=5 does not divide n_steps=12, covering the remainder path."""
    _assert_bitwise(
        _jit_grads("pnode", method=method, offload="spill",
                   offload_segment=5),
        _jit_grads("pnode", method=method))


def test_spill_one_callback_per_segment():
    """The tentpole claim, host-measured: ceil(12/4)=3 write callbacks in
    the forward sweep and 3 prefetch callbacks in the reverse sweep —
    not 12+12 as with the per-step API."""
    f = _vf()
    u0, th = _problem()

    def loss(u0_, th_):
        uf = odeint(f, u0_, th_, dt=DT, n_steps=N_STEPS, adjoint="pnode",
                    offload="spill", offload_segment=4)
        return jnp.sum(uf ** 2)

    gfn = jax.jit(jax.grad(loss, argnums=(0, 1)))
    jax.block_until_ready(gfn(u0, th))  # compile + first run
    reset_spill_stats()
    jax.block_until_ready(gfn(u0, th))
    st = spill_stats()
    n_segments = math.ceil(N_STEPS / 4)
    assert st["write_cb"] == n_segments, st
    assert st["read_cb"] == n_segments, st
    assert st["write_slots"] == N_STEPS, st
    assert st["read_slots"] == N_STEPS, st


def test_spill_default_segment_is_sqrt():
    assert default_segment(1) == 1
    assert default_segment(16) == 4
    assert default_segment(24) == 5
    assert default_segment(512) == 23
    for n in (1, 7, 100):
        s = default_segment(n)
        assert s * s >= n and (s - 1) ** 2 < n


def test_write_batch_prefetch_roundtrip():
    st = SpillStore()
    tree = {"a": jnp.arange(8.0).reshape(4, 2), "b": (jnp.ones((4, 3)),)}
    tok = st.init_token()
    tok = st.write_batch(tok, 10, tree)  # slots 10..13
    jax.block_until_ready(tok)
    assert set(st._host) == {10, 11, 12, 13}
    tok2, got = st.prefetch(tok, 10, 4)
    jax.block_until_ready(tok2)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # out-of-range slots read back as zeros (the cond-masked tail)
    _, padded = st.prefetch(tok2, 12, 3)
    np.testing.assert_array_equal(np.asarray(padded["a"][2]),
                                  np.zeros(2))


def test_offload_segment_validation():
    with pytest.raises(ValueError, match="offload_segment"):
        _jit_grads("pnode", offload_segment=4)  # no spill tier selected
    with pytest.raises(ValueError, match="offload_segment"):
        _jit_grads("pnode", offload="spill", offload_segment=0)


# ---------------------------------------------------------------------------
# masked adaptive reverse sweep
# ---------------------------------------------------------------------------

def _adaptive_grads(offload=None, **kw):
    f = _vf()
    u0, th = _problem()

    def loss(u0_, th_):
        uf, _ = odeint_adaptive(f, u0_, th_, t0=0.0, t1=0.6, rtol=1e-6,
                                atol=1e-6, max_steps=64, offload=offload,
                                **kw)
        return jnp.sum(uf ** 2)

    return jax.jit(jax.grad(loss, argnums=(0, 1)))(u0, th)


def test_adaptive_masked_sweep_grads_match_spill_and_fused():
    g_dev = _adaptive_grads()
    _assert_bitwise(_adaptive_grads(offload="spill", offload_segment=8),
                    g_dev)
    _assert_bitwise(_adaptive_grads(fused_stages=True), g_dev)


def test_adaptive_reverse_reads_only_accepted_prefix():
    """Segments past n_accepted are cond-skipped: the reverse sweep
    prefetches ceil(n_acc/seg) segments, not max_steps/seg — host-counted
    proof the invalid ring-buffer tail costs nothing."""
    f = _vf()
    u0, th = _problem()
    max_steps, seg = 64, 8

    uf, info = odeint_adaptive(f, u0, th, t0=0.0, t1=0.6, rtol=1e-6,
                               atol=1e-6, max_steps=max_steps)
    n_acc = int(info.n_accepted)
    n_att = n_acc + int(info.n_rejected)
    assert 0 < n_acc < max_steps // 2  # the tail actually exists

    def loss(u0_, th_):
        uf, _ = odeint_adaptive(f, u0_, th_, t0=0.0, t1=0.6, rtol=1e-6,
                                atol=1e-6, max_steps=max_steps,
                                offload="spill", offload_segment=seg)
        return jnp.sum(uf ** 2)

    gfn = jax.jit(jax.grad(loss, argnums=(0, 1)))
    jax.block_until_ready(gfn(u0, th))
    reset_spill_stats()
    jax.block_until_ready(gfn(u0, th))
    st = spill_stats()
    assert st["read_cb"] <= math.ceil(n_acc / seg) + 1, (st, n_acc)
    assert st["read_slots"] <= n_acc + 2 * seg, (st, n_acc)
    # the forward staging ring flushes once per FULL segment of accepted
    # steps plus one trailing partial flush — O(n/seg) callbacks, never
    # one per attempted step (the pre-PR-9 O(N) path)
    assert st["write_cb"] <= math.ceil(n_att / seg) + 1, (st, n_att)
    # flushes ship whole rings: accepted slots rounded up to the segment
    assert st["write_slots"] == math.ceil(n_acc / seg) * seg, (st, n_acc)


def test_adaptive_gradient_still_correct_vs_fd():
    f = _vf()
    u0, th = _problem()

    def loss(u0_):
        uf, _ = odeint_adaptive(f, u0_, th, t0=0.0, t1=0.8, rtol=1e-9,
                                atol=1e-9, max_steps=256)
        return jnp.sum(uf ** 2)

    g = jax.grad(loss)(u0)
    eps = 1e-6
    for i in range(2):
        e = jnp.zeros(D).at[i].set(eps)
        fd = (loss(u0 + e) - loss(u0 - e)) / (2 * eps)
        np.testing.assert_allclose(g[i], fd, rtol=5e-6)


# ---------------------------------------------------------------------------
# vmap-of-odeint-with-offload: clear error (satellite)
# ---------------------------------------------------------------------------

def test_vmap_offload_raises_clear_error():
    f = _vf()
    u0, th = _problem()
    us = jnp.stack([u0, u0 + 0.1])
    with pytest.raises(NotImplementedError, match="offload='device'"):
        jax.vmap(lambda u: odeint(f, u, th, dt=DT, n_steps=N_STEPS,
                                  adjoint="revolve", ncheck=3,
                                  offload="spill"))(us)
    with pytest.raises(NotImplementedError, match="offload='device'"):
        jax.vmap(lambda u: odeint_adaptive(
            f, u, th, t0=0.0, t1=0.5, offload="spill")[0])(us)


def test_vmap_of_grad_offload_raises_clear_error():
    """vmap(grad(...)) wraps the batch axis inside JVP tracers — the guard
    must unwrap them, or the slot-addressed host dict would alias
    per-example checkpoints and silently return wrong gradients."""
    f = _vf()
    u0, th = _problem()
    us = jnp.stack([u0, u0 + 0.1])

    def loss(u):
        return jnp.sum(odeint(f, u, th, dt=DT, n_steps=N_STEPS,
                              adjoint="revolve", ncheck=3,
                              offload="spill") ** 2)

    with pytest.raises(NotImplementedError, match="offload='device'"):
        jax.vmap(jax.grad(loss))(us)


def test_offload_segment_rejected_for_slot_addressed_policies():
    """revolve checkpoints are slot-addressed; the segment knob would be
    silently ignored — reject it loudly."""
    with pytest.raises(ValueError, match="slot-addressed"):
        _jit_grads("revolve", ncheck=3, offload="spill", offload_segment=4)


def test_vmap_device_offload_still_works():
    f = _vf()
    u0, th = _problem()
    us = jnp.stack([u0, u0 + 0.1])
    out = jax.vmap(lambda u: odeint(f, u, th, dt=DT, n_steps=N_STEPS,
                                    adjoint="pnode"))(us)
    assert out.shape == (2, D) and bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# planner: caller's loss_fn in measured-verify mode (satellite)
# ---------------------------------------------------------------------------

def test_planner_accepts_caller_loss_fn():
    from repro.mem import measure_reverse_cost, plan_odeint
    f = _vf()
    u0, th = _problem()
    kw = dict(dt=DT, n_steps=8, method="rk4")

    def caller_loss(uf):
        return jnp.sum(jnp.abs(uf)) + jnp.sum(uf ** 4)

    m_canon = measure_reverse_cost(f, u0, th, policy="pnode", **kw)
    m_caller = measure_reverse_cost(f, u0, th, policy="pnode",
                                    loss_fn=caller_loss, **kw)
    assert m_caller["hlo_peak_bytes"] > 0
    # distinct cache entries: the caller's loss compiles its own reverse
    m_caller2 = measure_reverse_cost(f, u0, th, policy="pnode",
                                     loss_fn=caller_loss, **kw)
    assert m_caller2 is m_caller or m_caller2 == m_caller

    budget = int(m_caller["hlo_peak_bytes"])
    plan = plan_odeint(f, u0, th, mem_budget=budget, verify="measure",
                       loss_fn=caller_loss, **kw)
    assert plan.fits
    assert plan.measured_bytes is not None
    assert plan.measured_bytes <= budget


def test_planner_records_spill_callback_count():
    from repro.mem import policy_cost, spill_callback_counts
    c = policy_cost("pnode", method="rk4", n_steps=16, state_bytes=100,
                    offload="spill", segment=4)
    assert c.host_callbacks == 2 * 4  # 2 * ceil(16/4)
    assert spill_callback_counts("pnode", 16, segment=4)["total"] == 8
    r = spill_callback_counts("revolve", 16, ncheck=4)
    assert r["forward"] == 5 and r["total"] > r["forward"]
