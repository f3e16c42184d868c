"""Reverse accuracy of every adjoint policy (the paper's central claim) +
the O(h^2) continuous-adjoint discrepancy of Prop. 1 + NFE accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adjoint import (POLICIES, checkpoint_floats, nfe_backward,
                                nfe_forward, odeint)
from repro.core.tableaus import get_tableau

jax.config.update("jax_enable_x64", True)

D = 8


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


def _grads(policy, method="rk4", n_steps=16, dt=0.05, **kw):
    f = _vf()
    u0, th = _problem()

    def loss(u0, th):
        uf = odeint(f, u0, th, dt=dt, n_steps=n_steps, method=method,
                    adjoint=policy, **kw)
        return jnp.sum(uf ** 2)

    return jax.grad(loss, argnums=(0, 1))(u0, th)


REVERSE_ACCURATE = ["pnode", "pnode2", "aca", "anode"]


@pytest.mark.parametrize("method", ["euler", "midpoint", "bosh3", "rk4",
                                    "dopri5"])
@pytest.mark.parametrize("policy", REVERSE_ACCURATE)
def test_reverse_accuracy(policy, method):
    """Discrete-adjoint policies match AD-through-the-solver to ~machine eps."""
    g_ref = _grads("naive", method=method)
    g = _grads(policy, method=method)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-13)


@pytest.mark.parametrize("policy", ["revolve", "revolve2"])
@pytest.mark.parametrize("ncheck", [1, 2, 3, 7, 15])
def test_revolve_reverse_accuracy(ncheck, policy):
    g_ref = _grads("naive")
    g = _grads(policy, ncheck=ncheck)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-13)


def test_continuous_adjoint_not_reverse_accurate_but_h2():
    """Prop. 1: continuous-adjoint error is O(h^2) per step ~ O(h) overall
    at fixed horizon; halving h must shrink the gap ~4x per step (>=2x
    accumulated)."""
    f = _vf()
    u0, th = _problem()

    def gap(n_steps):
        dt = 0.8 / n_steps

        def loss(pol):
            def L(u0, th):
                uf = odeint(f, u0, th, dt=dt, n_steps=n_steps,
                            method="euler", adjoint=pol)
                return jnp.sum(uf ** 2)
            return jax.grad(L)(u0, th)

        return float(jnp.max(jnp.abs(gap_ := loss("continuous")
                                     - loss("naive"))))

    g1, g2, g4 = gap(10), gap(20), gap(40)
    assert g1 > 1e-8  # the discrepancy is real
    assert g1 / g2 > 1.7  # shrinks at least linearly with h
    assert g2 / g4 > 1.7


@pytest.mark.parametrize("method", ["euler", "rk4", "dopri5"])
def test_nfe_accounting(method):
    """Counted f evaluations in fwd/bwd match the Table-2 formulas."""
    n_steps = 7
    counter = {"n": 0}

    def f(u, th, t):
        counter["n"] += 1
        return jnp.tanh(th["W"] @ u)

    u0, th = _problem()

    s = get_tableau(method).num_stages
    # forward NFE (count traces: use python-level eval via no jit)
    counter["n"] = 0
    with jax.disable_jit():
        odeint(f, u0, th, dt=0.05, n_steps=n_steps, method=method,
               adjoint="naive")
    assert counter["n"] == nfe_forward(method, n_steps) == s * n_steps

    # pnode backward: one linearization (1 eval) per stage
    counter["n"] = 0
    with jax.disable_jit():
        def L(u0, th):
            return jnp.sum(odeint(f, u0, th, dt=0.05, n_steps=n_steps,
                                  method=method, adjoint="pnode") ** 2)
        jax.grad(L)(u0, th)
    total = counter["n"]
    assert total == nfe_forward(method, n_steps) \
        + nfe_backward(method, n_steps, "pnode")


def test_checkpoint_floats_ordering():
    """Memory model: pnode == pnode2 == aca > anode (a pnode checkpoint is
    the state: its reverse step rebuilds the stages, so it holds what
    pnode2 holds, where Table 2 has pnode > pnode2); revolve(ncheck) <
    pnode for small ncheck."""
    kw = dict(method="dopri5", n_steps=20, state_size=1000)
    pnode = checkpoint_floats(adjoint="pnode", **kw)
    pnode2 = checkpoint_floats(adjoint="pnode2", **kw)
    aca = checkpoint_floats(adjoint="aca", **kw)
    anode = checkpoint_floats(adjoint="anode", **kw)
    rev = checkpoint_floats(adjoint="revolve", ncheck=3, **kw)
    assert pnode == pnode2 == aca > anode
    assert rev < pnode


def test_all_policies_run_pytree_state():
    """Policies accept pytree states (dict of arrays), not just vectors."""
    def f(u, th, t):
        return {"a": jnp.tanh(th @ u["a"]), "b": -u["b"]}

    th = 0.2 * jax.random.normal(jax.random.PRNGKey(0), (4, 4))
    u0 = {"a": jnp.ones((4,)), "b": jnp.ones((3,))}
    for pol in POLICIES:
        kw = {"ncheck": 2} if pol.startswith("revolve") else {}
        uf = odeint(f, u0, th, dt=0.1, n_steps=5, method="midpoint",
                    adjoint=pol, **kw)
        assert jnp.all(jnp.isfinite(uf["a"])) and jnp.all(
            jnp.isfinite(uf["b"]))


def test_unknown_policy_raises():
    with pytest.raises(ValueError):
        odeint(_vf(), jnp.ones(3), {}, dt=0.1, n_steps=2, adjoint="bogus")


def test_quadrature_loss_term():
    """eq. 2's integral term: for f = -u, q = |u|^2 the quadrature equals
    (1 - e^{-2T})/2 * |u0|^2, and its gradient is policy-equivalent."""
    from repro.core.adjoint import odeint_with_quadrature

    def f(u, th, t):
        return -u * th

    def q(u, th, t):
        return jnp.sum(u ** 2)

    u0 = jnp.array([1.0, 2.0])
    th = jnp.float64(1.0)
    T, n = 1.0, 200

    def L(u0, th, pol, **kw):
        uf, Q = odeint_with_quadrature(f, q, u0, th, dt=T / n, n_steps=n,
                                       method="rk4", adjoint=pol, **kw)
        return Q + jnp.sum(uf ** 2)

    exact_Q = (1 - np.exp(-2 * T)) / 2 * 5.0
    Q = L(u0, th, "pnode") - float(np.exp(-2 * T) * 5.0)
    np.testing.assert_allclose(float(Q), exact_Q, rtol=1e-8)

    g_ref = jax.grad(lambda a, b: L(a, b, "naive"), argnums=(0, 1))(u0, th)
    for pol, kw in [("pnode", {}), ("revolve", {"ncheck": 3}),
                    ("revolve2", {"ncheck": 3})]:
        g = jax.grad(lambda a, b: L(a, b, pol, **kw), argnums=(0, 1))(u0, th)
        for x, y in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(x, y, rtol=1e-12)
