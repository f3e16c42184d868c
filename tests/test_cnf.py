"""FFJORD CNF on the PNODE core: exactness of the log-det integral on an
analytically-known linear flow, trace estimators, and policy equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cnf import (cnf_log_prob, cnf_sample, exact_trace_vf,
                            hutchinson_trace_vf)
from repro.models.ode_nets import cnf_vf, cnf_vf_init

jax.config.update("jax_enable_x64", True)


def test_linear_flow_logdet_exact():
    """For f = A x, log det of the flow over [0,T] is T * tr(A)."""
    d = 4
    A = jnp.array(np.random.RandomState(0).randn(d, d) * 0.3)

    def f(x, th, t):
        return x @ th.T

    x = jnp.array(np.random.RandomState(1).randn(8, d))
    T, n = 1.0, 50
    lp = cnf_log_prob(f, x, A, dt=T / n, n_steps=n, method="rk4",
                      adjoint="naive")
    # z = expm(T A) x, so log p(x) = log N(z) + log|det dz/dx|
    # = log N(z) + T tr(A): the field accumulates -T tr(A) and
    # cnf_log_prob subtracts it
    z = x @ jax.scipy.linalg.expm(A).T
    base = -0.5 * jnp.sum(z ** 2, -1) - 0.5 * d * jnp.log(2 * jnp.pi)
    expected = base + T * jnp.trace(A)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(expected),
                               rtol=1e-6)


@pytest.mark.parametrize("trace", ["exact", "hutchinson"])
def test_linear_flow_density_integrates_to_one(trace):
    """On the 1-D flow f = a u, z = x e^a and log p(x) = log N(z) + a:
    the program's density integrates to 1 (a density with the sign of
    the log-determinant flipped integrates to e^(-2a))."""
    a = 0.7

    def f(u, th, t):
        return th * u

    xs = jnp.linspace(-12.0, 12.0, 20001)[:, None]
    probe = jnp.ones_like(xs) if trace == "hutchinson" else None
    lp = cnf_log_prob(f, xs, jnp.asarray(a), dt=1.0 / 32, n_steps=32,
                      method="rk4", adjoint="pnode", trace=trace,
                      probe=probe)
    mass = float(jnp.sum(jnp.exp(lp)) * (xs[1, 0] - xs[0, 0]))
    assert abs(mass - 1.0) < 1e-3, mass
    assert np.isclose(float(lp[10000]), -0.5 * np.log(2 * np.pi) + a,
                      atol=1e-6)


@pytest.mark.parametrize("adjoint", ["pnode", "pnode2", "aca"])
def test_cnf_gradients_policy_equivalent(adjoint):
    d = 3
    theta = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float64),
        cnf_vf_init(jax.random.PRNGKey(0), d, hidden=(16, 16)))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, d), jnp.float64)

    def nll(theta, pol):
        lp = cnf_log_prob(cnf_vf, x, theta, dt=0.1, n_steps=10,
                          method="bosh3", adjoint=pol)
        return -lp.mean()

    g_ref = jax.grad(lambda th: nll(th, "naive"))(theta)
    g = jax.grad(lambda th: nll(th, adjoint))(theta)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-12)


def test_hutchinson_trace_unbiased():
    """Average of Hutchinson estimates over many probes ~ exact trace."""
    d = 6
    theta = cnf_vf_init(jax.random.PRNGKey(0), d, hidden=(24,))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, d))
    exact = exact_trace_vf(cnf_vf, d)((x, jnp.zeros(4)), theta, 0.3)[1]

    ests = []
    for i in range(800):
        probe = jax.random.rademacher(
            jax.random.PRNGKey(i), (4, d), jnp.float64)
        est = hutchinson_trace_vf(cnf_vf, probe)((x, jnp.zeros(4)), theta,
                                                 0.3)[1]
        ests.append(np.asarray(est))
    mean_est = np.mean(ests, axis=0)
    np.testing.assert_allclose(mean_est, np.asarray(exact), atol=0.05)


def test_sample_inverts_log_prob_flow():
    """flow(sample(z)) should land back near z for a smooth field."""
    d = 2
    theta = cnf_vf_init(jax.random.PRNGKey(0), d, hidden=(16,))
    z = jax.random.normal(jax.random.PRNGKey(1), (6, d))
    x = cnf_sample(cnf_vf, z, theta, dt=0.02, n_steps=50, method="rk4")

    aug = exact_trace_vf(cnf_vf, d)
    from repro.core.adjoint import odeint
    z_back, _ = odeint(aug, (x, jnp.zeros(6)), theta, dt=0.02, n_steps=50,
                       method="rk4", adjoint="naive")
    np.testing.assert_allclose(np.asarray(z_back), np.asarray(z), atol=1e-5)
