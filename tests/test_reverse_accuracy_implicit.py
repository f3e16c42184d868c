"""Reverse accuracy of the implicit family (theta-methods, §3.3): for
theta in {0.5 (cn), 1.0 (beuler)} the discrete adjoint of every implicit
checkpoint policy must match AD through an unrolled dense-Jacobian Newton
solve of the same scheme (the differentiable oracle — backprop through the
production Newton/GMRES ``while_loop`` has no reverse rule), and under jit
the policies must agree bitwise.  The tier sweep is in
``test_reverse_accuracy_tiers.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.reverse_problem import (D, DT_IMP, IMPLICIT_MATRIX, N_IMP,
                                   _THETA_OF, _assert_bitwise,
                                   _implicit_grads, _problem, _vf)

jax.config.update("jax_enable_x64", True)


def _oracle_implicit_grads(method):
    """AD through an unrolled dense-Jacobian Newton solve of the identical
    theta-scheme: the reverse-accuracy reference the production
    matrix-free solver cannot provide itself."""
    theta = _THETA_OF[method]
    f = _vf()
    u0, th = _problem()

    def step(u, th_, t_n):
        t_next = t_n + DT_IMP
        g_const = u + DT_IMP * (1 - theta) * f(u, th_, t_n)
        v = u + DT_IMP * f(u, th_, t_n)
        for _ in range(25):
            r = v - DT_IMP * theta * f(v, th_, t_next) - g_const
            J = jnp.eye(D) - DT_IMP * theta * jax.jacfwd(
                lambda uu: f(uu, th_, t_next))(v)
            v = v - jnp.linalg.solve(J, r)
        return v

    def loss(u0_, th_):
        u = u0_
        for k in range(N_IMP):
            u = step(u, th_, k * DT_IMP)
        return jnp.sum(u ** 2)

    return jax.grad(loss, argnums=(0, 1))(u0, th)


@pytest.mark.parametrize("method", ["cn", "beuler"])
@pytest.mark.parametrize("policy,ncheck", [(p, k) for p, k, _ in
                                           IMPLICIT_MATRIX])
def test_implicit_policy_matches_ad_through_newton(method, policy, ncheck):
    """Each implicit checkpoint policy reproduces AD-through-dense-Newton
    to tight tolerance, for both theta points of the family."""
    g_ref = _oracle_implicit_grads(method)
    kw = {"ncheck": ncheck} if ncheck is not None else {}
    g = _implicit_grads(method, policy, **kw)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-7, atol=1e-9)


def test_implicit_policies_bitwise_identical_under_jit():
    """Under jit the checkpoint policies are not merely close — recompute
    is bitwise-deterministic, so dense pnode, revolve and revolve2 agree
    exactly (the implicit analogue of the explicit policy matrix)."""
    anchor = _implicit_grads("cn", "pnode", jit=True)
    for policy, ncheck, _ in IMPLICIT_MATRIX[1:]:
        g = _implicit_grads("cn", policy, jit=True, ncheck=ncheck)
        _assert_bitwise(g, anchor, f"cn/{policy} vs pnode under jit")
