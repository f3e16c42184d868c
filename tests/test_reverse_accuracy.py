"""Reverse accuracy across ALL adjoint policies (the paper's central claim,
swept over `repro.core.adjoint.POLICIES`).

Every discrete policy (anode / aca / pnode / pnode2 / revolve / revolve2)
must reproduce the `naive` AD-through-the-solver gradients to machine
precision — they are exact reorderings of the same chain rule.  The
`continuous` adjoint is the one policy that is NOT reverse-accurate: its
per-step discrepancy is O(h^2) (Prop. 1), checked here by a dt-halving
convergence sweep at fixed horizon (global gap O(h), per-step gap O(h^2)).

The implicit family gets the same lockdown in
``test_reverse_accuracy_implicit.py`` and ``test_reverse_accuracy_tiers.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adjoint import POLICIES, odeint
from tests.reverse_problem import HORIZON, _problem, _vf

jax.config.update("jax_enable_x64", True)


def _grads(policy, *, method="rk4", n_steps=12, dt=HORIZON / 12, **kw):
    f = _vf()
    u0, th = _problem()

    def loss(u0_, th_):
        uf = odeint(f, u0_, th_, dt=dt, n_steps=n_steps, method=method,
                    adjoint=policy, **kw)
        return jnp.sum(uf ** 2)

    return jax.grad(loss, argnums=(0, 1))(u0, th)


def _gap(g, g_ref) -> float:
    return max(float(jnp.max(jnp.abs(a - b))) for a, b in
               zip(jax.tree_util.tree_leaves(g),
                   jax.tree_util.tree_leaves(g_ref)))


@pytest.mark.parametrize("policy", [p for p in POLICIES if p != "continuous"])
def test_policy_reverse_accurate(policy):
    """Each discrete policy == naive grads to near machine precision."""
    kw = {"ncheck": 3} if policy.startswith("revolve") else {}
    g_ref = _grads("naive")
    g = _grads(policy, **kw)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-13)


def test_continuous_adjoint_o_h2_per_step():
    """Prop. 1: the continuous adjoint's per-step gradient discrepancy is
    O(h^2): halving dt at fixed horizon must shrink the per-step gap ~4x
    (global gap ~2x, since the step count doubles)."""
    def gap_at(n_steps):
        dt = HORIZON / n_steps
        g_c = _grads("continuous", method="euler", n_steps=n_steps, dt=dt)
        g_n = _grads("naive", method="euler", n_steps=n_steps, dt=dt)
        return _gap(g_c, g_n)

    ns = (8, 16, 32, 64)
    gaps = [gap_at(n) for n in ns]
    per_step = [g / n for g, n in zip(gaps, ns)]
    assert gaps[0] > 1e-9, "discrepancy must be real, not roundoff"
    for a, b in zip(per_step, per_step[1:]):
        assert a / b > 2.8, (per_step, "per-step gap must shrink ~4x per "
                                       "dt halving (O(h^2), Prop. 1)")
    # contrast: a reverse-accurate policy stays at machine eps on the same ladder
    for n in (ns[0], ns[-1]):
        g_p = _grads("pnode", method="euler", n_steps=n, dt=HORIZON / n)
        g_n = _grads("naive", method="euler", n_steps=n, dt=HORIZON / n)
        assert _gap(g_p, g_n) < 1e-10
