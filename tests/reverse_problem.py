"""The problem and helpers the reverse-accuracy tests share: a small
time-dependent tanh field, its seeded weights, and the implicit family's
policy matrix and gradient driver."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.implicit import odeint_implicit

jax.config.update("jax_enable_x64", True)

D = 6
HORIZON = 0.6


def _vf():
    def f(u, th, t):
        return jnp.tanh(th["W"] @ u + th["b"]) - 0.2 * u \
            + 0.05 * jnp.cos(t) * u
    return f


def _problem(seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    u0 = jax.random.normal(ks[0], (D,))
    th = {"W": 0.4 * jax.random.normal(ks[1], (D, D)),
          "b": 0.1 * jax.random.normal(ks[2], (D,))}
    return u0, th


N_IMP = 6
DT_IMP = HORIZON / N_IMP
_THETA_OF = {"cn": 0.5, "beuler": 1.0}

#: (policy, ncheck, offload tiers that policy writes through)
IMPLICIT_MATRIX = [
    ("pnode", None, (None, "spill")),
    ("revolve", 2, (None, "host", "spill")),
    ("revolve2", 2, (None, "host", "spill")),
]


def _implicit_grads(method, policy="pnode", *, jit=False, **kw):
    f = _vf()
    u0, th = _problem()

    def loss(u0_, th_):
        uf = odeint_implicit(f, u0_, th_, dt=DT_IMP, n_steps=N_IMP,
                             method=method, adjoint=policy, newton_iters=20,
                             newton_tol=1e-13, gmres_tol=1e-13, **kw)
        return jnp.sum(uf ** 2)

    fn = jax.grad(loss, argnums=(0, 1))
    return (jax.jit(fn) if jit else fn)(u0, th)


def _assert_bitwise(g, g_ref, ctx=""):
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=ctx)
