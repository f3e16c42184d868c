"""Butcher tableaus of the explicit methods the cells use, and a plain
fixed-step loop over them for the references.  Written out from the
textbooks; nothing of the program's ``core/tableaus.py`` is imported."""
from __future__ import annotations

import jax

# name -> (a (lower triangle, row i holds a[i][:i]), b, c)
TABLEAUS = {
    "rk4": ([[], [0.5], [0.0, 0.5], [0.0, 0.0, 1.0]],
            [1 / 6, 1 / 3, 1 / 3, 1 / 6],
            [0.0, 0.5, 0.5, 1.0]),
}


def explicit_rk(f, u0, theta, *, method: str, t0: float, t1: float,
                n_steps: int):
    """u(t1) of du/dt = f(u, theta, t) by ``n_steps`` equal steps.  ``u0``
    is a pytree; arithmetic stays in its dtype."""
    a, b, c = TABLEAUS[method]
    dt = (t1 - t0) / n_steps

    def axpy(u, ks, coefs):
        def leaf(x, *kx):
            acc = x
            for co, k in zip(coefs, kx):
                if co:
                    acc = acc + (dt * co) * k
            return acc.astype(x.dtype)
        return jax.tree.map(leaf, u, *ks)

    def step(u, n):
        t = t0 + n * dt
        ks = []
        for i in range(len(b)):
            ui = axpy(u, ks, a[i]) if i else u
            ks.append(f(ui, theta, t + c[i] * dt))
        return axpy(u, ks, b), None

    u, _ = jax.lax.scan(step, u0, jax.numpy.arange(n_steps))
    return u
