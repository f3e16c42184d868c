"""Paper Fig. 3: memory growth vs number of time steps N_t per policy.

Memory = XLA's compiled live-buffer accounting (temp + args) of the jitted
loss-and-grad — the compiler's own statement of what must be resident.
The paper's claims to reproduce:
  * NODE-naive grows ~N_t * N_s * N_l (steepest),
  * ACA / PNODE2 grow ~N_t (solutions only),
  * PNODE grows ~N_t * (N_s+1) but with NO NN graph inside (shallow);
    this implementation's PNODE stores states only, so it grows ~N_t,
  * NODE-cont is flat,
  * slope(PNODE)/slope(naive) ~ (N_s+1)/(N_s*N_l-ish)  — big savings
    (~1/(N_s*N_l-ish) here)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import compiled_bytes, fmt_row
from repro.core.adjoint import odeint

D, HID, BATCH = 128, 256, 16


def _problem():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    u0 = jax.random.normal(ks[0], (BATCH, D))
    th = {"w1": 0.05 * jax.random.normal(ks[1], (D, HID)),
          "w2": 0.05 * jax.random.normal(ks[2], (HID, HID)),
          "w3": 0.05 * jax.random.normal(ks[3], (HID, D))}

    def f(u, theta, t):
        h = jnp.tanh(u @ theta["w1"])
        h = jnp.tanh(h @ theta["w2"])
        return h @ theta["w3"]

    return f, u0, th


POLICIES = [("naive", {}), ("continuous", {}), ("aca", {}), ("pnode", {}),
            ("pnode2", {}), ("revolve", {"ncheck": 4}),
            ("revolve2", {"ncheck": 4})]


def main(method: str = "dopri5") -> None:
    from repro.mem.model import f_activation_bytes, policy_cost, tree_bytes

    f, u0, th = _problem()
    nts = (2, 5, 8, 11)
    state_b = tree_bytes(u0)
    theta_b = tree_bytes(th)
    fa = f_activation_bytes(f, u0, th)
    print(f"== fig3_memory ({method}): compiled temp bytes (MiB) vs N_t, "
          "measured | model-predicted ==")
    print(fmt_row("policy", *[f"N_t={n}" for n in nts], "slope MiB/step",
                  widths=[12] + [14] * len(nts) + [15]))
    rows = {}
    for pol, kw in POLICIES:
        mibs, preds = [], []
        for n in nts:
            # the planner's validity rule: at most one slot per step
            nkw = {k: min(v, n - 1) for k, v in kw.items()}

            def L(u0, th):
                uf = odeint(f, u0, th, dt=0.5 / n, n_steps=n, method=method,
                            adjoint=pol, **nkw)
                return jnp.sum(uf ** 2)

            mem = compiled_bytes(
                lambda u0, th: jax.grad(L, argnums=(0, 1))(u0, th), u0, th)
            mibs.append(mem["temp"] / 2 ** 20)
            preds.append(policy_cost(
                pol, method=method, n_steps=n, state_bytes=state_b,
                theta_bytes=theta_b, f_act_bytes=fa,
                ncheck=nkw.get("ncheck")).peak_bytes / 2 ** 20)
        slope = (mibs[-1] - mibs[0]) / (nts[-1] - nts[0])
        rows[pol] = slope
        print(fmt_row(pol, *[f"{m:.2f}|{p:.2f}" for m, p in zip(mibs, preds)],
                      f"{slope:.3f}",
                      widths=[12] + [14] * len(nts) + [15]))
    if rows.get("naive", 0) > 0:
        print(f"PNODE slope / naive slope = "
              f"{rows['pnode'] / rows['naive']:.3f} "
              f"(paper: ~71% memory saved at dopri5 N_t=11)")


if __name__ == "__main__":
    main()
