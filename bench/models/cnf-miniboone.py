"""FFJORD's tabular CNF at its MINIBOONE settings, run through the
program's ``core.cnf.cnf_log_prob`` with a Hutchinson trace and the
program's AdamW: loss = -mean log p(x), the jitted value-and-grad of it
and the update in one step.

``ref_loss`` is the plain reference: the concatsquash MLP and the
augmented dynamics d[x, logdet]/dt = [f, tr(df/dx)] written out with a
fixed-step rk4 loop under ``jax.grad``, and the change of variables
log p(x) = log N(z; 0, I) + logdet (FFJORD, Grathwohl et al. 2019, eq. 4),
float32 at highest matmul precision (bfloat16 for the control)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.gen.tabular import rademacher, tabular_batch
from bench.lib.tableaus import TABLEAUS, explicit_rk
from bench.lib.train_cell import TrainModel

_ACT = {"softplus": jax.nn.softplus, "tanh": jnp.tanh}


def sizes(cfg):
    return [cfg["dim"]] + [cfg["hdim_factor"] * cfg["dim"]] \
        * cfg["nhidden"] + [cfg["dim"]]


def init_params(cfg, key):
    s = sizes(cfg)
    ks = jax.random.split(key, len(s) - 1)
    layers = []
    for i, k in enumerate(ks):
        w = (1.0 / s[i]) ** 0.5 * jax.random.normal(k, (s[i], s[i + 1]))
        if i == len(ks) - 1:
            w = w * 1e-2
        z = jnp.zeros((s[i + 1],))
        layers.append({"w": w, "b": z, "t_gate": z, "t_gate_b": z,
                       "t_bias": z})
    return {"layers": layers}


# --- the plain reference -------------------------------------------------

def _mlp(cfg, prec):
    act = _ACT[cfg["nonlinearity"]]

    def f(x, th, t):
        layers = th["layers"]
        for i, ly in enumerate(layers):
            y = jnp.dot(x, ly["w"], precision=prec) + ly["b"]
            y = y * jax.nn.sigmoid(ly["t_gate"] * t + ly["t_gate_b"]) \
                + ly["t_bias"] * t
            x = act(y) if i < len(layers) - 1 else y
        return x
    return f


def ref_loss(cfg, traffic, params, batch, dtype, half, logdet_sign=1.0):
    """-mean log p(x).  ``logdet_sign`` -1 gives the program's sign of the
    log-determinant term (a test's witness of the fault; see PERF.md)."""
    x, probe = batch
    if half:
        x, probe = x[: x.shape[0] // 2], probe[: probe.shape[0] // 2]
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    f = _mlp(cfg, prec)
    x, probe = x.astype(dtype), probe.astype(dtype)

    def aug(state, th, t):
        u, _ = state
        fu, vjp = jax.vjp(lambda v: f(v, th, t), u)
        (ep,) = vjp(probe)
        return fu, jnp.sum(ep * probe, axis=-1)     # tr(df/dx) estimate

    z, logdet = explicit_rk(aug, (x, jnp.zeros(x.shape[:1], dtype)), params,
                            method=traffic["method"], t0=0.0,
                            t1=traffic["t1"], n_steps=traffic["n_steps"])
    d = x.shape[-1]
    logp_z = -0.5 * jnp.sum(z * z, axis=-1) - 0.5 * d * math.log(2 * math.pi)
    return -jnp.mean(logp_z + logdet_sign * logdet)


# --- model FLOPs -----------------------------------------------------------

def flops_per_step(cfg, traffic) -> float:
    """Operations the forward and backward passes require, from shapes:
    the MLP's matmuls, multiply-add counted as 2.  One augmented
    evaluation is f and the Hutchinson vector-Jacobian product (the input
    gradient of every layer: as many operations again); the gradient of
    each counts 2x more (input and weight gradients).  Recomputation is
    not counted."""
    s = sizes(cfg)
    f = 2 * traffic["batch"] * sum(a * b for a, b in zip(s[:-1], s[1:]))
    evals = len(TABLEAUS[traffic["method"]][1]) * traffic["n_steps"]
    return float(3 * evals * 2 * f)


# --- the timed path --------------------------------------------------------

def build(cfg: dict, traffic: dict) -> TrainModel:
    from repro.core.cnf import cnf_log_prob
    from repro.models.ode_nets import cnf_vf
    from repro.optim.adamw import AdamW

    o = cfg["optimizer"]
    opt = AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
                warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
                min_lr_frac=o["min_lr_frac"])
    vf = functools.partial(cnf_vf, act=cfg["nonlinearity"])
    n = traffic["n_steps"]

    def loss_fn(theta, x, probe):
        lp = cnf_log_prob(vf, x, theta, dt=traffic["t1"] / n, n_steps=n,
                          method=traffic["method"],
                          adjoint=traffic["adjoint"], trace="hutchinson",
                          probe=probe)
        return -jnp.mean(lp)

    @jax.jit
    def step(theta, state, x, probe):
        loss, grads = jax.value_and_grad(loss_fn)(theta, x, probe)
        theta, state, _ = opt.update(grads, state, theta)
        return theta, state, loss

    def batch(key):
        kx, kp = jax.random.split(key)
        shape = (traffic["batch"], cfg["dim"])
        return tabular_batch(kx, shape[0], shape[1]), rademacher(kp, shape)

    return TrainModel(
        init_params=lambda key: init_params(cfg, key),
        opt_init=opt.init,
        batch=batch,
        step=step,
        grad_from_state=lambda s: jax.tree.map(lambda m: m / (1 - o["b1"]),
                                               s.m),
        ref_loss=lambda p, b, dt, half: ref_loss(cfg, traffic, p, b, dt,
                                                 half),
        opt=o,
        flops_per_step=flops_per_step(cfg, traffic),
    )
