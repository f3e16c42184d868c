"""Plain AdamW with decoupled weight decay (Loshchilov & Hutter), a global
gradient-norm clip and a linear-warmup cosine schedule, written out for the
reference.  It imports nothing of the program; the configuration's
``optimizer`` group gives its settings."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def schedule(opt: dict, step):
    """Learning rate before update ``step`` (0-based)."""
    warm = jnp.minimum(1.0, (step + 1) / max(1, opt["warmup_steps"]))
    span = max(1, opt["total_steps"] - opt["warmup_steps"])
    prog = jnp.clip((step - opt["warmup_steps"]) / span, 0.0, 1.0)
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * warm * frac


def clip(opt: dict, grads):
    """Gradients as the update sees them, after the global-norm clip."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (norm + 1e-9))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)


def init(params):
    zeros = lambda p: jnp.zeros_like(p)
    return {"step": jnp.zeros((), jnp.int32), "m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params)}


def update(opt: dict, grads, state: dict, params):
    """One step.  Arithmetic stays in the dtype of ``params``."""
    b1, b2 = opt["b1"], opt["b2"]
    g = clip(opt, grads)
    k = state["step"] + 1
    kf = k.astype(jnp.float32)
    lr = schedule(opt, state["step"])
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, state["m"], g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                     state["v"], g)

    def step(p, m_, v_):
        dt = p.dtype
        mh = m_ / (1 - b1 ** kf).astype(dt)
        vh = v_ / (1 - b2 ** kf).astype(dt)
        upd = mh / (jnp.sqrt(vh) + opt["eps"]) + opt["weight_decay"] * p
        return (p - lr.astype(dt) * upd.astype(dt)).astype(dt)

    params = jax.tree.map(step, params, m, v)
    return params, {"step": k, "m": m, "v": v}
