"""The control and the planted faults that ``correct`` has to catch.

Each takes a model file's ``build`` and returns a ``build`` whose timed
step is broken underneath (or replaced by the control); the rest of a run
is unchanged.  Used by ``bench/tests/test_control.py``."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.lib import adamw_ref


def control(build):
    """The plain reference in the program's place, in bfloat16 throughout:
    weights, activations, optimizer state and update."""
    def wrapped(cfg, traffic):
        model = build(cfg, traffic)
        opt = model.opt
        bf = jnp.bfloat16

        @jax.jit
        def step(params, state, *batch):
            p = jax.tree.map(lambda x: x.astype(bf), params)
            loss, g = jax.value_and_grad(
                lambda q: model.ref_loss(q, batch, bf, False))(p)
            p, state = adamw_ref.update(opt, g, state, p)
            return p, state, loss

        return dataclasses.replace(
            model, step=step,
            opt_init=lambda p: adamw_ref.init(
                jax.tree.map(lambda x: x.astype(bf), p)),
            grad_from_state=lambda s: jax.tree.map(
                lambda m: m.astype(jnp.float32) / (1 - opt["b1"]), s["m"]))
    return wrapped


def _wrap_step(build, make):
    def wrapped(cfg, traffic):
        model = build(cfg, traffic)
        return dataclasses.replace(model, step=make(model.step))
    return wrapped


def unchanged_state(build):
    """A step that returns its parameters and optimizer state unchanged."""
    def make(step):
        def broken(params, state, *batch):
            return (params, state) + tuple(step(params, state, *batch)[2:])
        return broken
    return _wrap_step(build, make)


def half_batch(build):
    """Half of the batch left out, the mean taken over the rest."""
    def make(step):
        def broken(params, state, *batch):
            return step(params, state,
                        *[x[: x.shape[0] // 2] for x in batch])
        return broken
    return _wrap_step(build, make)


def doubled_update(build):
    """The answer altered where it is produced: the largest leaf of the
    parameters moves by twice its update."""
    def make(step):
        def broken(params, state, *batch):
            out = step(params, state, *batch)
            leaves, tree = jax.tree.flatten(params)
            new = jax.tree.leaves(out[0])
            i = max(range(len(leaves)), key=lambda j: leaves[j].size)
            new[i] = leaves[i] + 2 * (new[i] - leaves[i])
            return (jax.tree.unflatten(tree, new),) + tuple(out[1:])
        return broken
    return _wrap_step(build, make)


def doubled_gradient(build):
    """The gradient doubled before the program's AdamW takes it: its
    moments hold twice the gradient and four times its square.  Adam's
    step barely changes under a uniform scale, so the parameters are the
    program's own."""
    def wrapped(cfg, traffic):
        model = build(cfg, traffic)
        b1, b2, step = model.opt["b1"], model.opt["b2"], model.step

        def broken(params, state, *batch):
            out = step(params, state, *batch)
            new = out[1]
            m = jax.tree.map(lambda n, o: 2 * n - b1 * o, new.m, state.m)
            v = jax.tree.map(lambda n, o: 4 * n - 3 * b2 * o, new.v, state.v)
            return (out[0], new._replace(m=m, v=v)) + tuple(out[2:])
        return dataclasses.replace(model, step=broken)
    return wrapped


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "doubled_update": doubled_update,
          "doubled_gradient": doubled_gradient}
