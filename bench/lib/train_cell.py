"""What every training cell shares: weights and a batch pool made on the
device from the seed, the first three steps taken through the timed step
in set-up, and the plain reference's first three steps to compare them
with.

A model file under ``bench/models/`` builds a ``TrainModel``: its
``init_params``/``batch`` are the benchmark's own, ``step`` and
``opt_init`` are the program's (the system under test), and ``ref_loss``
is the plain float32 reference of the loss, importing nothing of the
program.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import adamw_ref
from bench.lib.seeds import seed_key

FIRST_STEPS = 3
WEIGHTS, BATCHES = 0, 1     # seed streams


@dataclasses.dataclass
class TrainModel:
    init_params: Callable        # key -> params (benchmark's own init)
    opt_init: Callable           # params -> optimizer state (program's)
    batch: Callable              # key -> tuple of arrays (one step's feed)
    step: Callable               # program's jitted step: (params, state,
                                 # *batch) -> (params, state, loss, ...)
    grad_from_state: Callable    # state after one step -> gradient tree
    ref_loss: Callable           # (params, batch, dtype, half) -> loss
    opt: dict                    # the configuration's optimizer group
    flops_per_step: float        # model FLOPs of one optimizer step

    def __post_init__(self):
        self._jit = {}

    def jitted(self, name: str, fn: Callable):
        """One compiled program per name for the life of the model."""
        if name not in self._jit:
            self._jit[name] = jax.jit(fn)
        return self._jit[name]

    def init(self, key):
        def init(key):
            p = self.init_params(key)
            return p, self.opt_init(p)
        return self.jitted("init", init)(key)

    def params0(self, key):
        return self.jitted("params0", self.init_params)(key)

    def batches(self, seed: int, size: int) -> list:
        """``size`` distinct batches from the seed, each its own device
        arrays, made by one compiled generator."""
        gen = self.jitted("batch", self.batch)
        key = seed_key(seed, BATCHES)
        return [gen(jax.random.fold_in(key, i)) for i in range(size)]


def to_host(tree) -> list:
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def first_steps(model: TrainModel, params, state, pool: list):
    """Take the first ``FIRST_STEPS`` steps through the timed step and
    record what the comparison needs.  Returns (params, state, record)."""
    losses = []
    for i in range(FIRST_STEPS):
        params, state, loss, *_ = model.step(params, state, *pool[i])
        losses.append(float(loss))
        if i == 0:
            grad1 = to_host(model.grad_from_state(state))
    record = {"losses": losses, "grad1": grad1, "p3": to_host(params)}
    return params, state, record


def finish_record(model: TrainModel, seed: int, record: dict) -> dict:
    """Add the parameters' change over the first steps (the weights at
    step 0 are made again from the seed)."""
    p0 = to_host(model.params0(seed_key(seed, WEIGHTS)))
    delta = [a.astype(np.float64) - b for a, b in zip(record["p3"], p0)]
    return {"losses": record["losses"], "grad1": record["grad1"],
            "delta": delta}


def reference(model: TrainModel, seed: int, dtype=jnp.float32,
              half: bool = False) -> dict:
    """The plain reference's first steps from the same seed.  ``dtype``
    float32 is the reference (matmuls at highest precision); bfloat16 is
    the control.  ``half`` takes the loss over the first half of each
    batch (a planted fault)."""
    tag = f"{jnp.dtype(dtype).name}-{half}"
    opt = model.opt
    vg = model.jitted("ref-vg-" + tag, jax.value_and_grad(
        lambda p, b: model.ref_loss(p, b, dtype, half)))
    upd = model.jitted("ref-upd-" + tag, lambda g, s, p: adamw_ref.update(
        opt, g, s, p))
    clip = model.jitted("ref-clip-" + tag, lambda g: adamw_ref.clip(opt, g))
    p0 = model.params0(seed_key(seed, WEIGHTS))
    params = jax.tree.map(lambda x: x.astype(dtype), p0)
    batches = model.batches(seed, FIRST_STEPS)
    state = adamw_ref.init(params)
    losses = []
    for i in range(FIRST_STEPS):
        loss, g = vg(params, batches[i])
        losses.append(float(loss))
        if i == 0:
            grad1 = to_host(clip(g))
        params, state = upd(g, state, params)
    delta = [np.asarray(a, np.float64) - np.asarray(b, np.float64)
             for a, b in zip(to_host(params), to_host(p0))]
    return {"losses": losses, "grad1": grad1, "delta": delta}
