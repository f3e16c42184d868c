"""ODE-Net (Chen et al. 2018, arXiv:1806.07366, section 3) as torchdiffeq's
``examples/odenet_mnist.py`` builds it: a convolutional down-sampler to
64 channels, one ODE block whose function is GroupNorm, ReLU, a 3x3 conv
with time as an extra input channel, GroupNorm, ReLU, the same conv,
GroupNorm; then GroupNorm, ReLU, global pooling and a linear head.

The network is user code, written here once in plain ``jax.numpy``.  The
system under test is what a user of this framework hands it to: the
program's ``ODEBlock`` (``odeint`` with the cell's adjoint policy and
checkpoint tier) and its ``AdamW``, under one jitted step.  The plain
reference integrates the same network with a fixed-step rk4 loop under
``jax.grad``, float32 at highest matmul precision (bfloat16 at default
precision for the control), and steps a plain AdamW."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.gen.images import image_batch
from bench.lib.tableaus import TABLEAUS, explicit_rk
from bench.lib.train_cell import TrainModel

DIMS = ("NHWC", "HWIO", "NHWC")


# --- the network (user code) ----------------------------------------------

def _conv_init(key, k, c_in, c_out):
    """PyTorch's default: weights and bias uniform in +-1/sqrt(fan_in)."""
    kw, kb = jax.random.split(key)
    bound = (k * k * c_in) ** -0.5
    return {"w": jax.random.uniform(kw, (k, k, c_in, c_out), minval=-bound,
                                    maxval=bound),
            "b": jax.random.uniform(kb, (c_out,), minval=-bound,
                                    maxval=bound)}


def _norm_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def init_params(cfg, key):
    c, c_in = cfg["channels"], cfg["image"][2]
    k = jax.random.split(key, 6)
    bound = c ** -0.5
    return {
        "down": {"conv1": _conv_init(k[0], 3, c_in, c), "norm1": _norm_init(c),
                 "conv2": _conv_init(k[1], 4, c, c), "norm2": _norm_init(c),
                 "conv3": _conv_init(k[2], 4, c, c)},
        "ode": {"norm1": _norm_init(c), "conv1": _conv_init(k[3], 3, c + 1, c),
                "norm2": _norm_init(c), "conv2": _conv_init(k[4], 3, c + 1, c),
                "norm3": _norm_init(c)},
        "head": {"norm": _norm_init(c),
                 "w": jax.random.uniform(k[5], (c, cfg["classes"]),
                                         minval=-bound, maxval=bound),
                 "b": jnp.zeros((cfg["classes"],))},
    }


def _conv(p, x, prec, stride=1, pad=((1, 1), (1, 1))):
    y = jax.lax.conv_general_dilated(x, p["w"], (stride, stride), pad,
                                     dimension_numbers=DIMS, precision=prec)
    return y + p["b"]


def _norm(p, x, groups):
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + 1e-5)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def _concat_conv(p, x, t, prec):
    tt = jnp.full(x.shape[:-1] + (1,), t, x.dtype)
    return _conv(p, jnp.concatenate([tt, x], -1), prec)


def odefunc(cfg, prec):
    """f(u, theta, t), the framework's vector-field signature."""
    g = cfg["norm_groups"]

    def f(u, th, t):
        x = jax.nn.relu(_norm(th["norm1"], u, g))
        x = jax.nn.relu(_norm(th["norm2"], _concat_conv(th["conv1"], x, t,
                                                        prec), g))
        return _norm(th["norm3"], _concat_conv(th["conv2"], x, t, prec), g)
    return f


def downsample(cfg, p, x, prec):
    g = cfg["norm_groups"]
    x = _conv(p["conv1"], x, prec, pad="VALID")
    x = jax.nn.relu(_norm(p["norm1"], x, g))
    x = _conv(p["conv2"], x, prec, stride=2)
    x = jax.nn.relu(_norm(p["norm2"], x, g))
    return _conv(p["conv3"], x, prec, stride=2)


def head(cfg, p, h, prec):
    x = jax.nn.relu(_norm(p["norm"], h, cfg["norm_groups"]))
    feat = jnp.mean(x, axis=(1, 2))
    return jnp.dot(feat, p["w"], precision=prec) + p["b"]


def xent(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# --- the plain reference -------------------------------------------------

def ref_loss(cfg, traffic, params, batch, dtype, half):
    x, y = batch
    if half:
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    h = downsample(cfg, params["down"], x.astype(dtype), prec)
    h = explicit_rk(odefunc(cfg, prec), h, params["ode"],
                    method=traffic["method"], t0=0.0, t1=traffic["t1"],
                    n_steps=traffic["n_steps"])
    return xent(head(cfg, params["head"], h, prec).astype(jnp.float32), y)


# --- model FLOPs -----------------------------------------------------------

def _side(n, k, stride, pad):
    return (n + 2 * pad - k) // stride + 1


def flops_per_step(cfg, traffic) -> float:
    """Operations the forward and backward passes require, from shapes:
    convolutions and the head's matmul, multiply-add counted as 2.  A
    layer counts 3x (forward, input gradient, weight gradient), the first
    conv 2x (its input is data).  Each of the forward sweep's
    f-evaluations holds two 3x3 convs, C+1 -> C channels.  Norms and
    elementwise work are not counted, nor is recomputation."""
    b, (h, w, c_in) = traffic["batch"], cfg["image"]
    c = cfg["channels"]
    h1, w1 = _side(h, 3, 1, 0), _side(w, 3, 1, 0)
    h2, w2 = _side(h1, 4, 2, 1), _side(w1, 4, 2, 1)
    h3, w3 = _side(h2, 4, 2, 1), _side(w2, 4, 2, 1)
    down = (2 * 2 * b * h1 * w1 * 9 * c_in * c
            + 3 * 2 * b * (h2 * w2 + h3 * w3) * 16 * c * c)
    f_eval = 2 * (2 * b * h3 * w3 * 9 * (c + 1) * c)
    evals = len(TABLEAUS[traffic["method"]][1]) * traffic["n_steps"]
    fc = 3 * 2 * b * c * cfg["classes"]
    return float(down + 3 * evals * f_eval + fc)


# --- the timed path --------------------------------------------------------

def build(cfg: dict, traffic: dict) -> TrainModel:
    from repro.core.depth_ode import ODEBlock
    from repro.optim.adamw import AdamW

    o = cfg["optimizer"]
    opt = AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
                warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
                min_lr_frac=o["min_lr_frac"])
    kw = {k: traffic[k] for k in ("ncheck", "offload") if traffic.get(k)}
    block = ODEBlock(odefunc(cfg, None), n_steps=traffic["n_steps"],
                     method=traffic["method"], adjoint=traffic["adjoint"],
                     t0=0.0, t1=traffic["t1"], **kw)

    def loss_fn(params, x, y):
        h = block(downsample(cfg, params["down"], x, None), params["ode"])
        return xent(head(cfg, params["head"], h, None), y)

    @jax.jit
    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        params, opt_state, _ = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    h, w, c_in = cfg["image"]
    return TrainModel(
        init_params=lambda key: init_params(cfg, key),
        opt_init=opt.init,
        batch=lambda key: image_batch(key, traffic["batch"], height=h,
                                      width=w, channels=c_in,
                                      n_classes=cfg["classes"]),
        step=step,
        grad_from_state=lambda s: jax.tree.map(lambda m: m / (1 - o["b1"]),
                                               s.m),
        ref_loss=lambda p, b, dt, half: ref_loss(cfg, traffic, p, b, dt,
                                                 half),
        opt=o,
        flops_per_step=flops_per_step(cfg, traffic),
    )
