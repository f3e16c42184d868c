"""Vector-field networks for the paper's experiments.

Three families, matching §5 of the paper:
  * ``mlp_vf``      — small MLP f(u, t): Robertson / stiff-dynamics learning
                      (5 hidden GELU layers, as in Kim et al. / the paper).
  * ``cnf_vf``      — concatsquash-style MLP used by FFJORD CNF density
                      estimation (hidden widths from the FFJORD configs).
  * ``conv_vf``     — 3x3 conv ODE block for image classification
                      (SqueezeNext-style channel mixing), NHWC layout.

All are pure ``init``/``apply`` pairs with the framework-wide vector-field
signature ``f(u, theta, t) -> du/dt``.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

_ACTS = {
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "silu": jax.nn.silu,
    "softplus": jax.nn.softplus,
    "relu": jax.nn.relu,
}


def _dense_init(key, d_in: int, d_out: int, scale: float | None = None):
    w_key, _ = jax.random.split(key)
    scale = (1.0 / d_in) ** 0.5 if scale is None else scale
    return {"w": scale * jax.random.normal(w_key, (d_in, d_out), jnp.float32),
            "b": jnp.zeros((d_out,), jnp.float32)}


# ---------------------------------------------------------------------------
# MLP vector field (Robertson / stiff dynamics)
# ---------------------------------------------------------------------------

def mlp_vf_init(key, dim: int, hidden: int = 50, n_hidden: int = 5):
    ks = jax.random.split(key, n_hidden + 1)
    sizes = [dim] + [hidden] * n_hidden + [dim]
    layers = [_dense_init(ks[i], sizes[i], sizes[i + 1])
              for i in range(len(sizes) - 1)]
    # near-zero last layer: f ~ 0 at init so the ODE starts near-identity
    layers[-1]["w"] = layers[-1]["w"] * 1e-2
    return {"layers": layers}


def mlp_vf(u, theta, t, act: str = "gelu"):
    """f(u, theta, t) for a plain MLP; u may be (D,) or (B, D)."""
    a = _ACTS[act]
    x = u
    layers = theta["layers"]
    for lyr in layers[:-1]:
        x = a(x @ lyr["w"] + lyr["b"])
    lyr = layers[-1]
    return x @ lyr["w"] + lyr["b"]


# ---------------------------------------------------------------------------
# concatsquash MLP (FFJORD CNF)
# ---------------------------------------------------------------------------

def cnf_vf_init(key, dim: int, hidden: Sequence[int] = (64, 64, 64)):
    """FFJORD concatsquash layers: y = (Wx+b) * sigmoid(a_t t + c) + g_t t."""
    sizes = [dim] + list(hidden) + [dim]
    ks = jax.random.split(key, len(sizes) - 1)
    layers = []
    for i in range(len(sizes) - 1):
        k1, k2 = jax.random.split(ks[i])
        lyr = _dense_init(k1, sizes[i], sizes[i + 1])
        lyr["t_gate"] = jnp.zeros((sizes[i + 1],), jnp.float32)
        lyr["t_gate_b"] = jnp.zeros((sizes[i + 1],), jnp.float32)
        lyr["t_bias"] = jnp.zeros((sizes[i + 1],), jnp.float32)
        layers.append(lyr)
    layers[-1]["w"] = layers[-1]["w"] * 1e-2
    return {"layers": layers}


def cnf_vf(u, theta, t, act: str = "tanh"):
    a = _ACTS[act]
    x = u
    t = jnp.asarray(t, jnp.float32)
    layers = theta["layers"]
    for i, lyr in enumerate(layers):
        y = x @ lyr["w"] + lyr["b"]
        gate = jax.nn.sigmoid(lyr["t_gate"] * t + lyr["t_gate_b"])
        y = y * gate + lyr["t_bias"] * t
        x = a(y) if i < len(layers) - 1 else y
    return x


# ---------------------------------------------------------------------------
# conv vector field + classifier head (image classification, §5.1)
# ---------------------------------------------------------------------------

def _conv_init(key, kh: int, kw: int, c_in: int, c_out: int):
    scale = (1.0 / (kh * kw * c_in)) ** 0.5
    return {"w": scale * jax.random.normal(key, (kh, kw, c_in, c_out),
                                           jnp.float32),
            "b": jnp.zeros((c_out,), jnp.float32)}


def _conv(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def conv_vf_init(key, channels: int):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"conv1": _conv_init(k1, 3, 3, channels + 1, channels),
         "conv2": _conv_init(k2, 3, 3, channels + 1, channels),
         "gn_scale": jnp.ones((channels,), jnp.float32),
         "gn_bias": jnp.zeros((channels,), jnp.float32)}
    p["conv2"]["w"] = p["conv2"]["w"] * 1e-2
    return p


def _group_norm(x, scale, bias, groups: int = 8):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + 1e-5)
    return xg.reshape(b, h, w, c) * scale + bias


def conv_vf(u, theta, t):
    """ODE-block conv vector field with time concatenated as a channel
    (the standard Chen et al. 'concat' conv).  u: (B, H, W, C)."""
    b, h, w, _ = u.shape
    tt = jnp.broadcast_to(jnp.asarray(t, u.dtype), (b, h, w, 1))
    x = _group_norm(u, theta["gn_scale"], theta["gn_bias"])
    x = jax.nn.relu(x)
    x = _conv(theta["conv1"], jnp.concatenate([x, tt], axis=-1))
    x = jax.nn.relu(x)
    x = _conv(theta["conv2"], jnp.concatenate([x, tt], axis=-1))
    return x


def classifier_init(key, channels: int = 32, n_classes: int = 10,
                    in_channels: int = 3):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "stem": _conv_init(k1, 3, 3, in_channels, channels),
        "ode": conv_vf_init(k2, channels),
        "head": _dense_init(k3, channels, n_classes),
    }


def classifier_apply(params, images, *, odeint_fn):
    """stem conv -> ODE block (via the caller-supplied odeint closure)
    -> global average pool -> linear head.  images: (B, H, W, C_in)."""
    x = jax.nn.relu(_conv(params["stem"], images))
    x = odeint_fn(conv_vf, x, params["ode"])
    x = x.mean(axis=(1, 2))
    return x @ params["head"]["w"] + params["head"]["b"]


def softmax_xent(logits, labels) -> jax.Array:
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (logz - gold).mean()


def synthetic_cifar(key, n, n_classes=10):
    """CIFAR-10-shaped stand-in (the dataset is not available offline):
    class-conditional Gaussian blobs in image space, (n, 32, 32, 3) images
    and int labels.  Learnable but non-trivial (accuracy well above chance
    requires the conv features)."""
    kl, kx = jax.random.split(key)
    labels = jax.random.randint(kl, (n,), 0, n_classes)
    base = jax.random.normal(
        jax.random.PRNGKey(0), (n_classes, 8, 8, 3))  # fixed class templates
    t = base[labels]
    t = jax.image.resize(t, (n, 32, 32, 3), "nearest")
    x = t + 0.6 * jax.random.normal(kx, (n, 32, 32, 3))
    return x, labels


def make_classifier_step(block, opt):
    """One optimizer step of the §5.1 classifier whose ODE block is
    ``block`` (a ``core.depth_ode.ODEBlock`` over ``conv_vf``).

    Returns the jitted ``step(params, opt_state, images, labels) ->
    (params, opt_state, loss, logits, grads)``; ``grads`` are the
    gradients the update was taken from."""
    def loss_fn(params, images, labels):
        logits = classifier_apply(
            params, images, odeint_fn=lambda vf, u, th: block(u, th))
        return softmax_xent(logits, labels), logits

    @jax.jit
    def step(params, opt_state, images, labels):
        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, images, labels)
        params, opt_state, _ = opt.update(grads, opt_state, params)
        return params, opt_state, loss, logits, grads

    return step
