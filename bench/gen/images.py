"""Image-classification inputs (no data set can be downloaded): class-
conditional Gaussian blobs in image space, as ``synthetic_cifar`` in the
program's ``models/ode_nets.py`` makes them, at any image shape.  The
class templates are fixed; the seed draws labels and noise."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def image_batch(key, n: int, *, height: int, width: int, channels: int,
                n_classes: int):
    """(images (n, H, W, C) float32, labels (n,) int32)."""
    kl, kx = jax.random.split(key)
    labels = jax.random.randint(kl, (n,), 0, n_classes)
    base = jax.random.normal(jax.random.PRNGKey(0),
                             (n_classes, height // 4, width // 4, channels))
    t = jax.image.resize(base[labels], (n, height, width, channels),
                         "nearest")
    x = t + 0.6 * jax.random.normal(kx, (n, height, width, channels))
    return x.astype(jnp.float32), labels.astype(jnp.int32)
