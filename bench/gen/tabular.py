"""Tabular inputs in the shape of FFJORD's preprocessed MINIBOONE (the
dataset cannot be downloaded): ``dim`` standardized, correlated features
drawn from a fixed mixture of Gaussians.  The mixture is fixed; the seed
draws the rows and the Hutchinson probes."""
from __future__ import annotations

import jax
import jax.numpy as jnp

N_COMPONENTS = 8


def _mixture(dim: int):
    km, ka = jax.random.split(jax.random.PRNGKey(0))
    means = 1.5 * jax.random.normal(km, (N_COMPONENTS, dim))
    mix = jax.random.normal(ka, (N_COMPONENTS, dim, dim)) / jnp.sqrt(dim)
    return means, mix


def tabular_batch(key, n: int, dim: int):
    """(n, dim) float32 rows, roughly zero-mean and unit-scale."""
    means, mix = _mixture(dim)
    kc, kz = jax.random.split(key)
    comp = jax.random.randint(kc, (n,), 0, N_COMPONENTS)
    z = jax.random.normal(kz, (n, dim))
    x = means[comp] + jnp.einsum("nd,nde->ne", z, mix[comp],
                                 precision="highest")
    return (x / 2.0).astype(jnp.float32)


def rademacher(key, shape):
    """The Hutchinson probe: +-1 entries, float32."""
    return jax.random.rademacher(key, shape, jnp.float32)
