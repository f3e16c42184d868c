"""Microbatched pipeline parallelism over a ``pod`` mesh axis (GPipe
schedule) via ``shard_map``.

The layer stack's leading axis is split across the ``pod`` axis so each
stage holds ``n_layers / n_stages`` consecutive layers.  The batch is cut
into ``n_micro`` microbatches that stream through the stages: at every tick
each stage applies its local layers to its current microbatch and passes the
result to the next stage with ``ppermute``; the last stage accumulates
finished microbatches.  Total ticks = ``n_micro + n_stages - 1`` (the usual
bubble).  Because every microbatch traverses the same per-layer ops in the
same order as a sequential sweep, the result is exact (not just close) —
tested against the unsharded reference in tests/test_dist.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import tree_util as jtu
from jax.sharding import PartitionSpec as P


def _apply_layers(layer_fn, params, h, n_layers):
    """Sequentially apply ``n_layers`` stacked layers (leading-axis params)."""
    def body(carry, p):
        return layer_fn(p, carry), None

    out, _ = jax.lax.scan(body, h, params, length=n_layers)
    return out


def pipeline_apply(layer_fn, params, x, *, mesh, n_micro: int,
                   axis: str = "pod"):
    """Apply a stacked layer pytree to ``x`` with pipeline parallelism.

    layer_fn(p, h) -> h  must preserve h's shape (residual blocks).
    ``params`` leaves carry the layer index on dim 0; ``n_layers`` must be a
    multiple of ``mesh.shape[axis]`` and ``x.shape[0]`` of ``n_micro``.
    """
    n_stages = int(mesh.shape[axis])
    n_layers = jtu.tree_leaves(params)[0].shape[0]
    if n_stages == 1:
        return _apply_layers(layer_fn, params, x, n_layers)
    if n_layers % n_stages != 0:
        raise ValueError(f"n_layers={n_layers} not divisible by "
                         f"{axis}={n_stages}")
    b = x.shape[0]
    if b % n_micro != 0:
        raise ValueError(f"batch {b} not divisible by n_micro={n_micro}")
    mb = b // n_micro
    per_stage = n_layers // n_stages
    fwd = [(j, j + 1) for j in range(n_stages - 1)]

    def stage(local_params, xg):
        i = jax.lax.axis_index(axis)
        micro = xg.reshape((n_micro, mb) + xg.shape[1:])
        n_ticks = n_micro + n_stages - 1

        def tick(t, carry):
            cur, outbuf = carry
            feed = jax.lax.dynamic_index_in_dim(
                micro, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            h = jnp.where(i == 0, feed, cur)
            h = _apply_layers(layer_fn, local_params, h, per_stage)
            # the last stage finishes microbatch t - (n_stages - 1) at tick t
            w = t - (n_stages - 1)
            wc = jnp.clip(w, 0, n_micro - 1)
            write = (i == n_stages - 1) & (w >= 0)
            slot = jax.lax.dynamic_index_in_dim(outbuf, wc, 0, keepdims=False)
            outbuf = jax.lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(write, h, slot), wc, 0)
            cur = jax.lax.ppermute(h, axis, fwd)
            return cur, outbuf

        cur0 = jnp.zeros_like(micro[0])
        out0 = jnp.zeros_like(micro)
        _, outbuf = jax.lax.fori_loop(0, n_ticks, tick, (cur0, out0))
        # only the last stage holds real outputs; psum replicates them
        outbuf = jax.lax.psum(
            jnp.where(i == n_stages - 1, outbuf, jnp.zeros_like(outbuf)),
            axis)
        return outbuf.reshape((b,) + xg.shape[1:])

    pspecs = jtu.tree_map(lambda _: P(axis), params)
    fn = jax.shard_map(stage, mesh=mesh, in_specs=(pspecs, P()),
                       out_specs=P(), check_vma=False)
    return fn(params, x)
