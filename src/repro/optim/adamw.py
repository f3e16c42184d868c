"""AdamW with fp32 moments over (possibly bf16) params, global-norm clip,
warmup-cosine schedule, gradient accumulation, and optional gradient
compression for the cross-pod reduction (optax is not available offline).

State layout.  The moments of every param leaf that ``dist.sharding``
replicates under every mesh (``replicated_leaf``: no role rule claims it
and it has at most ``_REPLICATE_MAX`` elements) are packed, in leaf
order, into one flat fp32 buffer for m and one for v.  Every other leaf
keeps an m and a v buffer of its own, so the spec of a sharded param
still applies to its moments.  With a scalar step count the state is
3 + 2 x (leaves with own buffers) device buffers rather than
1 + 2 x (leaves): every buffer is an output that a jitted step allocates
at dispatch.  Which leaves pack depends only on their paths and shapes.
``state.m`` / ``state.v`` unpack to param-shaped trees.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import tree_util as jtu

from repro.dist.sharding import replicated_leaf


@dataclasses.dataclass(frozen=True)
class _Layout:
    """Where each param leaf's moments live: ``slots[i]`` is the
    ``(offset, shape)`` of leaf i in the packed buffers, or None for a
    leaf with buffers of its own; ``own_names`` are those leaves' paths.
    Static (pytree aux data)."""
    treedef: Any
    slots: tuple
    own_names: tuple

    @classmethod
    def of(cls, params) -> "_Layout":
        leaves, treedef = jtu.tree_flatten_with_path(params)
        slots, names, off = [], [], 0
        for path, p in leaves:
            if replicated_leaf(path, p.shape):
                slots.append((off, tuple(p.shape)))
                off += math.prod(p.shape)
            else:
                slots.append(None)
                names.append(jtu.keystr(path, simple=True, separator="/"))
        return cls(treedef, tuple(slots), tuple(names))

    @property
    def n_packed(self) -> int:
        return len(self.slots) - len(self.own_names)

    def own(self, tree) -> list:
        """The leaves of a param-shaped tree that keep their own buffers."""
        return [x for x, s in zip(self.treedef.flatten_up_to(tree),
                                  self.slots) if s is None]

    def split(self, tree):
        """Param-shaped tree -> (fp32 vector of the packed leaves, the
        other leaves as they are)."""
        leaves = self.treedef.flatten_up_to(tree)
        small = [x.astype(jnp.float32).ravel()
                 for x, s in zip(leaves, self.slots) if s is not None]
        flat = (jnp.concatenate(small) if small
                else jnp.zeros((0,), jnp.float32))
        return flat, self.own(tree)

    def merge(self, flat, own):
        """Inverse of ``split``: a param-shaped tree of fp32 packed leaves
        and the other leaves."""
        it = iter(own)
        leaves = [next(it) if s is None
                  else flat[s[0]:s[0] + math.prod(s[1])].reshape(s[1])
                  for s in self.slots]
        return self.treedef.unflatten(leaves)


@jtu.register_pytree_with_keys_class
class AdamWState:
    """AdamW's step count and moments.  Pytree children: ``(step,
    packed_m, packed_v, *own_m, *own_v)``, keyed ``step``, ``packed_m``,
    ``packed_v``, ``m/<param path>``, ``v/<param path>``; the layout is
    aux data."""

    def __init__(self, step, packed_m, packed_v, own_m, own_v,
                 layout: _Layout):
        self.step = step
        self.packed_m = packed_m
        self.packed_v = packed_v
        self.own_m = tuple(own_m)
        self.own_v = tuple(own_v)
        self.layout = layout

    @classmethod
    def pack(cls, step, m, v, layout: _Layout) -> "AdamWState":
        """The state holding param-shaped moment trees ``m`` and ``v``."""
        pm, om = layout.split(m)
        pv, ov = layout.split(v)
        return cls(step, pm, pv, om, ov, layout)

    @property
    def m(self):
        return self.layout.merge(self.packed_m, self.own_m)

    @property
    def v(self):
        return self.layout.merge(self.packed_v, self.own_v)

    def _replace(self, step=None, m=None, v=None) -> "AdamWState":
        return AdamWState.pack(self.step if step is None else step,
                               self.m if m is None else m,
                               self.v if v is None else v, self.layout)

    def tree_flatten(self):
        return ((self.step, self.packed_m, self.packed_v)
                + self.own_m + self.own_v), self.layout

    def tree_flatten_with_keys(self):
        children, layout = self.tree_flatten()
        keys = ([jtu.GetAttrKey(k) for k in ("step", "packed_m", "packed_v")]
                + [jtu.DictKey(f"{mv}/{name}") for mv in "mv"
                   for name in layout.own_names])
        return list(zip(keys, children)), layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        n = len(layout.own_names)
        step, pm, pv, *rest = children
        return cls(step, pm, pv, rest[:n], rest[n:], layout)


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1
    # cast gradients to this dtype before the (cross-pod) reduction/update —
    # halves all-reduce bytes when bf16 (distributed-optimization trick)
    grad_dtype: str | None = None

    def init(self, params) -> AdamWState:
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return AdamWState.pack(jnp.zeros((), jnp.int32),
                               jtu.tree_map(zeros, params),
                               jtu.tree_map(zeros, params),
                               _Layout.of(params))

    def schedule(self, step) -> jax.Array:
        warm = jnp.minimum(1.0, (step + 1) / max(1, self.warmup_steps))
        prog = jnp.clip((step - self.warmup_steps)
                        / max(1, self.total_steps - self.warmup_steps), 0.0, 1.0)
        cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
        frac = self.min_lr_frac + (1 - self.min_lr_frac) * cos
        return self.lr * warm * frac

    def update(self, grads, state: AdamWState, params):
        layout = state.layout
        if self.grad_dtype:
            gd = jnp.dtype(self.grad_dtype)
            grads = jtu.tree_map(lambda g: g.astype(gd), grads)
        # packed leaves' gradients as one fp32 vector; the others apart
        g_flat, g_own = layout.split(grads)
        g_own = [g.astype(jnp.float32) for g in g_own]
        # global-norm clip
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in [g_flat, *g_own]))
        scale = jnp.minimum(1.0, self.clip_norm / (gnorm + 1e-9))
        g_flat = g_flat * scale
        g_own = [g * scale for g in g_own]

        step = state.step + 1
        lr = self.schedule(state.step)
        b1c = 1 - self.b1 ** step.astype(jnp.float32)
        b2c = 1 - self.b2 ** step.astype(jnp.float32)

        mom = lambda m_, g: self.b1 * m_ + (1 - self.b1) * g
        sec = lambda v_, g: self.b2 * v_ + (1 - self.b2) * g * g
        m_flat = mom(state.packed_m, g_flat)
        v_flat = sec(state.packed_v, g_flat)
        m_own = [mom(m_, g) for m_, g in zip(state.own_m, g_own)]
        v_own = [sec(v_, g) for v_, g in zip(state.own_v, g_own)]

        def upd(p, m_, v_):
            mh = m_ / b1c
            vh = v_ / b2c
            u = mh / (jnp.sqrt(vh) + self.eps) + self.weight_decay \
                * p.astype(jnp.float32)
            return p.astype(jnp.float32) - lr * u

        p_flat, p_own = layout.split(params)
        new = layout.merge(upd(p_flat, m_flat, v_flat),
                           [upd(*a) for a in zip(p_own, m_own, v_own)])
        new_params = jtu.tree_map(lambda n, p: n.astype(p.dtype), new, params)
        new_state = AdamWState(step, m_flat, v_flat, m_own, v_own, layout)
        return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
