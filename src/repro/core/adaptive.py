"""Adaptive-step Dopri5 with discrete adjoint over *accepted* steps.

The paper (§4) notes that rejected steps have no influence on the cost or
memory of PNODE's reverse pass because the adjoint involves only accepted
steps.  We reproduce that here: the forward pass is a bounded
``lax.while_loop`` with a PI step-size controller; accepted steps write
(state, stages, h, t) into a preallocated ring buffer of ``max_steps``; the
reverse pass scans the buffer backward applying the per-stage discrete
adjoint with each step's own h.

The reverse sweep's cost scales with *accepted* steps, not ``max_steps``:
each slot's adjoint step sits inside a ``lax.cond`` on ``idx <
n_accepted``, so the invalid tail of the ring runs the identity branch,
with zero f evaluations (NFE-B = ``adjoint_stages('dopri5') *
n_accepted``).  Returns (u_final, info); info counts accepted and
rejected steps.

mem — the ring allocates max_steps*(N_s+1) state vectors up front
(Table-2 pnode storage at the worst-case step count).  ``offload="spill"``
(or ``"disk"``) writes accepted steps through a ``repro.mem.offload``
store instead: they land in a device ring of ``offload_segment`` slots
(rejected attempts where-mask to a no-op), flushed by ONE ``write_batch``
each time the accepted count crosses a segment boundary, plus a trailing
flush of the whole ring, whose stale slots past ``n_accepted`` are never
read.  The reverse is ``repro.core.sweeps.reverse_segments`` with
``n_accepted`` as its limit: segments past it cost no callback.  Device
memory is O(segment) states for any max_steps, with identical gradients.

``fused_stages=True`` lowers the RK stage updates (forward) and per-stage
adjoint recursion (reverse) through the Pallas ``fused_lincomb`` kernel
(interpret-mode on CPU) — same flag and caveats as ``odeint``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import tree_util as jtu

from repro.core import sweeps
from repro.core.integrators import (
    PyTree,
    VectorField,
    rk_adjoint_step,
    rk_combine,
    rk_stages,
    tree_add,
    tree_stack,
    tree_zeros_like,
)
from repro.core.tableaus import DOPRI5
from repro.obs.profile import scope


class AdaptiveInfo(NamedTuple):
    n_accepted: jax.Array
    n_rejected: jax.Array
    nfe_forward: jax.Array


def _error_norm(u, u_new, err, rtol, atol):
    def leaf(e, a, b):
        scale = atol + rtol * jnp.maximum(jnp.abs(a), jnp.abs(b))
        return jnp.sum((e / scale) ** 2), e.size

    parts = [leaf(e, a, b) for e, a, b in zip(
        jtu.tree_leaves(err), jtu.tree_leaves(u), jtu.tree_leaves(u_new))]
    total = sum(p[0] for p in parts)
    count = sum(p[1] for p in parts)
    return jnp.sqrt(total / count)


def odeint_adaptive(f: VectorField, u0: PyTree, theta: PyTree, *,
                    t0: float, t1: float, rtol: float = 1e-6,
                    atol: float = 1e-6, max_steps: int = 512,
                    h0: float | None = None, method: str = "dopri5",
                    offload: str | None = None,
                    offload_segment: int | None = None,
                    snaps_in_ram: int | None = None,
                    offload_dir: str | None = None,
                    fused_stages: bool = False,
                    obs=None, fault_plan=None):
    """Adaptive solve from t0 to t1; differentiable (discrete adjoint over
    accepted steps).  Returns (u_final, AdaptiveInfo).  ``offload``
    ("spill"/"disk"), ``offload_segment`` (default
    ceil(sqrt(max_steps))), ``snaps_in_ram`` and ``offload_dir`` replace
    the ring buffer with a host-side store as in ``odeint`` (module
    docstring); ``fused_stages`` selects the Pallas stage-fusion kernels.

    ``obs=`` attaches a ``repro.obs.FlightRecorder``: every *attempted*
    step emits a runtime ``adaptive.step`` event (t, h, error norm,
    accept, and the attempt counter — ``FlightRecorder.adaptive_steps()``
    reconstructs the exact accepted/rejected sequence from them), and the
    spill store's callbacks record per-segment ``spill.*`` traffic.  The
    taps are ``jax.debug.callback`` effects: no op feeds the computation,
    so gradients are bitwise-identical to ``obs=None`` (which traces no
    tap at all — zero overhead when off).

    ``fault_plan=`` (a ``repro.ft.FaultPlan``) injects NaN-poisoned f
    evaluations at chosen *attempt* indices (site ``"adaptive"``, kind
    ``"nan"``).  The controller is written to survive them without help: a
    NaN error norm rejects the attempt (``NaN <= 1.0`` is False), the
    non-finite PI factor falls back to the minimum shrink (0.2) instead of
    poisoning every later step size, and a total-attempt cap bounds the
    reject loop — so once the fault window passes, integration resumes at
    a smaller h (recovery here is convergent, not bitwise: the step-size
    trajectory legitimately differs from the fault-free run).  The
    ``adaptive.step`` obs stream records each poisoned attempt
    (``err_norm`` NaN, ``accept`` False)."""
    if method != "dopri5":
        raise ValueError("adaptive integration currently supports dopri5")
    requested = offload
    store, segment, offload = sweeps.resolve_offload(
        "odeint_adaptive", u0, theta, tier=offload, policy="pnode",
        n_steps=int(max_steps), segment=offload_segment,
        snaps_in_ram=snaps_in_ram, offload_dir=offload_dir,
        fault_plan=fault_plan, batched=False, obs=obs)
    if store is None:
        segment = 1
        if requested in ("spill", "disk"):  # degraded past the scanned tiers
            offload = None
    h_init = float(h0) if h0 is not None else (float(t1) - float(t0)) / 100.0
    if obs is not None:
        obs.record("adaptive.solve", method=method, t0=float(t0),
                   t1=float(t1), rtol=float(rtol), atol=float(atol),
                   max_steps=int(max_steps), h0=h_init,
                   offload=offload, segment=segment,
                   fused=bool(fused_stages))
    return _odeint_adaptive(f, float(t0), float(t1), float(rtol),
                            float(atol), int(max_steps), float(h_init),
                            store, segment, bool(fused_stages), obs,
                            fault_plan, u0, theta)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11))
def _odeint_adaptive(f, t0, t1, rtol, atol, max_steps, h0, store, segment,
                     fused, obs, fault, u0, theta):
    return _adaptive_fwd_solve(f, t0, t1, rtol, atol, max_steps, h0, store,
                               segment, fused, obs, fault, u0, theta)[0]


def _adaptive_fwd_solve(f, t0, t1, rtol, atol, max_steps, h0, store, segment,
                        fused, obs, fault, u0, theta):
    tab = DOPRI5
    s = tab.num_stages
    order = tab.order
    spill = store is not None
    seg = max(1, min(int(segment), int(max_steps)))

    def buf_like(x):
        return jnp.zeros((max_steps,) + x.shape, x.dtype)

    def ring_like(x):
        return jnp.zeros((seg,) + x.shape, x.dtype)

    stage0 = tree_stack([u0] * s)  # shape template for stages
    if spill:
        # the carry holds the store token plus a segment-sized staging
        # ring: accepted steps land at ring position n_acc % seg and ONE
        # write_batch callback flushes the full ring each time the
        # accepted count crosses a segment boundary — O(n_acc/seg)
        # callbacks instead of one write_at per attempted step
        fdt = jnp.result_type(float)
        ring0 = (jtu.tree_map(ring_like, u0),
                 jtu.tree_map(ring_like, jtu.tree_map(jnp.zeros_like,
                                                      stage0)),
                 jnp.zeros((seg,), fdt), jnp.zeros((seg,), fdt))
        bufs0 = (store.init_token(), ring0)
    else:
        state_buf = jtu.tree_map(buf_like, u0)
        stage_buf = jtu.tree_map(buf_like,
                                 jtu.tree_map(jnp.zeros_like, stage0))
        h_buf = jnp.zeros((max_steps,), jnp.result_type(float))
        t_buf = jnp.zeros((max_steps,), jnp.result_type(float))
        bufs0 = (state_buf, stage_buf, h_buf, t_buf)

    def cond(carry):
        u, t, h, n_acc, n_rej, bufs, err_prev = carry
        # the total-attempt cap bounds the reject loop: a persistently
        # rejecting step (e.g. poisoned f-evals) can no longer hang the
        # while_loop — it exits with t short of t1, which the caller sees
        # in the counters.  Never binds on a healthy solve (rejections
        # would have to outnumber accepts 7:1 at the accept cap).
        return jnp.logical_and(
            jnp.logical_and(t < t1 - 1e-14, n_acc < max_steps),
            n_acc + n_rej < 8 * max_steps)

    def body(carry):
        u, t, h, n_acc, n_rej, bufs, err_prev = carry
        h = jnp.minimum(h, t1 - t)
        f_step = f
        if fault is not None:
            bad = fault.traced_gate("adaptive", "nan", n_acc + n_rej)
            if bad is not False:
                def f_step(uu, th, tt):
                    out = f(uu, th, tt)
                    return jtu.tree_map(
                        lambda x: jnp.where(bad, jnp.full_like(x, jnp.nan),
                                            x), out)
        ks = rk_stages(f_step, tab, u, theta, t, h, fused=fused)
        u_new = rk_combine(tab, u, ks, h, fused=fused)
        # embedded error estimate
        err = None
        for i in range(s):
            ci = float(tab.b[i] - tab.b_err[i])
            if ci == 0.0:
                continue
            term = jtu.tree_map(lambda k: h * ci * k, ks[i])
            err = term if err is None else tree_add(err, term)
        enorm = _error_norm(u, u_new, err, rtol, atol)
        accept = enorm <= 1.0
        if obs is not None:
            # debug-effect tap only — nothing feeds the computation, so
            # the solve (and its gradients) is bitwise-unchanged; the
            # attempt counter makes the event stream order-reconstructible
            obs.emit("adaptive.step", t=t, h=h, err_norm=enorm,
                     accept=accept, attempt=n_acc + n_rej)

        # PI controller (Hairer-Norsett-Wanner II.4): alpha=0.7/p, beta=0.4/p
        alpha, beta = 0.7 / order, 0.4 / order
        factor = 0.9 * (enorm + 1e-10) ** (-alpha) * (err_prev + 1e-10) ** (beta)
        # a NaN/Inf error norm (poisoned f-evals) must not poison the step
        # size forever: fall back to the maximum shrink so the retry probes
        # a smaller h.  Bitwise-neutral when factor is finite.
        factor = jnp.where(jnp.isfinite(factor), factor,
                           jnp.asarray(0.2, factor.dtype))
        factor = jnp.clip(factor, 0.2, 5.0)
        h_next = h * jnp.where(accept, factor, jnp.minimum(factor, 1.0))

        idx = n_acc
        if spill:
            tok, ring = bufs
            pos = jnp.remainder(idx, seg)
            ring2 = jtu.tree_map(
                lambda b, x: b.at[pos].set(jnp.where(accept, x, b[pos])),
                ring, (u, tree_stack(ks), h, t))
            # flush the staging ring once the accepted index fills it:
            # one segment-batched callback per seg ACCEPTED steps;
            # rejected attempts never reach the host
            do_flush = jnp.logical_and(accept, pos == seg - 1)
            tok2 = jax.lax.cond(
                do_flush,
                lambda t_: store.write_batch(t_, idx + 1 - seg, ring2),
                lambda t_: t_, tok)
            bufs2 = (tok2, ring2)
        else:
            sb, kb, hb, tb = bufs
            sb2 = jtu.tree_map(lambda b, x: b.at[idx].set(
                jnp.where(accept, x, b[idx])), sb, u)
            kb2 = jtu.tree_map(lambda b, x: b.at[idx].set(
                jnp.where(accept, x, b[idx])), kb, tree_stack(ks))
            hb2 = hb.at[idx].set(jnp.where(accept, h, hb[idx]))
            tb2 = tb.at[idx].set(jnp.where(accept, t, tb[idx]))
            bufs2 = (sb2, kb2, hb2, tb2)

        u_out = jtu.tree_map(lambda a, b: jnp.where(accept, b, a), u, u_new)
        t_out = jnp.where(accept, t + h, t)
        return (u_out, t_out, h_next,
                n_acc + accept.astype(jnp.int32),
                n_rej + (1 - accept.astype(jnp.int32)),
                bufs2,
                jnp.where(accept, enorm, err_prev))

    carry0 = (u0, jnp.asarray(t0, jnp.result_type(float)),
              jnp.asarray(h0, jnp.result_type(float)),
              jnp.array(0, jnp.int32), jnp.array(0, jnp.int32),
              bufs0,
              jnp.asarray(1.0, jnp.result_type(float)))
    u_f, t_f, h_f, n_acc, n_rej, bufs, _ = jax.lax.while_loop(cond, body, carry0)
    nfe = (n_acc + n_rej) * s
    info = AdaptiveInfo(n_accepted=n_acc, n_rejected=n_rej, nfe_forward=nfe)
    if spill:
        # trailing flush: ship the partially-filled ring (positions >=
        # n_acc % seg are stale entries landing at slots >= n_acc, which
        # the reverse sweep cond-skips — they are never read)
        tok, ring = bufs
        rem_n = jnp.remainder(n_acc, seg)
        tok = jax.lax.cond(
            rem_n > 0,
            lambda t_: store.write_batch(t_, n_acc - rem_n, ring),
            lambda t_: t_, tok)
        bufs = tok  # the ring is dead past this point; residual = token
    return (u_f, info), (bufs, n_acc, theta)


@scope("adaptive/bwd")
def _odeint_adaptive_bwd(f, t0, t1, rtol, atol, max_steps, h0, store,
                         segment, fused, obs, fault, res, g):
    if obs is not None:
        obs.record("adaptive.adjoint", max_steps=max_steps,
                   segment=segment,
                   tier=store.tier if store is not None else "device")
    bufs, n_acc, theta = res
    g_u, _g_info = g  # ignore cotangents of the counters
    st = _Accepted(f, fused)
    if store is None:  # the ring; its tail past n_accepted is skipped
        return sweeps.adjoint_scan(st, theta, g_u, tree_zeros_like(theta),
                                   bufs, None, None, max_steps, sliced=True,
                                   limit=n_acc)
    lam, mu, _, _ = sweeps.reverse_segments(
        st, theta, store, max(1, min(segment, max_steps)), max_steps, g_u,
        tree_zeros_like(theta), None, bufs, limit=n_acc)
    return lam, mu


@dataclasses.dataclass(frozen=True, eq=False)
class _Accepted(sweeps.Stepper):
    """The adjoint of an accepted dopri5 step: its checkpoint carries its
    own step size and time."""

    f: Any
    fused: bool = False

    def adjoint(self, ckpt, u_next, theta, n, lam):
        u, stages, h, t = ckpt
        return rk_adjoint_step(self.f, DOPRI5, u, stages, theta, t, h, lam,
                               fused=self.fused)


_odeint_adaptive.defvjp(scope("adaptive/fwd")(_adaptive_fwd_solve),
                        _odeint_adaptive_bwd)
