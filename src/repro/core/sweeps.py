"""Checkpointed adjoint sweeps: one ``jax.custom_vjp`` per checkpoint
placement, shared by the explicit RK (``core.adjoint``) and implicit
theta-method (``core.implicit``) solves, plus the offload resolver their
entry points and ``odeint_adaptive`` call.

Placements (the reverse pass applies the stepper's per-step discrete
adjoint to stored checkpoints; it never differentiates a step graph).  A
checkpoint is one step's state, S bytes, for both steppers: the explicit
RK adjoint step rebuilds the step's stages from their own linearisations.

  dense       every step's state rides the custom_vjp residuals.
  revolve     the binomial (Prop. 2) schedule, unrolled at trace time,
              through a slot-addressed ``CheckpointStore`` (device /
              pinned-host / callback spill): the recompute optimum.
  revolve2    two-level and scanned: the forward stores only the boundary
              states of the optimal sweep placement; the reverse re-advances
              each segment once under ``lax.scan``, saving its states, and
              adjoints it, so compiled liveness is bounded on every backend.
  segmented   the scanned sweep through a spill/disk store: an inner scan
              stages ``segment`` steps' states and ONE ``write_batch``
              callback ships them; ``reverse_segments`` reads them back.
              The residual is the store's token, so device-live memory is
              O(segment) checkpoints for any N_t, and the gradients are
              bitwise the dense ones.

A ``Stepper`` (internal, not a user option) says what a step, its
adjoint and a checkpoint are: ``RKStepper`` (``core.adjoint``) and
``ThetaStepper`` (``core.implicit``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import tree_util as jtu

from repro.core import revolve as revolve_mod
from repro.core.integrators import PyTree, tree_add, tree_zeros_like
from repro.obs.profile import scope

#: offload tiers a solve accepts (None: the placement's default)
TIERS = (None, "device", "host", "spill", "disk")


def validate_ncheck(adjoint: str, ncheck, n_steps: int) -> int:
    if ncheck is None:
        raise ValueError(
            f"adjoint={adjoint!r} requires ncheck (the number of checkpoint "
            "slots); pass it explicitly, or use adjoint='auto' with "
            "mem_budget=<bytes> and the planner will pick the minimal-"
            "recompute ncheck for the budget (Prop. 2)")
    ncheck = int(ncheck)
    if ncheck <= 0:
        raise ValueError(
            f"ncheck must be a positive number of checkpoint slots, got "
            f"{ncheck} (the reverse sweep needs at least one free slot to "
            "re-advance a segment)")
    if ncheck >= n_steps:
        raise ValueError(
            f"ncheck={ncheck} must be < n_steps={n_steps}: with a slot for "
            "every step there is nothing to recompute — that point of the "
            "memory/compute curve is adjoint='pnode' (or let "
            "adjoint='auto' choose)")
    return ncheck


def segment_bounds(n_steps: int, ncheck: int):
    """``(a, b)`` step ranges between revolve2's boundary checkpoints."""
    positions = [0] + revolve_mod.sweep_checkpoint_positions(n_steps, ncheck)
    return list(zip(positions, positions[1:] + [n_steps]))


def _reject_vmap(u0: PyTree, theta: PyTree, where: str) -> None:
    """vmap over a slot-addressed store (or a sweep that cannot take a
    batch) fails deep inside the callbacks, or aliases host-dict slots and
    returns wrong gradients; detect it up front.  Leaves may be
    BatchTracers directly (vmap(odeint)) or wrap one deeper in the tracer
    stack (vmap(grad(...))), so nested tracers are unwrapped."""
    from repro.mem.offload import is_batch_tracer  # deferred: import cycle

    def has_batch_tracer(x, depth=0) -> bool:
        if is_batch_tracer(x):
            return True
        if isinstance(x, jax.core.Tracer) and depth < 8:
            return any(
                sub is not None and has_batch_tracer(sub, depth + 1)
                for sub in (getattr(x, "primal", None),
                            getattr(x, "tangent", None),
                            getattr(x, "val", None)))
        return False

    if any(has_batch_tracer(x) for x in jtu.tree_leaves((u0, theta))):
        raise NotImplementedError(
            f"vmap over {where} with a slot-addressed offload store is not "
            "supported: the store's host-side dict sees one logical slot "
            "index for the entire batch, so per-example checkpoints would "
            "alias.  Workarounds: adjoint='pnode' with offload='spill'/"
            "'disk' (the scanned segment-batched path composes with vmap), "
            "offload='device' (checkpoints ride the residual pytree, which "
            "vmap understands), or fold the mapped axis into u0's leading "
            "batch dimension instead of vmapping.")


def resolve_offload(where: str, u0: PyTree, theta: PyTree, *, tier,
                    policy: str, n_steps: int, segment=None,
                    snaps_in_ram=None, offload_dir=None, store=None,
                    fault_plan=None, integrity: bool = False,
                    batched: bool = True, obs=None):
    """Check a solve's offload options against its policy (``"pnode"``:
    the scanned sweeps; ``"revolve"``/``"revolve2"``: slot-addressed),
    walk a fault plan's tier outages down the degradation ladder, and
    build the store.  ``store`` is a caller-owned spill/disk store for the
    scanned sweep; ``batched=False`` rejects vmap there too.  Returns
    ``(store, segment, tier)``: ``store`` None where the checkpoints ride
    the residuals, ``segment`` set on the segmented path."""
    if tier not in TIERS:
        raise ValueError(f"unknown offload tier {tier!r}; one of {TIERS}")
    scanned = policy == "pnode"
    if tier in ("host", "spill", "disk") and policy not in (
            "pnode", "revolve", "revolve2"):
        raise ValueError(
            f"offload={tier!r} is not supported for adjoint={policy!r}: "
            "only policies with explicit per-step checkpoints (pnode, "
            "revolve, revolve2) write through the store")
    if segment is not None:
        if tier not in ("spill", "disk"):
            raise ValueError("offload_segment only applies to the callback "
                             f"spill/disk tiers; got offload={tier!r}")
        if not scanned:
            raise ValueError(
                "offload_segment only applies to the scanned pnode sweep; "
                f"adjoint={policy!r} checkpoints are slot-addressed at trace "
                "time, one callback per schedule action")
        segment = int(segment)
        if segment < 1:
            raise ValueError(f"offload_segment must be >= 1, got {segment}")
    if snaps_in_ram is not None:
        if tier != "spill":
            raise ValueError(
                "snaps_in_ram is the spill tier's RAM/disk split "
                "(offload='spill'; offload='disk' is already the "
                f"snaps_in_ram=0 corner); got offload={tier!r}")
        snaps_in_ram = int(snaps_in_ram)
        if snaps_in_ram < 0:
            raise ValueError(f"snaps_in_ram must be >= 0, got {snaps_in_ram}")
    if offload_dir is not None and tier not in ("spill", "disk"):
        raise ValueError("offload_dir pins the disk tier's segment files "
                         f"(offload='spill'/'disk'); got offload={tier!r}")
    if store is not None and not (scanned and tier in ("spill", "disk")):
        raise ValueError(
            "offload_store supplies a caller-owned store to the scanned "
            f"pnode spill/disk path only (adjoint='pnode', offload='spill'/"
            f"'disk'); got adjoint={policy!r}, offload={tier!r}")
    from repro.mem.offload import (batch_scale, default_segment,
                                   effective_tier, make_store)
    tier = effective_tier(tier, fault_plan, scanned=scanned, obs=obs)
    if tier in ("host", "spill", "disk") and (
            not scanned or tier == "host" or not batched):
        _reject_vmap(u0, theta, where)
    if scanned and tier == "host":
        raise ValueError(
            "offload='host' applies to trace-time checkpoint sites "
            "(revolve/revolve2); the scanned pnode sweep offloads through "
            "offload='spill' or 'disk'")
    if scanned and tier not in ("spill", "disk") or policy not in (
            "pnode", "revolve", "revolve2"):
        return None, None, tier
    if store is None:
        store = make_store(tier, fault_plan=fault_plan, integrity=integrity,
                           snaps_in_ram=snaps_in_ram, disk_dir=offload_dir)
    elif getattr(store, "tier", None) not in ("spill", "disk"):
        raise ValueError(
            "offload_store must be a spill/disk-tier store "
            f"(make_store('spill'|'disk')); got {type(store).__name__}")
    if obs is not None:
        store.bind_obs(obs)
    if scanned:
        # mapped axes are only visible HERE (as BatchTracers on the args);
        # the custom_vjp fwd is retraced at logical shapes, so the store's
        # payload-cap chunking needs the batch factor handed to it
        store.payload_scale = batch_scale((u0, theta))
        segment = min(segment if segment is not None
                      else default_segment(n_steps), n_steps)
    return store, segment, tier


@dataclasses.dataclass(frozen=True, eq=False)
class Stepper:
    """One step of a scheme, its discrete adjoint, and what a checkpoint
    holds.  A subclass sets ``scopes`` (placement -> profiler scope) and
    the flags below, and implements what its placements call.  ``n`` is a
    step index (a Python int at trace-time sites, traced in scans),
    ``aux`` the stepper's running auxiliary output (None for none), and
    ``recompute`` marks a reverse-pass re-advance:

      solve(u, theta, base, m, save=False, recompute=False)
          m steps from step ``base``: ``(u_end, aux, checkpoints)``, the
          checkpoints stacked, None unless ``save``
      step(u, aux, theta, base, i)  step ``base + i`` of a segment scan:
          ``(u_next, aux, checkpoint, info)``, ``info`` stacked for ``tap``
      advance(u, aux, theta, start, m, recompute=False) -> (u, aux)
      adjoint(ckpt, u_next, theta, n, lam) -> (lam_n, theta_bar)
      shifted(states, u_end) -> u_{n+1} beside each u_n (``needs_next``)
      recompute(u, theta, base, m) -> a segment's checkpoints, for a
          checked read that failed (``resilient``)
    """

    scopes = {}
    needs_next = False  # the adjoint step reads u_{n+1}
    resilient = False   # segmented reads are checked, with recompute
    obs = None

    def aux0(self):
        return None

    def out(self, u, aux):
        return u

    def cotangent(self, ct):
        return ct

    def tap(self, seg_infos, rem_infos, rem_base, aux):
        """Record a segmented forward's stacked step infos."""


def solve(st, policy, n_steps, u0, theta, *, ncheck=None, store=None,
          segment=None):
    """Run the sweep of ``policy`` ("pnode", "revolve" or "revolve2") with
    the store ``resolve_offload`` built for it."""
    if policy == "revolve":
        return revolve(st, n_steps, ncheck, store, u0, theta)
    if policy == "revolve2":
        return revolve2(st, n_steps, ncheck, store, u0, theta)
    if store is not None:
        return segmented(st, n_steps, store, segment, u0, theta)
    return dense(st, n_steps, u0, theta)


def _primal(st, n_steps, u0, theta):
    u, aux, _ = st.solve(u0, theta, 0, n_steps)
    return st.out(u, aux)


def adjoint_scan(st, theta, lam, mu, ckpts, u_nexts, base, m, *,
                 sliced=False, limit=None):
    """Adjoint steps ``base + m - 1`` down to ``base`` over m stacked
    checkpoints (``base`` None: the scan index is the step index).
    ``sliced`` reads each checkpoint inside the body instead of scanning
    over them; there, steps at or past ``limit`` are skipped."""
    def adjoint(carry, ckpt, u_next, n):
        lam, mu = carry
        lam, th_bar = st.adjoint(ckpt, u_next, theta,
                                 n if base is None else base + n, lam)
        return lam, tree_add(mu, th_bar)

    if sliced:
        def body(carry, i):
            def do(c):
                return adjoint(c, jtu.tree_map(lambda b: b[i], ckpts), None,
                               i)
            if limit is None:
                return do(carry), None
            n = i if base is None else base + i
            return jax.lax.cond(n < limit, do, lambda c: c, carry), None
        xs = jnp.arange(m)
    else:
        def body(carry, inp):
            return adjoint(carry, *inp), None
        xs = (ckpts, u_nexts, jnp.arange(m))
    (lam, mu), _ = jax.lax.scan(body, (lam, mu), xs, reverse=True)
    return lam, mu


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def dense(st, n_steps, u0, theta):
    return _primal(st, n_steps, u0, theta)


def _dense_fwd(st, n_steps, u0, theta):
    with scope(f"{st.scopes['dense']}/fwd"):
        u, aux, ckpts = st.solve(u0, theta, 0, n_steps, save=True)
        return st.out(u, aux), (ckpts, u if st.needs_next else None, theta)


def _dense_bwd(st, n_steps, res, ct):
    with scope(f"{st.scopes['dense']}/bwd"):
        ckpts, u_end, theta = res
        lam = st.cotangent(ct)
        u_nexts = st.shifted(ckpts, u_end) if st.needs_next else None
        return adjoint_scan(st, theta, lam, tree_zeros_like(theta), ckpts,
                            u_nexts, None, n_steps)


dense.defvjp(_dense_fwd, _dense_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def revolve(st, n_steps, ncheck, store, u0, theta):
    return _primal(st, n_steps, u0, theta)


def _revolve_fwd(st, n_steps, ncheck, store, u0, theta):
    with scope(f"{st.scopes['revolve']}/fwd"):
        u, aux = u0, st.aux0()
        for a, b in segment_bounds(n_steps, ncheck):
            store.put(a, u)
            u, aux = st.advance(u, aux, theta, a, b - a)
        return st.out(u, aux), (store.pack(), u if st.needs_next else None,
                                theta)


def _revolve_bwd(st, n_steps, ncheck, store, res, ct):
    with scope(f"{st.scopes['revolve']}/bwd"):
        ckpt_res, u_next, theta = res
        store.unpack(ckpt_res, [a for a, _ in segment_bounds(n_steps,
                                                             ncheck)])
        lam, mu = st.cotangent(ct), tree_zeros_like(theta)
        # adjoints run in strictly decreasing step order, so u_{n+1} of the
        # step in hand is the previous adjoint's checkpoint (u_N first).
        # The schedule is unrolled at trace time: without the barrier XLA
        # may keep every step's theta-sized gradient live at once
        for act in revolve_mod.reverse_schedule(n_steps, ncheck):
            if act[0] == "advance":
                _, start, m = act
                u, _ = st.advance(store.get(start), None, theta, start, m,
                                  recompute=True)
                store.put(start + m, u)
            elif act[0] == "adjoint":
                _, idx = act
                ckpt = store.get(idx)
                store.free(idx)
                lam, th_bar = st.adjoint(ckpt, u_next, theta, idx, lam)
                mu = tree_add(mu, th_bar)
                if st.needs_next:
                    u_next = ckpt
                lam, mu = jax.lax.optimization_barrier((lam, mu))
            else:
                store.free(act[1])
        return lam, mu


revolve.defvjp(_revolve_fwd, _revolve_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def revolve2(st, n_steps, ncheck, store, u0, theta):
    return _primal(st, n_steps, u0, theta)


def _revolve2_fwd(st, n_steps, ncheck, store, u0, theta):
    with scope(f"{st.scopes['revolve2']}/fwd"):
        u, aux = u0, st.aux0()
        for a, b in segment_bounds(n_steps, ncheck):
            store.put(a, u)
            u, aux = st.advance(u, aux, theta, a, b - a)
        return st.out(u, aux), (store.pack(), theta)


def _revolve2_bwd(st, n_steps, ncheck, store, res, ct):
    with scope(f"{st.scopes['revolve2']}/bwd"):
        ckpt_res, theta = res
        bounds = segment_bounds(n_steps, ncheck)
        store.unpack(ckpt_res, [a for a, _ in bounds])
        lam, mu = st.cotangent(ct), tree_zeros_like(theta)
        for a, b in reversed(bounds):
            u_a = store.get(a)
            store.free(a)
            # the re-advanced segment end is bitwise the forward's u_b
            u_b, _, ckpts = st.solve(u_a, theta, a, b - a, save=True,
                                     recompute=True)
            u_nexts = st.shifted(ckpts, u_b) if st.needs_next else None
            lam, mu = adjoint_scan(st, theta, lam, mu, ckpts, u_nexts, a,
                                   b - a)
        return lam, mu


revolve2.defvjp(_revolve2_fwd, _revolve2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def segmented(st, n_steps, store, segment, u0, theta):
    return _primal(st, n_steps, u0, theta)


def _segmented_fwd(st, n_steps, store, segment, u0, theta):
    with scope(f"{st.scopes['segmented']}/fwd"):
        n_full, rem = divmod(n_steps, segment)

        def run(u, aux, tok, base, m):
            def step(carry, i):
                u_next, aux, ckpt, info = st.step(*carry, theta, base, i)
                return (u_next, aux), (ckpt, info)

            (u, aux), (staged, infos) = jax.lax.scan(step, (u, aux),
                                                     jnp.arange(m))
            tok = store.write_batch(tok, base, staged)  # ONE callback
            return u, aux, tok, infos

        u, aux, tok = u0, st.aux0(), store.init_token()
        seg_infos = rem_infos = starts = rem_start = None
        if n_full:
            # a resilient sweep keeps each segment's entry state in the
            # residuals (O(N_t/segment) states) to re-integrate a segment
            # whose spilled payload fails its check
            def seg_body(carry, s_idx):
                u, aux, tok, infos = run(*carry, s_idx * segment, segment)
                return (u, aux, tok), (infos,
                                       carry[0] if st.resilient else None)

            (u, aux, tok), (seg_infos, starts) = jax.lax.scan(
                seg_body, (u, aux, tok), jnp.arange(n_full))
        if rem:
            rem_start = u if st.resilient else None
            u, aux, tok, rem_infos = run(u, aux, tok,
                                         jnp.asarray(n_full * segment), rem)
        st.tap(seg_infos, rem_infos, n_full * segment, aux)
        return st.out(u, aux), (tok, u if st.needs_next else None, theta,
                                starts, rem_start)


def _segmented_bwd(st, n_steps, store, segment, res, ct):
    with scope(f"{st.scopes['segmented']}/bwd"):
        tok, u_end, theta, starts, rem_start = res
        lam, mu, _, _ = reverse_segments(
            st, theta, store, segment, n_steps, st.cotangent(ct),
            tree_zeros_like(theta), u_end, tok, starts, rem_start)
        return lam, mu


segmented.defvjp(_segmented_fwd, _segmented_bwd)


def reverse_segments(st, theta, store, segment, n_slots, lam, mu, u_next,
                     tok, starts=None, rem_start=None, limit=None):
    """The segmented reverse sweep over slots ``[0, n_slots)``: one
    ``prefetch`` per segment, the trailing partial segment first.  Right
    after each wait it issues the background gather of the segment it
    adjoints next (``prefetch_issue``), so host/disk reads overlap adjoint
    compute; without a partial segment a warm-up issue starts the
    pipeline.  Every issue and wait rides one token chain.  ``limit``
    (traced) skips every slot at or past it, whole segments without a
    callback, and then no warm-up is issued: the last segment may never be
    read.  Returns ``(lam, mu, u_next, tok)``."""
    n_full, rem = divmod(n_slots, segment)

    def run(carry, base, m, u_start):
        def read(carry):
            lam, mu, u_next, tok = carry
            if st.resilient:
                tok, ok, fetched = store.prefetch_checked(tok, base, m)
                ckpts = jax.lax.cond(
                    ok, lambda _: fetched,
                    lambda _: st.recompute(u_start, theta, base, m), None)
                if st.obs is not None:  # bwd-rule emits survive jit(grad)
                    st.obs.emit("spill.recover", base=jnp.asarray(base),
                                ok=ok)
            else:  # checked reads stay synchronous: deterministic order
                tok, ckpts = store.prefetch(tok, base, m)  # ONE callback
                nb = base - segment
                tok = jax.lax.cond(
                    nb >= 0,
                    lambda t: store.prefetch_issue(t, jnp.maximum(nb, 0),
                                                   segment),
                    lambda t: t, tok)
            if st.needs_next:
                lam, mu = adjoint_scan(st, theta, lam, mu, ckpts,
                                       st.shifted(ckpts, u_next), base, m)
                u_next = jtu.tree_map(lambda s: s[0], ckpts)
            else:
                lam, mu = adjoint_scan(st, theta, lam, mu, ckpts, None,
                                       base, m, sliced=True, limit=limit)
            return lam, mu, u_next, tok

        if limit is None:
            return read(carry)
        return jax.lax.cond(base < limit, read, lambda c: c, carry)

    if n_full and not rem and not st.resilient and limit is None:
        tok = store.prefetch_issue(tok, jnp.asarray((n_full - 1) * segment),
                                   segment)
    carry = (lam, mu, u_next, tok)
    if rem:
        carry = run(carry, jnp.asarray(n_full * segment), rem, rem_start)
    if n_full:
        def seg_body(carry, inp):
            s_idx, u_start = inp
            return run(carry, s_idx * segment, segment, u_start), None

        carry, _ = jax.lax.scan(seg_body, carry, (jnp.arange(n_full), starts),
                                reverse=True)
    return carry
