"""Analytic per-policy memory/compute cost model (the paper's Table 2).

Maps every adjoint policy to (peak live bytes, extra reverse-pass f
evaluations) as a function of N_t (steps), the tableau's stage counts, the
state size, and — for revolve — N_c (checkpoint slots):

  policy      ckpt storage (bytes)                 NFE-B (extra f evals)
  naive       N_t * N_s * A_f    (AD residuals)    0
  continuous  0                                    N_s * N_t   (not rev-acc)
  anode       N_t * N_s * A_f    (recompute+AD)    2 N_s N_t
  aca         N_t * S                              2 N_s N_t
  pnode       N_t * S                              N_s^a N_t
  pnode2      N_t * S                              (N_s + N_s^a) N_t
  revolve     (N_c+1) * S                          N_s p~(N_t,N_c) + N_s^a N_t
  revolve2    (N_c+1+seg) * S                      ~N_s (N_t-N_c) + N_s^a N_t

with S = state bytes, N_s^a = stages the discrete adjoint linearizes
(``adjoint_stages``), p~ the Prop-2 recompute optimum, and A_f the bytes of
AD residuals one f evaluation leaves behind (``f_activation_bytes`` — the
N_l-dependent term that makes NODE-naive the steepest curve in Fig. 3).
The rows price what the sweeps hold.  The paper's Table 2 gives pnode
N_t (N_s+1) S, and revolve (N_c+1)(N_s+1) S, because its checkpoint holds
the step's stage derivatives too; here a checkpoint is the state alone,
since the reverse step takes each stage derivative from the primal output
of that stage's linearisation, which it makes anyway
(``core.integrators.rk_linearize``), at no extra f evaluation.
An ``offload`` tier moves the ckpt-storage term off device (see
``repro.mem.offload``); it never changes NFE-B.

Off-device storage is itself two-tiered (the dolfin-adjoint multistage
split): ``snaps_in_ram`` caps how many checkpoint slots stay RAM-resident,
the overflow sinks to segment files on disk (``offload="disk"`` is the
all-disk corner).  ``CostEstimate`` prices the split with per-tier byte
columns (``ram_bytes``/``disk_bytes``) and a modeled transfer time
(``io_seconds`` — one fwd write + one bwd read of every slot at the
tier's bandwidth, plus per-callback latency), so the planner can solve
the ``snaps_in_ram`` split under separate RAM and disk byte budgets and
rank tiers by I/O cost where NFE-B ties.

Implicit theta-methods (``method="beuler"|"cn"``) dispatch to their own
Table-2 column (``core.implicit``): a checkpoint slot is ONE converged
state (S bytes — the Newton/GMRES iterates never enter the graph), the
reverse-step working set is dominated by the transposed-GMRES Krylov basis
(``gmres_iters`` state vectors), NFE-B counts f *linearizations*
(``implicit_adjoint_fevals`` per step) and a recomputed step costs a full
Newton solve (``implicit_step_fevals`` = newton_iters*(gmres_iters+2)+1
f evaluations) — which is why revolve checkpoint spacing is cheap in
memory but expensive in recompute for stiff solves, and the planner's
ranking by extra_fevals handles both families uniformly.

The model is validated against measured byte counts of the lowered reverse
pass (``launch/hlo_cost.peak_live_bytes`` on the compiled HLO) in
tests/test_mem.py, and ``measure_reverse_cost`` here is the measurement
used by both the planner's verify step and the fig3/mem_plan benchmarks.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import tree_util as jtu

from repro.core import revolve as revolve_mod
from repro.core.adjoint import (adjoint_stages, checkpoint_floats,
                                nfe_backward)
from repro.core.implicit import (IMPLICIT_POLICIES, implicit_adjoint_fevals,
                                 implicit_checkpoint_floats,
                                 implicit_nfe_backward, implicit_step_fevals,
                                 is_implicit_method)
from repro.core.tableaus import get_tableau

PyTree = Any

#: policies whose gradients are exact reorderings of the naive chain rule
REVERSE_ACCURATE = ("naive", "anode", "aca", "pnode", "pnode2", "revolve",
                    "revolve2")

#: modeled off-device transfer rates: host-RAM copies (pinned-host /
#: callback-dict) vs segment-file disk I/O, plus the fixed cost of one
#: host callback round-trip.  Coarse XLA:CPU figures — the planner uses
#: the RAM:disk *ratio* to price the snaps_in_ram split, so absolute
#: calibration is not load-bearing (measured peaks gate the budget, not
#: these).
HOST_COPY_BW = 8e9       # bytes/s
DISK_BW = 500e6          # bytes/s
CALLBACK_LATENCY_S = 50e-6


def slot_bytes(method: str, state_bytes: int) -> int:
    """Bytes of ONE checkpoint slot: S, the state, for explicit tableaus
    and implicit methods alike.  The unit of the ``snaps_in_ram`` RAM/disk
    split."""
    return int(state_bytes)


def _offload_io(offload: Optional[str], ckpt_bytes: int, callbacks: int,
                method: str, state_bytes: int,
                snaps_in_ram: Optional[int]) -> Tuple[int, int, float]:
    """(ram_bytes, disk_bytes, io_seconds) of one fwd+bwd round trip: the
    off-device checkpoint set split across the RAM/disk media, each byte
    written once and read once at its tier's bandwidth."""
    if offload not in ("host", "spill", "disk") or ckpt_bytes <= 0:
        return 0, 0, 0.0
    if offload == "disk":
        ram, disk = 0, int(ckpt_bytes)
    elif offload == "spill" and snaps_in_ram is not None:
        sb = max(1, slot_bytes(method, state_bytes))
        ram = min(int(ckpt_bytes), int(snaps_in_ram) * sb)
        disk = int(ckpt_bytes) - ram
    else:  # host, or spill with unlimited RAM
        ram, disk = int(ckpt_bytes), 0
    io = 2.0 * (ram / HOST_COPY_BW + disk / DISK_BW) \
        + callbacks * CALLBACK_LATENCY_S
    return ram, disk, io


def tree_bytes(tree: PyTree) -> int:
    """Total bytes of a pytree of (possibly abstract) arrays."""
    total = 0
    for leaf in jtu.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is None or dtype is None:
            leaf = jnp.asarray(leaf)
            size, dtype = leaf.size, leaf.dtype
        total += int(size) * jnp.dtype(dtype).itemsize
    return total


def f_activation_bytes(f: Callable, u0: PyTree, theta: PyTree,
                       t: float = 0.0) -> int:
    """AD-residual bytes one ``f`` evaluation leaves behind: the summed
    output bytes of every equation in f's jaxpr — the O(N_l) depth term
    that naive/anode pay per stage and the high-level adjoint avoids."""
    try:
        jaxpr = jax.make_jaxpr(lambda u, th: f(u, th, t))(u0, theta)
    except Exception:
        return tree_bytes(u0)
    total = 0
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                n = 1
                for d in aval.shape:
                    n *= int(d)
                total += n * jnp.dtype(aval.dtype).itemsize
    return max(total, tree_bytes(u0))


@dataclass(frozen=True)
class CostEstimate:
    """One Table-2 row instantiated at concrete sizes."""
    policy: str
    ncheck: Optional[int]
    offload: Optional[str]
    ckpt_bytes: int        # checkpoint storage between fwd and bwd sweeps
    work_bytes: int        # transient working set of one reverse step
    extra_fevals: int      # NFE-B: reverse-pass f evaluations
    reverse_accurate: bool
    host_callbacks: int = 0  # host round-trips per reverse pass (spill tier)
    ram_bytes: int = 0       # off-device ckpt bytes resident in host RAM
    disk_bytes: int = 0      # off-device ckpt bytes sunk to segment files
    io_seconds: float = 0.0  # modeled fwd-write + bwd-read transfer time

    @property
    def peak_bytes(self) -> int:
        """Predicted device-live peak: offloaded ckpt storage leaves the
        device, everything else stays (including, for the spill tiers, the
        segment staging buffer folded into work_bytes)."""
        if self.offload in ("host", "spill", "disk"):
            return self.work_bytes
        return self.ckpt_bytes + self.work_bytes


def spill_callback_counts(policy: str, n_steps: int, *,
                          ncheck: Optional[int] = None,
                          segment: Optional[int] = None) -> Dict[str, int]:
    """Host callbacks one reverse pass issues on the spill tier (the
    batched-I/O reality the planner ranks against; BENCH_3 measures it).

    pnode's scanned sweeps batch ``segment`` checkpoints per callback
    (fwd ``write_batch`` + bwd ``prefetch``); the revolve policies are
    slot-addressed at trace time and already pay one callback per
    checkpoint-schedule action (puts/gets/frees).
    """
    from repro.core import revolve as revolve_mod  # late: import cycle
    from repro.mem.offload import default_segment
    if policy == "pnode":
        seg = min(segment or default_segment(n_steps), n_steps)
        n_segments = -(-n_steps // seg)
        return {"forward": n_segments, "backward": n_segments,
                "total": 2 * n_segments}
    if policy == "revolve":
        fwd = ncheck + 1  # one put per sweep checkpoint
        bwd = 0
        for act in revolve_mod.reverse_schedule(n_steps, ncheck):
            bwd += {"advance": 2, "adjoint": 2, "free": 1}[act[0]]
        return {"forward": fwd, "backward": bwd, "total": fwd + bwd}
    if policy == "revolve2":
        from repro.core.sweeps import segment_bounds
        nb = len(segment_bounds(n_steps, ncheck))
        return {"forward": nb, "backward": 2 * nb, "total": 3 * nb}
    return {"forward": 0, "backward": 0, "total": 0}


#: state copies one implicit reverse step keeps in flight beyond the
#: transposed-GMRES Krylov basis (lam, lam_s, u_n, u_next)
_IMPLICIT_WORK_STATES = 4


def _implicit_policy_cost(policy: str, *, n_steps: int, state_bytes: int,
                          theta_bytes: int, ncheck: Optional[int],
                          offload: Optional[str], segment: Optional[int],
                          newton_iters: int, gmres_iters: int,
                          snaps_in_ram: Optional[int] = None,
                          method: str = "cn") -> CostEstimate:
    """Implicit-family Table-2 row: checkpoints are converged states only
    (S bytes/slot), work is Krylov-basis dominated, recompute is Newton
    solves (see module docstring)."""
    if policy not in IMPLICIT_POLICIES:
        raise ValueError(
            f"policy {policy!r} is not available for implicit methods; "
            f"one of {IMPLICIT_POLICIES} (AD-through-the-solver policies "
            "have no reverse rule for the Newton/GMRES while_loops)")
    work = (int(gmres_iters) + _IMPLICIT_WORK_STATES) * state_bytes \
        + 3 * theta_bytes
    ckpt = implicit_checkpoint_floats(n_steps, policy, state_bytes,
                                      ncheck=ncheck)
    extra = implicit_nfe_backward(n_steps, policy, ncheck=ncheck,
                                  newton_iters=newton_iters,
                                  gmres_iters=gmres_iters)
    callbacks = 0
    if offload in ("spill", "disk"):
        callbacks = spill_callback_counts(policy, n_steps, ncheck=ncheck,
                                          segment=segment)["total"]
        if policy == "pnode":
            # segment staging buffer (states only — no stages to stage)
            from repro.mem.offload import default_segment
            seg = min(segment or default_segment(n_steps), n_steps)
            work += seg * state_bytes
    ram, disk, io = _offload_io(offload, int(ckpt), callbacks, method,
                                state_bytes, snaps_in_ram)
    return CostEstimate(policy=policy, ncheck=ncheck, offload=offload,
                        ckpt_bytes=int(ckpt), work_bytes=int(work),
                        extra_fevals=int(extra), reverse_accurate=True,
                        host_callbacks=int(callbacks), ram_bytes=ram,
                        disk_bytes=disk, io_seconds=io)


def policy_cost(policy: str, *, method: str, n_steps: int, state_bytes: int,
                theta_bytes: int = 0, f_act_bytes: Optional[int] = None,
                ncheck: Optional[int] = None,
                offload: Optional[str] = None,
                segment: Optional[int] = None,
                newton_iters: int = 10,
                gmres_iters: int = 20,
                snaps_in_ram: Optional[int] = None) -> CostEstimate:
    """Analytic (peak bytes, extra f-evals) for one policy instance.
    ``newton_iters``/``gmres_iters`` only affect implicit methods;
    ``snaps_in_ram`` prices the spill tier's RAM/disk slot split
    (``ram_bytes``/``disk_bytes``/``io_seconds`` columns)."""
    if is_implicit_method(method):
        return _implicit_policy_cost(policy, n_steps=n_steps,
                                     state_bytes=state_bytes,
                                     theta_bytes=theta_bytes, ncheck=ncheck,
                                     offload=offload, segment=segment,
                                     newton_iters=newton_iters,
                                     gmres_iters=gmres_iters,
                                     snaps_in_ram=snaps_in_ram,
                                     method=method)
    tab = get_tableau(method)
    s = tab.num_stages
    fa = f_act_bytes if f_act_bytes is not None else state_bytes
    # one step's stages + a few state copies in flight + grad accumulators
    work = (s + 3) * state_bytes + 3 * theta_bytes

    if policy in ("naive", "anode"):
        # AD through the (re)computed forward: every stage's f residuals
        ckpt = n_steps * s * fa
        if policy == "anode":
            ckpt += state_bytes  # the block-input checkpoint itself
    elif policy == "continuous":
        ckpt = 0
    else:
        ckpt = checkpoint_floats(method, n_steps, policy,
                                 state_bytes, ncheck=ncheck)
    extra = nfe_backward(method, n_steps, policy,
                         ncheck=ncheck) if policy != "naive" else 0
    callbacks = 0
    if offload in ("spill", "disk"):
        callbacks = spill_callback_counts(policy, n_steps, ncheck=ncheck,
                                          segment=segment)["total"]
        if policy == "pnode":
            # segment staging buffer: the batched sweeps hold one segment
            # of state checkpoints on device between callbacks
            from repro.mem.offload import default_segment
            seg = min(segment or default_segment(n_steps), n_steps)
            work += seg * state_bytes
    ram, disk, io = _offload_io(offload, int(ckpt), callbacks, method,
                                state_bytes, snaps_in_ram)
    return CostEstimate(policy=policy, ncheck=ncheck, offload=offload,
                        ckpt_bytes=int(ckpt), work_bytes=int(work),
                        extra_fevals=int(extra),
                        reverse_accurate=policy in REVERSE_ACCURATE,
                        host_callbacks=int(callbacks), ram_bytes=ram,
                        disk_bytes=disk, io_seconds=io)


def max_fitting_ncheck(budget: int, *, method: str, n_steps: int,
                       state_bytes: int, theta_bytes: int = 0,
                       newton_iters: int = 10,
                       gmres_iters: int = 20) -> Optional[int]:
    """Largest N_c whose revolve checkpoint set fits the byte budget
    (storage (N_c+1)S: a slot is one state, explicit and implicit alike),
    clamped to the valid [1, N_t-1] range; None if even N_c = 1 does not
    fit."""
    probe = policy_cost("revolve", method=method, n_steps=n_steps,
                        state_bytes=state_bytes, theta_bytes=theta_bytes,
                        ncheck=1, newton_iters=newton_iters,
                        gmres_iters=gmres_iters)
    avail = budget - probe.work_bytes
    per_slot = slot_bytes(method, state_bytes)
    if per_slot <= 0:
        return n_steps - 1
    k = avail // per_slot - 1
    if k < 1:
        return None
    return int(min(k, n_steps - 1))


# ---------------------------------------------------------------------------
# measurement: the model's ground truth
# ---------------------------------------------------------------------------

_MEASURE_CACHE: Dict[Tuple, Dict[str, float]] = {}


def _struct_key(tree: PyTree) -> Tuple:
    leaves, treedef = jtu.tree_flatten(tree)
    return (str(treedef),) + tuple(
        (tuple(jnp.shape(x)), str(jnp.result_type(x))) for x in leaves)


def measure_reverse_cost(f: Callable, u0: PyTree, theta: PyTree, *,
                         dt: float, n_steps: int, t0: float = 0.0,
                         method: str = "rk4", policy: str = "pnode",
                         ncheck: Optional[int] = None,
                         offload: Optional[str] = None,
                         loss_fn: Optional[Callable] = None,
                         solver_opts: Optional[Dict[str, Any]] = None
                         ) -> Dict[str, float]:
    """Lower + compile the reverse pass (grad of a scalar loss of the
    solve) and measure its peak bytes two ways:

      hlo_peak_bytes  liveness sweep over the optimized HLO text
                      (``launch.hlo_cost.peak_live_bytes``) — the metric the
                      planner's budget check and the acceptance tests use;
      temp_bytes /    XLA's own compiled buffer-assignment accounting
      argument_bytes  (``compiled.memory_analysis()``), kept as a
                      cross-check column in the benchmarks.

    ``loss_fn(u_final) -> scalar`` measures the reverse pass of the
    CALLER'S loss (the planner forwards it from ``plan_odeint``) so the
    budget check sees the real training objective's working set; the
    default is the canonical sum-of-squares surrogate.

    ``solver_opts`` (newton_iters/newton_tol/gmres_iters/gmres_tol) is
    forwarded to ``odeint_implicit`` for implicit methods — the measured
    reverse pass uses the caller's actual solver configuration (the Krylov
    basis scales with gmres_iters), and the opts are part of the cache key.

    Results are cached on (f identity, loss_fn identity, arg structure,
    solve configuration): a planner verify step compiles each candidate at
    most once per session.
    """
    from repro.core.adjoint import odeint  # late: avoid import cycle
    from repro.launch.hlo_cost import peak_live_bytes

    opts_key = None if solver_opts is None else \
        tuple(sorted(solver_opts.items()))
    key = (id(f), None if loss_fn is None else id(loss_fn), _struct_key(u0),
           _struct_key(theta), float(dt), int(n_steps), float(t0), method,
           policy, ncheck, offload, opts_key,
           bool(jax.config.jax_enable_x64))
    hit = _MEASURE_CACHE.get(key)
    if hit is not None:
        return hit[1]

    def loss(u0_, th_):
        if is_implicit_method(method):
            from repro.core.implicit import odeint_implicit
            uf = odeint_implicit(f, u0_, th_, dt=dt, n_steps=n_steps, t0=t0,
                                 method=method, adjoint=policy,
                                 ncheck=ncheck, offload=offload,
                                 **(solver_opts or {}))
        else:
            uf = odeint(f, u0_, th_, dt=dt, n_steps=n_steps, t0=t0,
                        method=method, adjoint=policy, ncheck=ncheck,
                        offload=offload)
        if loss_fn is not None:
            return loss_fn(uf)
        return sum(jnp.sum(x * x) for x in jtu.tree_leaves(uf))

    grad_fn = jax.grad(loss, argnums=(0, 1))
    compiled = jax.jit(grad_fn).lower(u0, theta).compile()
    mem = compiled.memory_analysis()
    out = {
        "hlo_peak_bytes": float(peak_live_bytes(compiled.as_text())),
        "temp_bytes": float(getattr(mem, "temp_size_in_bytes", -1.0))
        if mem is not None else -1.0,
        "argument_bytes": float(getattr(mem, "argument_size_in_bytes", -1.0))
        if mem is not None else -1.0,
    }
    # the entry keeps strong references to f / loss_fn: id() keys would
    # otherwise be reusable after garbage collection and alias different
    # functions
    _MEASURE_CACHE[key] = ((f, loss_fn), out)
    return out
