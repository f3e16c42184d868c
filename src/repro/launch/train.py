"""End-to-end LM training driver: mesh + sharding + synthetic data + AdamW
+ fault tolerance (watchdog, straggler detection, checkpoint-restart).

Runs any assigned arch: a reduced config on the devices present by default
(so CPU runs finish), the full config on the devices present with --full
(e.g. SmolLM-135M on one TPU v5e), or the full config on the 16x16
production mesh with --production:

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --batch 8 --seq 128 --ckpt-dir ckpt
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --full \
      --steps 3 --batch 8 --seq 1024

Deterministic restart: the data pipeline is keyed by step and the checkpoint
carries (params, opt_state, step), so rerunning with the same --ckpt-dir
resumes and replays the exact loss curve (tested in tests/test_ft.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import time

import jax
import jax.numpy as jnp
from jax import tree_util as jtu

from repro.ckpt import CheckpointManager
from repro.configs.base import ModelConfig, ShapeCell, reduced
from repro.configs.registry import get_arch
from repro.data.pipeline import SyntheticLM
from repro.dist import sharding as shd
from repro.ft import StragglerDetector, TrainSupervisor
from repro.launch.mesh import (enable_compile_cache, make_host_mesh,
                               make_production_mesh)
from repro.launch.steps import init_compress_state, make_train_step
from repro.models import lm
from repro.obs import MetricsSink, StructuredLogger
from repro.optim.adamw import AdamW


def _compiled_peak_bytes(step_fn, *concrete_args):
    """Measured peak of the compiled train step
    (``launch.hlo_cost.peak_live_bytes`` — the same metric the byte-budget
    planner verifies against)."""
    from repro.launch.hlo_cost import peak_live_bytes
    compiled = step_fn.lower(*concrete_args).compile()
    return int(peak_live_bytes(compiled.as_text()))


def train(cfg: ModelConfig, cell: ShapeCell, *, steps: int, mesh=None,
          ckpt_dir: str | None = None, ckpt_every: int = 50,
          accum: int = 1, lr: float = 3e-4, log_every: int = 10,
          seed: int = 0, grad_dtype: str | None = None,
          compress: str | None = None, log_fn=print,
          sink: MetricsSink | None = None,
          predicted_peak_bytes: int | None = None,
          fault_plan=None, sentinel: bool = True,
          sentinel_bad_steps: int = 3, max_rollbacks: int = 2) -> dict:
    """Returns {"losses": [...], "grad_norms": [...], "resumed_from":
    step|None, ...}; the i-th loss and pre-clip global gradient norm are
    those of committed step ``start+i``.

    ``compress`` wires optim/compress.py gradient compression into the
    production step (flag-gated, default off; see launch/steps.py).

    ``sink`` (a ``repro.obs.MetricsSink``) receives one structured
    ``train.step`` record per step — loss, global grad norm, wall time —
    plus a ``train.compile`` record comparing the compiled step's measured
    peak bytes against ``predicted_peak_bytes`` (the planner's number,
    when a budget was planned); drift beyond 25% is warned through
    ``log_fn`` and flagged in the record.

    Fault tolerance (PR 8).  ``sentinel=True`` (default) builds the step
    with the in-graph non-finite sentinel (launch/steps.py): a step whose
    loss or grads are non-finite — injected or natural — commits nothing,
    and the loop *retries* it (the data pipeline is keyed by step, so the
    retry sees the identical batch; since nothing was committed, a clean
    retry reproduces the fault-free loss bitwise).  After
    ``sentinel_bad_steps`` consecutive bad attempts the loop rolls back to
    the last committed checkpoint and replays (deterministic pipeline =>
    exact replay); after ``max_rollbacks`` rollbacks — or with no
    checkpoint to roll back to — it raises ``FloatingPointError`` instead
    of looping forever on a genuinely divergent run.  SIGTERM requests a
    clean shutdown: the loop finishes the in-flight step, writes a final
    checkpoint, and drains pending ``CheckpointManager`` commits before
    returning (``result["preempted"]`` is True).  ``fault_plan=`` (a
    ``repro.ft.FaultPlan``) drives the chaos harness: site
    ``"train.step"`` kinds ``nan`` (poison that attempt in-graph) and
    ``preempt`` (request shutdown after that step, exercising the same
    drain path as a real SIGTERM).  The loss history is keyed by step, so
    retries and rollback-replays overwrite rather than duplicate:
    ``result["losses"][i]`` is the committed loss of step ``start+i``,
    directly comparable to a fault-free run."""
    mesh = mesh or make_host_mesh()
    slog = StructuredLogger(log_fn=log_fn, sink=sink)
    opt = AdamW(lr=lr, total_steps=max(steps, 2), warmup_steps=min(100, steps // 10 + 1),
                grad_dtype=grad_dtype)
    pipe = SyntheticLM(cfg, cell, seed=seed)

    with jax.set_mesh(mesh):
        params_shape = jax.eval_shape(
            lambda: lm.init_params(cfg, jax.random.PRNGKey(seed)))
        pspecs = shd.param_specs(cfg, params_shape, mesh)
        pshard = shd.to_shardings(pspecs, mesh)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        ospecs = shd.opt_state_specs(pspecs, opt_shape)
        oshard = shd.to_shardings(ospecs, mesh)

        init_fn = jax.jit(lambda k: lm.init_params(cfg, k),
                          out_shardings=pshard)
        params = init_fn(jax.random.PRNGKey(seed))
        opt_state = jax.jit(opt.init, out_shardings=oshard)(params)
        start_step = 0

        int8 = compress == "int8"
        comp_state = None
        if int8:
            comp_state = jax.jit(
                lambda p: init_compress_state(compress, p),
                out_shardings=pshard)(params)

        def ckpt_tree():
            # the int8 error-feedback residual is training state: dropping
            # it on resume would silently fork the loss trajectory
            tree = {"params": params, "opt_state": opt_state}
            if int8:
                tree["comp_state"] = comp_state
            return tree

        mgr = None
        shardings = {"params": pshard, "opt_state": oshard}
        if int8:
            shardings["comp_state"] = pshard
        if ckpt_dir:
            mgr = CheckpointManager(ckpt_dir, keep_n=3,
                                    fault_plan=fault_plan)
            latest = mgr.latest_step()
            if latest is not None:
                restored, start_step = mgr.restore_latest(ckpt_tree(),
                                                          shardings)
                params, opt_state = restored["params"], restored["opt_state"]
                if int8:
                    comp_state = restored["comp_state"]
                slog.log("train.resume",
                         f"[train] resumed from step {start_step}",
                         step=start_step)

        extra_in = (None,) if sentinel else ()  # the traced poison flag
        if int8:
            step_fn = jax.jit(
                make_train_step(cfg, opt, accum=accum, compress=compress,
                                sentinel=sentinel),
                in_shardings=(pshard, oshard, pshard, None, None) + extra_in,
                out_shardings=(pshard, oshard, pshard, None),
                donate_argnums=(0, 1, 2))
        else:
            step_fn = jax.jit(
                make_train_step(cfg, opt, accum=accum, compress=compress,
                                sentinel=sentinel),
                in_shardings=(pshard, oshard, None, None) + extra_in,
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1))

        measured_peak = None
        if sink is not None:
            # measure before step 0: donated buffers are gone afterwards
            first = pipe.batch(jnp.int32(start_step))
            cargs = ((params, opt_state, comp_state, first,
                      jnp.int32(start_step)) if int8 else
                     (params, opt_state, first, jnp.int32(start_step)))
            if sentinel:
                cargs = cargs + (False,)
            measured_peak = _compiled_peak_bytes(step_fn, *cargs)
            # a zero/absent prediction (planner skipped, dryrun config)
            # must still log the compile record — with drift=null — not
            # die on the division below
            drift = None
            if predicted_peak_bytes:
                # the planner prices live *activations*; the compiled peak
                # also holds params/opt-state/batch, so fold those in
                from repro.mem.model import tree_bytes
                predicted_peak_bytes = predicted_peak_bytes + tree_bytes(
                    (params, opt_state, first))
                if predicted_peak_bytes > 0:
                    drift = measured_peak / predicted_peak_bytes - 1.0
                if drift is not None and abs(drift) > 0.25:
                    slog.log("train.peak_drift",
                             f"[train] WARNING: measured peak "
                             f"{measured_peak} B is {drift:+.0%} off the "
                             f"planner's {predicted_peak_bytes} B",
                             measured_peak_bytes=measured_peak,
                             predicted_peak_bytes=predicted_peak_bytes,
                             drift=drift)
            slog.metric("train.compile",
                        measured_peak_bytes=measured_peak,
                        predicted_peak_bytes=predicted_peak_bytes,
                        drift=drift)
        detector = StragglerDetector()
        stragglers: list[int] = []
        loss_by_step: dict[int, float] = {}
        norm_by_step: dict[int, float] = {}
        skipped = 0
        rollbacks = 0
        consec_bad = 0
        preempted = False
        saved_at = None
        stop = {"sig": False}
        prev_handler = None
        try:  # SIGTERM = finish the in-flight step, checkpoint, drain
            prev_handler = signal.signal(
                signal.SIGTERM, lambda signum, frame:
                stop.__setitem__("sig", True))
        except ValueError:  # not on the main thread; no handler swap
            prev_handler = None
        try:
            with TrainSupervisor(
                    heartbeat_timeout_s=600.0, straggler=detector,
                    on_straggler=lambda s, dt: stragglers.append(s)) as sup:
                step = start_step
                while step < steps:
                    if stop["sig"]:
                        preempted = True
                        break
                    batch = pipe.batch(jnp.int32(step))
                    poison = False
                    want_preempt = False
                    if fault_plan is not None:
                        spec = fault_plan.tick("train.step")
                        if spec is not None and spec.kind == "nan":
                            poison = sentinel  # the in-graph hook
                        elif spec is not None and spec.kind == "preempt":
                            want_preempt = True
                    holder = {}

                    def do_step():
                        args = ((params, opt_state, comp_state, batch,
                                 jnp.int32(step)) if int8 else
                                (params, opt_state, batch, jnp.int32(step)))
                        if sentinel:
                            args = args + (poison,)
                        if int8:
                            p, o, c, m = step_fn(*args)
                            holder.update(c=c)
                        else:
                            p, o, m = step_fn(*args)
                        jax.block_until_ready(m["loss"])
                        holder.update(p=p, o=o, m=m)

                    dt = sup.step(do_step, step)
                    # the step donates its inputs: always pick up the
                    # returned buffers (on a skipped step they carry the
                    # old values bitwise — the in-graph select)
                    params, opt_state = holder["p"], holder["o"]
                    if int8:
                        comp_state = holder["c"]
                    m = holder["m"]
                    bad = sentinel and bool(m.get("nonfinite", 0))
                    if bad:
                        skipped += 1
                        consec_bad += 1
                        slog.log("train.skip",
                                 f"[train] step {step}: non-finite "
                                 f"loss/grad — update skipped (streak "
                                 f"{consec_bad})", step=step,
                                 streak=consec_bad)
                        if consec_bad >= sentinel_bad_steps:
                            if mgr is None or mgr.latest_step() is None:
                                raise FloatingPointError(
                                    f"training produced non-finite "
                                    f"loss/grads for {consec_bad} "
                                    f"consecutive attempts at step {step} "
                                    "and there is no checkpoint to roll "
                                    "back to")
                            if rollbacks >= max_rollbacks:
                                raise FloatingPointError(
                                    f"training still non-finite at step "
                                    f"{step} after {rollbacks} rollbacks "
                                    "— giving up (deterministic replay "
                                    "reproduces the divergence; this is "
                                    "not a transient)")
                            restored, rstep = mgr.restore_latest(
                                ckpt_tree(), shardings)
                            params = restored["params"]
                            opt_state = restored["opt_state"]
                            if int8:
                                comp_state = restored["comp_state"]
                            rollbacks += 1
                            consec_bad = 0
                            for s in [s for s in loss_by_step if s >= rstep]:
                                del loss_by_step[s], norm_by_step[s]
                            slog.log("train.rollback",
                                     f"[train] rolled back to step {rstep} "
                                     f"after {sentinel_bad_steps} "
                                     f"consecutive bad steps",
                                     step=rstep, rollbacks=rollbacks)
                            step = rstep
                        # else: retry the same step — nothing was
                        # committed, and the pipeline is keyed by step, so
                        # a clean retry reproduces the fault-free loss
                        # bitwise
                        continue
                    consec_bad = 0
                    loss = float(m["loss"])
                    loss_by_step[step] = loss
                    norm_by_step[step] = float(m["grad_norm"])
                    if sink is not None:
                        slog.metric("train.step", step=step, loss=loss,
                                    grad_norm=norm_by_step[step],
                                    step_ms=dt * 1e3)
                    if step % log_every == 0 or step == steps - 1:
                        log_fn(f"[train] step {step:5d} loss {loss:.4f} "
                               f"({dt*1e3:.0f} ms)")
                    if mgr and (step + 1) % ckpt_every == 0:
                        mgr.save(step + 1, ckpt_tree())
                        saved_at = step + 1
                    step += 1
                    if want_preempt:
                        fault_plan.note("train.preempt", step)
                        preempted = True
                        break
        finally:
            if prev_handler is not None:
                try:
                    signal.signal(signal.SIGTERM, prev_handler)
                except ValueError:
                    pass
        if mgr:
            # `step` is the committed progress (next step to run): the
            # final checkpoint lands there whether the loop completed or a
            # preemption broke out early (unless the loop just saved that
            # step: two async commits of one step race on its directory),
            # and wait() drains every pending async commit before we return
            if saved_at != step:
                mgr.save(step, ckpt_tree())
            mgr.wait()
        losses = [loss_by_step[s] for s in sorted(loss_by_step)]
        grad_norms = [norm_by_step[s] for s in sorted(norm_by_step)]
    return {"losses": losses, "grad_norms": grad_norms,
            "resumed_from": start_step or None,
            "stragglers": stragglers, "params": params,
            "skipped_steps": skipped, "rollbacks": rollbacks,
            "preempted": preempted}


def parse_bytes(spec: str) -> int:
    """'512M' / '8G' / '1e9' / '123456' -> bytes."""
    spec = str(spec).strip()
    mult = {"K": 2 ** 10, "M": 2 ** 20, "G": 2 ** 30, "T": 2 ** 40}
    if spec and spec[-1].upper() in mult:
        return int(float(spec[:-1]) * mult[spec[-1].upper()])
    return int(float(spec))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--full", action="store_true",
                      help="full config on the devices present "
                           "(default: the reduced smoke config)")
    mode.add_argument("--production", action="store_true",
                      help="full config on the 16x16 production mesh "
                           "(requires 256 real devices)")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--grad-dtype", default=None)
    ap.add_argument("--compress", default="none",
                    choices=["none", "bf16", "int8"],
                    help="gradient wire compression (optim/compress.py)")
    ap.add_argument("--mem-budget", default=None,
                    help="activation-memory budget in bytes (suffixes "
                         "K/M/G); the repro.mem planner picks the depth "
                         "remat policy for it, overriding --remat")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write per-step metrics as JSONL to PATH "
                         "(repro.obs.MetricsSink)")
    ap.add_argument("--no-sentinel", action="store_true",
                    help="disable the in-graph non-finite loss/grad "
                         "sentinel (skip-and-retry of poisoned steps)")
    ap.add_argument("--sentinel-bad-steps", type=int, default=3,
                    metavar="K",
                    help="roll back to the last committed checkpoint "
                         "after K consecutive non-finite steps (default 3)")
    ap.add_argument("--max-rollbacks", type=int, default=2,
                    help="give up (FloatingPointError) after this many "
                         "rollbacks (default 2)")
    args = ap.parse_args()

    enable_compile_cache()
    full = get_arch(args.arch)
    if args.production:
        cfg, mesh = full, make_production_mesh()
    elif args.full:
        cfg, mesh = full, make_host_mesh()
    else:
        cfg, mesh = reduced(full), make_host_mesh()
    if args.remat:
        cfg = dataclasses.replace(cfg, remat=args.remat)
    cell = ShapeCell("cli", args.seq, args.batch, "train")
    sink = MetricsSink(args.metrics) if args.metrics else None
    slog = StructuredLogger(sink=sink)
    predicted = None
    if args.mem_budget is not None:
        from repro.mem.planner import depth_remat_live_bytes, plan_depth_remat
        budget = parse_bytes(args.mem_budget)
        remat, ncheck, fits = plan_depth_remat(cfg, cell, budget)
        predicted = depth_remat_live_bytes(cfg, cell, remat, ncheck)
        slog.log("train.plan",
                 f"[train] mem budget {budget} B -> depth remat={remat!r} "
                 f"ncheck={ncheck} (predicted live {predicted} B)",
                 mem_budget=budget, remat=remat, ncheck=ncheck, fits=fits,
                 predicted_peak_bytes=predicted)
        if not fits:
            slog.log("train.plan_overflow",
                     "[train] WARNING: no depth-checkpointing policy fits "
                     "this budget — proceeding with the minimum-memory "
                     "plan, expect to exceed it", mem_budget=budget)
        cfg = dataclasses.replace(cfg, remat=remat, ncheck=ncheck)
    t0 = time.time()
    out = train(cfg, cell, steps=args.steps, mesh=mesh,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                accum=args.accum, lr=args.lr, grad_dtype=args.grad_dtype,
                compress=None if args.compress == "none" else args.compress,
                sink=sink, predicted_peak_bytes=predicted,
                sentinel=not args.no_sentinel,
                sentinel_bad_steps=args.sentinel_bad_steps,
                max_rollbacks=args.max_rollbacks)
    slog.log("train.done",
             f"[train] done in {time.time()-t0:.1f}s; "
             f"final loss {out['losses'][-1]:.4f}",
             final_loss=out["losses"][-1], stragglers=out["stragglers"])
    if sink is not None:
        sink.close()


if __name__ == "__main__":
    main()
