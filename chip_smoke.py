"""Bring-up check on a TPU: drive the main paths once through the entry
points a user calls, at full width, and check what comes out.

  python chip_smoke.py              # one chip: phases 1-6
  python chip_smoke.py --chips 4    # the sharded SmolLM-135M step on a 2x2
                                    # mesh, compared with one chip

Phases (one chip):
  1 device      the first device must be a TPU
  2 classifier  the paper's ODE image classifier (§5.1) at CIFAR-10 shape,
                AdamW steps under the naive, pnode and revolve adjoints;
                pnode/revolve gradients against naive at highest matmul
                precision; pnode with the fused Pallas stage kernels
  3 tiers       revolve with checkpoints in pinned host memory, pnode with
                checkpoints spilled through host callbacks; gradients
                against the device tier's at highest matmul precision
  4 implicit    Crank-Nicolson discrete-adjoint gradient over a vmapped
                ensemble of 1024 stiff Robertson systems, in float64
  5 serve       ODEEngine density/score requests over a CNF, against a
                direct cnf_log_prob reference
  6 lm          SmolLM-135M at its published widths, a few training steps
                through repro.launch.train.train on the devices present

One process owns the chip: nothing here starts a child process.  Every
phase prints one line; the first failure raises and exits non-zero.  The
last line of standard output is the JSON object
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
The persistent compile cache is the one ``repro.launch.mesh`` selects.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import tree_util as jtu  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.configs.base import ShapeCell  # noqa: E402
from repro.configs.registry import get_arch  # noqa: E402
from repro.core.depth_ode import ODEBlock  # noqa: E402
from repro.launch.mesh import enable_compile_cache, make_host_mesh  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models.ode_nets import (classifier_init, conv_vf,  # noqa: E402
                                   make_classifier_step, synthetic_cifar)
from repro.optim.adamw import AdamW  # noqa: E402


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def rel_gap(a, b) -> float:
    """Largest leaf-wise max|a-b| / max|b| over two gradient trees."""
    return max(float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-30))
               for x, y in zip(jtu.tree_leaves(a), jtu.tree_leaves(b)))


def bitwise(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jtu.tree_leaves(a), jtu.tree_leaves(b)))


def all_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jtu.tree_leaves(tree))


def compile_step(step, *args):
    """Compile a jitted step for ``args``; returns (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def phase_device(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {d.platform!r}")
    check(len(devs) >= chips, f"{chips} chips requested, {len(devs)} present")
    cache = enable_compile_cache()
    print(f"[1 device] kind={d.device_kind} count={len(devs)} "
          f"compile_cache={cache}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# ---------------------------------------------------------------------------
# 2-3: ODE classifier (paper §5.1) and its checkpoint tiers
# ---------------------------------------------------------------------------

class Classifier:
    """The §5.1 setup shared by phases 2 and 3: rk4 ODE block over
    ``conv_vf``, 10 classes, AdamW, seeded params and batches."""

    def __init__(self, batch: int = 128, channels: int = 32,
                 n_steps: int = 4, ncheck: int = 2, train_steps: int = 3):
        self.n_steps, self.ncheck = n_steps, ncheck
        self.params = classifier_init(jax.random.PRNGKey(0),
                                      channels=channels, n_classes=10)
        self.opt = AdamW(lr=2e-3, warmup_steps=1, total_steps=10)
        self.batches = [synthetic_cifar(jax.random.PRNGKey(100 + i), batch)
                        for i in range(train_steps)]

    def step(self, adjoint: str, **odeint_kw):
        kw = {"ncheck": self.ncheck} if adjoint == "revolve" else {}
        block = ODEBlock(conv_vf, n_steps=self.n_steps, method="rk4",
                         adjoint=adjoint, **kw, **odeint_kw)
        return make_classifier_step(block, self.opt)

    def args0(self):
        return (self.params, self.opt.init(self.params)) + self.batches[0]


def phase_classifier(clf: Classifier) -> dict:
    """Train a few steps per adjoint; returns each policy's step-0 grads at
    highest precision (the device-tier reference for phase 3)."""
    ref = {}
    for adjoint in ("naive", "pnode", "revolve"):
        compiled, secs = compile_step(clf.step(adjoint), *clf.args0())
        params, state = clf.params, clf.opt.init(clf.params)
        losses = []
        for i, (x, y) in enumerate(clf.batches):
            params, state, loss, logits, grads = compiled(params, state, x, y)
            if i == 0:
                ref[adjoint] = grads
            losses.append(float(loss))
        check(all(math.isfinite(v) for v in losses) and all_finite(params),
              f"{adjoint} losses {losses}")
        check(logits.shape == (x.shape[0], 10), f"logits {logits.shape}")
        print(f"[2 classifier] adjoint={adjoint} losses={losses} "
              f"compile_s={secs:.1f}", flush=True)

    with jax.default_matmul_precision("highest"):
        hi = {a: compile_step(clf.step(a), *clf.args0())[0](*clf.args0())[4]
              for a in ("naive", "pnode", "revolve")}
        fused, secs = compile_step(clf.step("pnode", fused_stages=True),
                                   *clf.args0())
        g_fused = fused(*clf.args0())[4]
    gaps_hi = {a: rel_gap(hi[a], hi["naive"]) for a in ("pnode", "revolve")}
    gaps_def = {a: rel_gap(ref[a], ref["naive"]) for a in ("pnode", "revolve")}
    print(f"[2 classifier] grad gap vs naive: highest={gaps_hi} "
          f"default={gaps_def}", flush=True)
    check(max(gaps_hi.values()) < 1e-4, f"highest-precision gaps {gaps_hi}")

    kernel = "tpu_custom_call" in fused.as_text()
    gap_fused = rel_gap(g_fused, hi["pnode"])
    print(f"[2 classifier] fused_stages: tpu_custom_call={kernel} "
          f"gap_vs_unfused={gap_fused:.3e} "
          f"bitwise={bitwise(g_fused, hi['pnode'])} compile_s={secs:.1f}",
          flush=True)
    check(kernel, "fused pnode step compiled without the Pallas kernel")
    check(gap_fused < 1e-5, f"fused vs unfused gap {gap_fused}")
    return hi


def phase_tiers(clf: Classifier, ref: dict) -> None:
    """Offloaded checkpoints against the device tier (``ref``), both at
    highest matmul precision: an offloaded program fuses differently, so
    on TPU its gradients may differ from the device tier's in the last
    bits, and the gap is reported with whether they are bitwise equal."""
    from repro.mem.offload import make_store, reset_spill_stats, spill_stats

    tier = make_store("host").effective_tier
    check(tier == "host", f"host store runs as {tier!r}")
    with jax.default_matmul_precision("highest"):
        lowered = clf.step("revolve", offload="host").lower(*clf.args0())
        pinned = "pinned_host" in lowered.as_text()
        check(pinned, "revolve residuals were not placed in pinned_host")
        g = lowered.compile()(*clf.args0())[4]
        gap = rel_gap(g, ref["revolve"])
        print(f"[3 tiers] host: effective_tier={tier} residuals_in_pinned_host="
              f"{pinned} gap_vs_device={gap:.3e} "
              f"bitwise_vs_device={bitwise(g, ref['revolve'])}", flush=True)
        check(gap < 1e-5, f"host-tier vs device-tier gap {gap}")

        compiled, _ = compile_step(clf.step("pnode", offload="spill"),
                                   *clf.args0())
        reset_spill_stats()
        g = jax.block_until_ready(compiled(*clf.args0())[4])
    st = spill_stats()
    gap = rel_gap(g, ref["pnode"])
    print(f"[3 tiers] spill: write_cb={st['write_cb']} read_cb="
          f"{st['read_cb']} slots={st['write_slots']} gap_vs_device="
          f"{gap:.3e} bitwise_vs_device={bitwise(g, ref['pnode'])}",
          flush=True)
    check(st["write_cb"] > 0 and st["read_cb"] > 0, f"no callbacks ran {st}")
    check(gap < 1e-5, f"spill-tier vs device-tier gap {gap}")


# ---------------------------------------------------------------------------
# 4: implicit (Crank-Nicolson) ensemble
# ---------------------------------------------------------------------------

def phase_implicit(ensemble: int = 1024, n_steps: int = 30,
                   dt: float = 0.01) -> None:
    """One gradient over a vmapped Robertson ensemble in the shape of
    ``benchmarks/stiff_ensemble.py``.  float64 for this phase only: the
    Newton tolerance (1e-10) is below float32 resolution."""
    from benchmarks.stiff_ensemble import LOSS_W, _solve
    with jax.enable_x64(True):
        u0s = jnp.tile(jnp.array([1.0, 0.0, 0.0]), (ensemble, 1))
        c_true = 0.2 * jax.random.normal(jax.random.PRNGKey(0),
                                         (ensemble, 3))
        c0 = jnp.zeros((ensemble, 3))
        truth = jax.jit(jax.vmap(lambda u, c: _solve(
            u, c, dt=dt, n_steps=n_steps, method="beuler")))(u0s, c_true)

        def loss(c):
            uf, stats = jax.vmap(lambda u, ci: _solve(
                u, ci, dt=dt, n_steps=n_steps, method="cn",
                return_stats=True))(u0s, c)
            return jnp.mean(jnp.sum((LOSS_W * (uf - truth)) ** 2, -1)), stats

        vg = jax.jit(jax.value_and_grad(loss, has_aux=True))
        compiled, secs = compile_step(vg, c0)
        (val, stats), g = compiled(c0)
        diverged = int(jnp.sum(stats.diverged))
        print(f"[4 implicit] ensemble={ensemble} n_steps={n_steps} "
              f"dtype={g.dtype} loss={float(val)!r} "
              f"|g|={float(jnp.linalg.norm(g))!r} diverged={diverged} "
              f"compile_s={secs:.1f}", flush=True)
        check(g.dtype == jnp.float64, f"ran in {g.dtype}")
        check(math.isfinite(float(val)) and all_finite(g), "non-finite")
        check(diverged == 0, f"{diverged} ensemble members diverged")


# ---------------------------------------------------------------------------
# 5: ODE serving
# ---------------------------------------------------------------------------

def phase_serve(n_requests: int = 12) -> None:
    """The engine set up as in ``examples/cnf_density.py --serve``."""
    from repro.core.cnf import cnf_log_prob
    from repro.models.ode_nets import cnf_vf, cnf_vf_init
    from repro.serve import BucketSpec, ODEEngine

    n_steps, method = 12, "bosh3"
    theta = cnf_vf_init(jax.random.PRNGKey(0), 2, hidden=(64, 64))
    eng = ODEEngine(cnf_vf, theta, dim=2, dt=1.0 / n_steps, n_steps=n_steps,
                    method=method, offload="spill", offload_segment=4,
                    buckets=BucketSpec((1, 2, 4, 8)))
    t0 = time.perf_counter()
    n_compiled = eng.warmup(kinds=("density", "score"))
    warm_s = time.perf_counter() - t0
    pts = np.random.default_rng(9).normal(size=(n_requests, 2)).astype(
        np.float32)
    kinds = ["score" if i % 4 == 0 else "density" for i in range(n_requests)]
    tickets = [eng.submit(k, p) for k, p in zip(kinds, pts)]
    served = eng.run()
    outs = [np.asarray(t.result(60.0)) for t in tickets]
    census = eng.slot_census()

    def ref_fn(x):
        lp = cnf_log_prob(cnf_vf, x, theta, dt=1.0 / n_steps,
                          n_steps=n_steps, method=method)
        return lp.sum(), lp

    (_, ref_lp), ref_score = jax.jit(jax.value_and_grad(
        ref_fn, has_aux=True))(jnp.asarray(pts))
    gap = max(float(np.max(np.abs(o - np.asarray(
        ref_lp[i] if k == "density" else ref_score[i]))))
        for i, (k, o) in enumerate(zip(kinds, outs)))
    print(f"[5 serve] programs={n_compiled} served={served}/{n_requests} "
          f"census={census} max_gap_vs_cnf_log_prob={gap:.3e} "
          f"warmup_s={warm_s:.1f}", flush=True)
    check(served == n_requests, f"served {served}")
    check(not any(census.values()), f"slots still live {census}")
    check(gap < 1e-4, f"engine vs cnf_log_prob gap {gap}")


# ---------------------------------------------------------------------------
# 6: SmolLM-135M training
# ---------------------------------------------------------------------------

def phase_lm(cfg, *, batch: int = 8, seq: int = 1024, steps: int = 3,
             mesh=None, tag: str = "6 lm") -> dict:
    cell = ShapeCell("chip_smoke", seq, batch, "train")
    out = train(cfg, cell, steps=steps, mesh=mesh or make_host_mesh(),
                log_every=1, log_fn=lambda line: print(f"[{tag}] {line}"))
    losses, norms = out["losses"], out["grad_norms"]
    print(f"[{tag}] {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} batch={batch}x{seq} losses={losses} "
          f"grad_norms={norms}", flush=True)
    check(len(losses) == steps, f"{len(losses)} of {steps} steps committed")
    check(all(math.isfinite(v) for v in losses + norms), "non-finite")
    # random init: step 0 predicts close to uniform over the vocabulary
    check(abs(losses[0] - math.log(cfg.vocab_size)) < 1.0,
          f"step-0 loss {losses[0]} far from ln(vocab)")
    return out


def phase_lm_sharded(cfg, *, batch: int = 8, seq: int = 1024) -> None:
    """One step on a (data=2, model=2) mesh and the same batch on one
    chip: step-0 loss and grad norm agree within bf16 rounding, and the
    parameters are spread over the four devices."""
    out4 = phase_lm(cfg, batch=batch, seq=seq, steps=1,
                    mesh=make_host_mesh(model_axis=2), tag="lm 2x2")
    per_dev: dict = {}
    for leaf in jtu.tree_leaves(out4["params"]):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    total = sum(x.nbytes for x in jtu.tree_leaves(out4["params"]))
    one = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                        axis_types=(AxisType.Auto,) * 2)
    out1 = phase_lm(cfg, batch=batch, seq=seq, steps=1, mesh=one,
                    tag="lm 1x1")
    dl = abs(out4["losses"][0] - out1["losses"][0]) / out1["losses"][0]
    dn = abs(out4["grad_norms"][0] - out1["grad_norms"][0]) \
        / out1["grad_norms"][0]
    print(f"[lm sharded] param_bytes_per_device={per_dev} "
          f"param_bytes_total={total} rel_gap_loss={dl:.3e} "
          f"rel_gap_grad_norm={dn:.3e}", flush=True)
    check(len(per_dev) == 4 and max(per_dev.values()) < 0.75 * total,
          "parameters are not spread over the four devices")
    check(dl < 1e-2 and dn < 5e-2, "2x2 and 1-chip step 0 disagree")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded SmolLM step on a 2x2 "
                         "mesh and its one-chip comparison")
    args = ap.parse_args()

    device = phase_device(args.chips)
    smollm = get_arch("smollm-135m")
    if args.chips == 4:
        phase_lm_sharded(smollm)
    else:
        clf = Classifier()
        ref = phase_classifier(clf)
        phase_tiers(clf, ref)
        phase_implicit()
        phase_serve()
        phase_lm(smollm)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
