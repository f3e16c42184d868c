"""Readings from which the limits of ``correct`` are set (not part of a
benchmark run).  For a configuration and traffic mix, in one process:

  program     the timed step's first three steps against the reference,
              on every seed given
  control     the reference in bfloat16 in the program's place
  <fault>     the timed step broken underneath, for each planted fault of
              ``bench/lib/faults.py``

on the control seeds.  One JSON line per reading, then a summary line:
the largest program reading and the smallest control and fault readings
of each number.

  python3 bench/calibrate.py --config odenet-mnist \\
      --traffic clf-b128-rk4x8-pnode --seeds 1 2 3 --control-seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def first_steps_readings(model, seed, ref):
    """The model's first steps from ``seed`` against the reference's."""
    import numpy as np

    from bench.lib import compare, train_cell
    from bench.lib.seeds import seed_key

    params, state = model.init(seed_key(seed, train_cell.WEIGHTS))
    pool = model.batches(seed, train_cell.FIRST_STEPS)
    _, _, record = train_cell.first_steps(model, params, state, pool)
    del params, state, pool
    prog = train_cell.finish_record(model, seed, record)
    gnorm = float(np.sqrt(sum(np.sum(np.square(g.astype(np.float64)))
                              for g in record["grad1"])))
    return {**compare.readings(prog, ref), "losses": prog["losses"],
            "grad_norm": gnorm}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import run
    from bench.lib import compare, faults, spec, train_cell

    run.device_or_exit(1)
    run.enable_cache()
    cfg, traffic = spec.config(args.config), spec.traffic(args.traffic)
    build = spec.model_module(args.config).build
    model = build(cfg, traffic)
    rows = []

    def emit(kind, seed, values):
        row = {"kind": kind, "seed": seed, **values}
        rows.append(row)
        print(json.dumps(row), flush=True)

    def reference(seed, **kw):
        with jax.default_matmul_precision("highest"):
            return train_cell.reference(model, seed, **kw)

    for seed in args.seeds:
        t0 = time.perf_counter()
        ref = reference(seed)
        emit("program", seed, {**first_steps_readings(model, seed, ref),
                               "ref_losses": ref["losses"],
                               "s": time.perf_counter() - t0})
    broken = {name: make(build)(cfg, traffic)
              for name, make in faults.FAULTS.items()}
    for seed in args.control_seeds:
        ref = reference(seed)
        ctl = train_cell.reference(model, seed, dtype=jnp.bfloat16)
        emit("control", seed, compare.readings(ctl, ref))
        for name, bad in broken.items():
            emit(name, seed, first_steps_readings(bad, seed, ref))

    names = ("loss_gap", "grad_gap", "update_gap")
    summary = {"config": args.config, "traffic": args.traffic,
               "device": jax.devices()[0].device_kind}
    for kind in ["program", "control", *broken]:
        got = [r for r in rows if r["kind"] == kind]
        pick = max if kind == "program" else min
        if got:
            summary[kind] = {n: pick(r[n] for r in got) for n in names}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
