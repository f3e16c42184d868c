import os
# default to a pod's worth of fake host devices for the production-mesh CLI,
# but never stomp a caller that already forced its own device count (other
# XLA_FLAGS, e.g. --xla_dump_to, are preserved and the count appended)
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512"
                               ).strip()

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes, prove memory fits, and extract roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod|multipod]
  ... --accum 8 --remat sqrt --seq-shard   (hillclimb knobs)

Results are cached as JSON under experiments/dryrun/<mesh>/<arch>__<shape>*.json.
"""
import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import tree_util as jtu
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS, cell_runnable, get_arch, get_shape
from repro.data.pipeline import SyntheticLM
from repro.dist import sharding as shd
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import (HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.launch.steps import make_decode_step, make_prefill_step, \
    make_train_step
from repro.models import lm
from repro.obs import MetricsSink, StructuredLogger
from repro.optim.adamw import AdamW

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in (post-SPMD) HLO text."""
    out = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        for c in _COLLECTIVES:
            # result-typed ops look like:  %x = f32[..]{..} all-gather(...)
            if f" {c}(" in ls or f" {c}-start(" in ls:
                lhs = ls.split(f" {c}")[0]
                out[c] += _shape_bytes(lhs)
                break
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def _shaped(tree):
    return jtu.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def build_cell(arch: str, shape: str, mesh, accum: int = 1,
               remat: str | None = None, attn_impl: str | None = None):
    """Returns (fn, arg_shapes, in_shardings, kind)."""
    cfg = get_arch(arch)
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    cell = get_shape(shape)

    params_shape = jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = shd.param_specs(cfg, params_shape, mesh)
    pshard = shd.to_shardings(pspecs, mesh)

    if cell.kind == "train":
        opt = AdamW(total_steps=1000)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        ospecs = shd.opt_state_specs(pspecs, opt_shape)
        oshard = shd.to_shardings(ospecs, mesh)
        pipe = SyntheticLM(cfg, cell)
        batch_shape = jax.eval_shape(pipe.batch, jnp.zeros((), jnp.int32))
        bspecs = shd.batch_specs(cfg, cell, mesh)
        bshard = jtu.tree_map(lambda s: NamedSharding(mesh, s), bspecs,
                              is_leaf=lambda x: isinstance(x, P))
        step_fn = make_train_step(cfg, opt, accum=accum)
        args = (params_shape, opt_shape, batch_shape,
                jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = (pshard, oshard, bshard, NamedSharding(mesh, P()))
        return step_fn, args, in_sh, cfg, cell

    if cell.kind == "prefill":
        pipe = SyntheticLM(cfg, cell)
        batch_shape = jax.eval_shape(pipe.batch, jnp.zeros((), jnp.int32))
        batch_shape = {k: v for k, v in batch_shape.items() if k != "targets"}
        bspecs = {k: v for k, v in
                  shd.batch_specs(cfg, cell, mesh).items()
                  if k in batch_shape}
        bshard = jtu.tree_map(lambda s: NamedSharding(mesh, s), bspecs,
                              is_leaf=lambda x: isinstance(x, P))
        step_fn = make_prefill_step(cfg, max_seq=cell.seq_len)
        return step_fn, (params_shape, batch_shape), (pshard, bshard), cfg, cell

    # decode
    bsz = cell.global_batch
    state_shape = jax.eval_shape(
        lambda: lm.init_decode_state(cfg, bsz, cell.seq_len))
    sspecs = shd.decode_state_specs(cfg, cell, state_shape, mesh)
    sshard = shd.to_shardings(sspecs, mesh)
    ba = shd.batch_axes(mesh)
    bspec = ba if ba and bsz % max(
        1, int(jnp.prod(jnp.array([mesh.shape[a] for a in ba])))) == 0 else None
    token_shape = jax.ShapeDtypeStruct((bsz, 1), jnp.int32)
    tshard = NamedSharding(mesh, P(bspec, None))
    pos_shape = jax.ShapeDtypeStruct((), jnp.int32)
    step_fn = make_decode_step(cfg)
    return (step_fn, (params_shape, state_shape, token_shape, pos_shape),
            (pshard, sshard, tshard, NamedSharding(mesh, P())), cfg, cell)


def model_flops(cfg, cell, accum=1) -> float:
    """Useful-work FLOPs: 6ND (2ND inference) for parameter matmuls PLUS
    the attention score/value matmuls (2*2*B*S*ctx*H*dh fwd), which 6ND
    ignores but which dominate small-d_model archs at 4k+ context.  Causal
    global attention uses ctx = S/2; sliding-window layers use ctx = w;
    decode uses ctx = cache length.  SSM ('w') layers add the chunked
    linear-attention state matmuls ~6*B*S*H*dh^2.  RG-LRU ('r') recurrences
    are elementwise (negligible)."""
    n_active = cfg.active_param_count()
    b, s = cell.global_batch, cell.seq_len
    tokens = b * (s if cell.kind != "decode" else 1)
    mult = 3.0 if cell.kind == "train" else 1.0
    flops = (2.0 * mult) * n_active * tokens

    h, dh = (cfg.n_heads or 0), cfg.dh
    for kind, win in zip(cfg.kinds, cfg.win):
        if kind == "a" and h:
            if cell.kind == "decode":
                ctx = min(win, s) if win else s
                flops += mult * 4.0 * b * ctx * h * dh
            else:
                ctx = min(win, s) if win else s / 2.0
                flops += mult * 4.0 * b * s * ctx * h * dh
        elif kind == "w":
            nh = cfg.n_heads or (cfg.d_model // 64)
            dhw = cfg.d_model // nh
            per_tok = 6.0 * nh * dhw * dhw
            flops += mult * per_tok * tokens
    if cfg.family == "encdec" and cfg.enc_seq:
        # encoder self-attention (bidirectional) + decoder cross-attention
        se = cfg.enc_seq
        flops += mult * cfg.n_enc_layers * 4.0 * b * se * se * h * dh
        q = s if cell.kind != "decode" else 1
        flops += mult * cfg.n_layers * 4.0 * b * q * se * h * dh
    return flops


def run_cell(arch: str, shape: str, multi_pod: bool, accum: int = 1,
             remat: str | None = None, attn_impl: str | None = None,
             out_dir: str = "experiments/dryrun", force: bool = False,
             tag: str = "") -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    out_path = Path(out_dir) / mesh_name / f"{arch}__{shape}{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    ok, reason = cell_runnable(arch, shape)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "accum": accum,
           "remat": remat, "tag": tag}
    if not ok:
        rec.update(status="skipped", reason=reason)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = mesh.size
        t0 = time.time()
        fn, args, in_sh, cfg, cell = build_cell(arch, shape, mesh, accum,
                                                remat, attn_impl)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        # trip-count-aware accounting (compiled.cost_analysis() counts every
        # lax.scan body ONCE — see launch/hlo_cost.py); all numbers are
        # per-partition (the SPMD module is per-device)
        acc = hlo_analyze(hlo)
        coll = {k: v for k, v in acc.collective_bytes.items()}
        coll["total"] = acc.collective_total
        flops = acc.flops
        bytes_acc = acc.bytes
        t_compute = flops / PEAK_FLOPS_BF16
        t_memory = bytes_acc / HBM_BW
        # ~4 usable ICI links per v5e chip on a 2D torus (x2 dirs x2 axes)
        t_coll = coll["total"] / (4 * ICI_BW_PER_LINK)
        mflops = model_flops(cfg, cell, accum)
        rec.update(
            status="ok",
            n_chips=n_chips,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory=None if mem is None else {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None),
            },
            hlo_flops_per_device=flops,
            hlo_bytes_per_device=bytes_acc,
            collective_bytes_per_device=coll,
            roofline={
                "compute_s": t_compute,
                "memory_s": t_memory,
                "collective_s": t_coll,
                "dominant": max(
                    [("compute", t_compute), ("memory", t_memory),
                     ("collective", t_coll)], key=lambda kv: kv[1])[0],
            },
            model_flops_total=mflops,
            model_flops_per_device=mflops / n_chips,
            useful_flops_ratio=(mflops / n_chips) / max(flops, 1.0),
            params_total=cfg.param_count(),
            params_active=cfg.active_param_count(),
        )
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write one structured JSONL record per cell to "
                         "PATH (repro.obs.MetricsSink)")
    args = ap.parse_args()

    cells = []
    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    sink = MetricsSink(args.metrics) if args.metrics else None
    slog = StructuredLogger(sink=sink)
    for arch, shape in cells:
        try:
            rec = run_cell(arch, shape, args.mesh == "multipod", args.accum,
                           args.remat, args.attn_impl, args.out_dir,
                           args.force, args.tag)
            if rec["status"] == "ok":
                r = rec["roofline"]
                slog.log(
                    "dryrun.cell",
                    f"{arch:26s} {shape:12s} OK  compile={rec['compile_s']:.1f}s "
                    f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                    f"coll={r['collective_s']:.4f}s dom={r['dominant']}",
                    arch=arch, shape=shape, status="ok",
                    compile_s=rec["compile_s"], roofline=r,
                    memory=rec.get("memory"))
            else:
                slog.log("dryrun.cell",
                         f"{arch:26s} {shape:12s} SKIP ({rec['reason'][:60]})",
                         arch=arch, shape=shape, status="skipped",
                         reason=rec["reason"])
        except Exception as e:  # noqa: BLE001 — report and continue the sweep
            slog.log("dryrun.cell",
                     f"{arch:26s} {shape:12s} FAIL {type(e).__name__}: {e}",
                     arch=arch, shape=shape, status="fail",
                     error=f"{type(e).__name__}: {e}")
        sys.stdout.flush()
    if sink is not None:
        sink.close()


if __name__ == "__main__":
    main()
