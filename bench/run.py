"""Run one benchmark cell once on the accelerator JAX finds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights and a pool of batches on the device from the
seed, and takes the cell's first three optimizer steps through the timed
step (compiling it; these are the steps the reference checks).  The
window then dispatches steps back to back for ``--seconds``, threading
parameters and optimizer state, with at most ``INFLIGHT`` steps queued
ahead of the device.  With ``--trace 1`` a shorter window runs under the
profiler and the per-layer metrics are read from its trace.  After the
window the peak device memory is read, the program's state is freed, and
the plain reference decides ``correct``.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (optimizer steps in the window), ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import spec as speclib  # noqa: E402

CACHE_DIR = ROOT / ".bench_jax_cache"
INFLIGHT = 2
TRACE_SECONDS = 2.0


class Compiles:
    """Counts compilations and persistent-cache hits through
    ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.backend = 0
        self.traced = 0
        self.hits = 0
        self.backend_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend += 1
            self.backend_s += secs
        elif name == "/jax/core/compile/jaxpr_trace_duration":
            self.traced += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return (self.backend, self.traced, self.hits)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_or_exit(chips: int, *, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        log(f"bench: needs a TPU; JAX found {devs[0].platform!r}")
        sys.exit(3)
    if len(devs) < chips:
        log(f"bench: the cell asks for {chips} chips; JAX found {len(devs)}")
        sys.exit(3)
    return devs[:chips]


def enable_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else a fixed directory in the
    checkout; every program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def run_window(step, params, state, pool, seconds: float, annotate=False):
    """Dispatch steps for ``seconds``; returns (params, state, steps,
    window seconds).  At most ``INFLIGHT`` steps wait ahead of the device,
    so the host clock follows the device; the window ends when the last
    step dispatched has finished."""
    import jax
    ann = (jax.profiler.TraceAnnotation if annotate
           else lambda _: contextlib.nullcontext())
    pending = collections.deque()
    n = 0
    t0 = time.perf_counter()
    while True:
        with ann("bench/dispatch"):
            params, state, loss, *_ = step(params, state,
                                           *pool[n % len(pool)])
        n += 1
        pending.append(loss)
        if len(pending) > INFLIGHT:
            with ann("bench/wait"):
                pending.popleft().block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    with ann("bench/wait"):
        jax.block_until_ready((params, state))
    return params, state, n, time.perf_counter() - t0


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, bench: Path | None = None,
             require_tpu: bool = True, trace_dir: str | None = None,
             build=None) -> dict:
    """One run of cell ``name``; returns the result object.  ``build``
    replaces the model file's ``build`` (tests plant faults with it)."""
    import jax

    from bench.lib import compare, train_cell
    from bench.lib.peaks import peak_for
    from bench.lib.seeds import seed_key

    bench = bench or root / "bench"
    spec = speclib.load_benchmark(root)
    cell = speclib.workload(spec, name)
    devices = device_or_exit(cell["chips"], require_tpu=require_tpu)
    dev = devices[0]
    cache = enable_cache() if require_tpu else None
    compiles = Compiles()
    cfg = speclib.config(cell["config"], bench)
    traffic = speclib.traffic(cell["traffic"], bench)
    model = (build or speclib.model_module(cell["config"], bench).build)(
        cfg, traffic)
    peak = peak_for(dev.device_kind) if require_tpu else None

    params, state = model.init(seed_key(seed, train_cell.WEIGHTS))
    pool = model.batches(seed, traffic["pool"])
    params, state, record = train_cell.first_steps(model, params, state, pool)
    jax.block_until_ready((params, state))
    setup_s = time.perf_counter() - T_START
    c_setup = compiles.snapshot()

    traced = None
    if trace:
        from bench.lib import trace as tracelib
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        length = min(seconds, TRACE_SECONDS)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(tdir, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench/window"):
                params, state, steps, window_s = run_window(
                    model.step, params, state, pool, length, annotate=True)
    else:
        params, state, steps, window_s = run_window(
            model.step, params, state, pool, seconds)
    c_window = compiles.snapshot()
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    del params, state, pool

    window_compiles = (c_window[0] - c_setup[0]) + (c_window[1] - c_setup[1])
    log(f"bench: cell={name} seed={seed} setup_s={setup_s!r} "
        f"cold={c_setup[0] > 0} backend_compiles={c_setup[0]} "
        f"backend_compile_s={compiles.backend_s!r} cache_hits={c_setup[2]} "
        f"cache_dir={cache} window_compiles={window_compiles} "
        f"steps={steps} window_s={window_s!r} memory_stats={stats}")

    if trace:
        traced = tracelib.reduce_dir(tdir)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)

    prog = train_cell.finish_record(model, seed, record)
    with jax.default_matmul_precision("highest"):
        ref = train_cell.reference(model, seed)
    values = compare.readings(prog, ref)
    correct, checks = compare.judge(values, traffic["limits"])

    # what a metric reader is given
    ctx = types.SimpleNamespace(
        cell=cell, cfg=cfg, traffic=traffic, steps=steps, window_s=window_s,
        setup_s=setup_s, peak_bytes=peak_bytes,
        flops_per_step=model.flops_per_step, peak=peak, trace=traced)
    metrics = {}
    for m in speclib.cell_metrics(spec, name, trace):
        value = speclib.metric_reader(m["name"], bench).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": steps,
           "failed": 0 if correct else steps, "metrics": metrics,
           "device": device}
    if traced is not None:
        device["busy_s"] = traced.busy_s()
        device["window_s"] = traced.window_s()
        out["breakdown"] = traced.breakdown()
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   trace_dir=args.trace_dir)
    for k, c in out["checks"].items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    log(f"correct: {out['correct']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
