"""Serving driver: a thin front over ``repro.serve.LMEngine``.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b \
      --batch 4 --prompt-len 64 --gen 32 --replicas 2

The engine owns admission, wave scheduling, prefill/decode interleaving
and the per-call timing log; this driver builds synthetic prompts,
submits them, and turns the engine's ``call_log`` into the ``serve.done``
record.  Accounting (fixed here, previously wrong in two ways): the first
sampled token — produced by prefill — counts toward throughput, and the
first decode call's compile time is reported as *warm-up* instead of
being lumped into the steady-state rate:

  ``warmup_s``          prefill wall + the first (compiling) decode call
  ``steady_s``          every later decode call
  ``tok_per_s_steady``  tokens emitted by post-warm-up decode calls / steady_s
  ``tok_per_s``         ALL tokens (batch * gen, first token included) over
                        the end-to-end wall — the honest user-facing rate

``--replicas N`` runs N model replicas (one ``LMEngine`` each, lanes
split across them, decode state sharded per ``repro.dist``
decode-state specs) and aggregates their stats.

Reduced configs on host devices by default (CPU-runnable); the full-config
production path is exercised shape-only by launch/dryrun.py decode cells.
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeCell, reduced
from repro.configs.registry import get_arch
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import enable_compile_cache, make_host_mesh
from repro.obs import MetricsSink, StructuredLogger
from repro.serve import LMEngine


def _stats_from_log(call_log, tokens_total: int) -> dict:
    """Warm-up / steady-state split of an engine ``call_log``."""
    prefill_s = sum(c["wall_s"] for c in call_log if c["op"] == "prefill")
    decode = [c for c in call_log if c["op"] == "decode"]
    decode_s = sum(c["wall_s"] for c in decode)
    warm = [c for c in decode if c.get("compile")]
    steady = [c for c in decode if not c.get("compile")]
    warmup_s = prefill_s + sum(c["wall_s"] for c in warm)
    steady_s = sum(c["wall_s"] for c in steady)
    steady_tok = sum(c["tokens"] for c in steady)
    total_s = prefill_s + decode_s
    return {
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "warmup_s": warmup_s,
        "steady_s": steady_s,
        "tokens": tokens_total,
        "tok_per_s": tokens_total / max(total_s, 1e-9),
        "tok_per_s_steady": steady_tok / max(steady_s, 1e-9),
    }


def serve(cfg, *, batch: int, prompt_len: int, gen: int, mesh=None,
          temperature: float = 0.0, seed: int = 0, log_fn=print,
          sink: MetricsSink | None = None, replicas: int = 1,
          decode_slice: int = 8):
    """Prefill + greedy/temperature decode through the serve engine.
    Returns (tokens ``(batch, gen)``, stats).

    ``sink`` receives a structured ``serve.done`` record (warm-up and
    steady-state split out — see module docstring) alongside the human
    line through ``log_fn``."""
    replicas = max(1, int(replicas))
    if batch % replicas != 0:
        raise ValueError(f"batch {batch} must divide evenly over "
                         f"{replicas} replicas")
    lanes = batch // replicas
    mesh = mesh or make_host_mesh()
    cell = ShapeCell("serve", prompt_len, batch, "prefill")
    pipe = SyntheticLM(cfg, cell, seed=seed)
    prompt = {k: np.asarray(v) for k, v in
              pipe.batch(jnp.zeros((), jnp.int32)).items()
              if k != "targets"}
    extras_keys = [k for k in prompt if k != "tokens"]

    engines = [LMEngine(cfg, lanes=lanes, prompt_len=prompt_len,
                        max_gen=gen, decode_slice=decode_slice,
                        temperature=temperature, seed=seed, mesh=mesh,
                        shard=replicas > 1)
               for _ in range(replicas)]
    tickets = []
    for b in range(batch):
        eng = engines[b % replicas]
        extras = {k: prompt[k][b] for k in extras_keys}
        tickets.append(eng.submit(prompt["tokens"][b], gen=gen,
                                  extras=extras or None))
    for eng in engines:
        eng.run()
    tokens = jnp.asarray(np.stack([t.result(60.0) for t in tickets]))

    merged = [c for eng in engines for c in eng.call_log]
    stats = _stats_from_log(merged, tokens_total=batch * gen)
    stats["replicas"] = replicas
    StructuredLogger(log_fn=log_fn, sink=sink).log(
        "serve.done",
        f"[serve] warm-up {stats['warmup_s']*1e3:.0f} ms, "
        f"steady {stats['tok_per_s_steady']:.1f} tok/s "
        f"({stats['tok_per_s']:.1f} end-to-end)",
        batch=batch, prompt_len=prompt_len, gen=gen, **stats)
    return tokens, stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="model replicas (lanes split across them; decode "
                         "state sharded per repro.dist specs)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write structured serve stats as JSONL to PATH")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced(get_arch(args.arch))
    sink = MetricsSink(args.metrics) if args.metrics else None
    tokens, stats = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                          gen=args.gen, temperature=args.temperature,
                          replicas=args.replicas, sink=sink)
    print(f"[serve] generated {tokens.shape} tokens; stats={stats}")
    if sink is not None:
        sink.close()


if __name__ == "__main__":
    main()
