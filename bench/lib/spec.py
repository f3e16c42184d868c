"""Finds everything a cell needs by the names in ``BENCHMARK.json``:

  bench/configs/<config>.json    the configuration's sizes, as run
  bench/models/<config>.py       builds the timed step, the reference and
                                 the FLOP count (``build(cfg, traffic)``)
  bench/traffic/<traffic>.json   the traffic mix: batch, solver, policy,
                                 tier, pool, and the limits of ``correct``
  bench/metrics/<stem>.py        the reader of every metric whose name
                                 is ``<stem>`` or ``<stem>.<part>``

A later cell, configuration or metric is a new file and a new entry:
nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as fh:
        return json.load(fh)


def _json(kind: str, name: str, bench: Path) -> dict:
    with open(bench / kind / f"{name}.json") as fh:
        return json.load(fh)


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in spec['workloads']]}")


def config(name: str, bench: Path = BENCH) -> dict:
    return _json("configs", name, bench)


def traffic(name: str, bench: Path = BENCH) -> dict:
    return _json("traffic", name, bench)


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(config_name: str, bench: Path = BENCH):
    return _module(bench / "models" / f"{config_name}.py",
                   f"bench_model_{config_name.replace('-', '_')}")


def metric_reader(metric_name: str, bench: Path = BENCH):
    """The module whose ``read(ctx)`` gives ``metric_name``: one reader
    serves every metric named ``<stem>`` or ``<stem>.<part>``."""
    stem = metric_name.split(".")[0]
    return _module(bench / "metrics" / f"{stem}.py",
                   f"bench_metric_{stem}")


def cell_metrics(spec: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``.  A metric
    with ``workloads`` belongs to the cells listed; a per-layer metric
    without it belongs to every cell that reports the metric it moves."""
    def listed(m):
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if listed(m)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]
