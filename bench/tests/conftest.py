"""CPU tests of the benchmark at small sizes: ``python -m pytest bench/tests``.

``tiny_checkout`` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
directory and shrinks every configuration and traffic file there, so that
a whole run of a cell fits a test (the harness's look for a chip is
skipped with ``require_tpu=False``)."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

SMALL_CONFIG = {"odenet-mnist": {"channels": 8, "norm_groups": 4,
                                 "image": [12, 12, 1]},
                "cnf-miniboone": {"dim": 6, "hdim_factor": 4}}
SMALL_TRAFFIC = {"batch": 8, "pool": 4}
# cells kept out of BENCHMARK.json that the tests still drive
EXTRA_CELLS = [{"name": "cnf-miniboone-pnode", "config": "cnf-miniboone",
                "traffic": "cnf-b1000-rk4x8-hutch-pnode", "chips": 1,
                "why": "test only"}]


def shrink(root: Path) -> None:
    bench = root / "bench"
    for p in (bench / "configs").glob("*.json"):
        cfg = json.loads(p.read_text())
        cfg.update(SMALL_CONFIG.get(p.stem, {}))
        p.write_text(json.dumps(cfg))
    for p in (bench / "traffic").glob("*.json"):
        t = json.loads(p.read_text())
        t.update(SMALL_TRAFFIC)
        p.write_text(json.dumps(t))


@pytest.fixture
def tiny_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"] += EXTRA_CELLS
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shrink(tmp_path)
    return tmp_path
