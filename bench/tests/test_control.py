"""``correct`` comes out false for the control and for each planted fault:
a whole run of the harness (its look for a chip skipped) with the timed
step replaced by the bfloat16 reference, or broken underneath."""
import pytest

from bench.lib import faults, spec

CELLS = [w for w in spec.load_benchmark()["workloads"]]
BREAKS = {"control": faults.control, **faults.FAULTS}


@pytest.mark.parametrize("brk", sorted(BREAKS))
@pytest.mark.parametrize("cell", [w["name"] for w in CELLS])
def test_broken_step_is_not_correct(tiny_checkout, cell, brk):
    from bench import run
    config = next(w["config"] for w in CELLS if w["name"] == cell)
    build = spec.model_module(config, tiny_checkout / "bench").build
    out = run.run_cell(cell, 2 ** 32 + 5, 0.2, False, root=tiny_checkout,
                       require_tpu=False, build=BREAKS[brk](build))
    assert out["correct"] is False, out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
