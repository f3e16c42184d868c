"""The harness finds cells, traffic and metric readers by name: a new
traffic file, cell entry and metric file need no edit to the harness."""
import json

from bench.lib import spec


def test_new_cell_and_metric_found_by_name(tiny_checkout):
    bench = tiny_checkout / "bench"
    t = json.loads((bench / "traffic" / "clf-b128-rk4x8-pnode.json")
                   .read_text())
    t["n_steps"] = 3
    (bench / "traffic" / "clf-new-mix.json").write_text(json.dumps(t))
    (bench / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    s = json.loads((tiny_checkout / "BENCHMARK.json").read_text())
    s["workloads"].append({"name": "clf-new", "config":
                           "odenet-mnist", "traffic": "clf-new-mix",
                           "chips": 1, "why": "test"})
    s["end_to_end"][1]["workloads"].append("clf-new")
    s["per_layer"].append({"name": "steps_seen.dev", "unit": "1",
                           "better": "higher", "source": "program_counter",
                           "layer": "whole step", "moves": "step_ms"})
    (tiny_checkout / "BENCHMARK.json").write_text(json.dumps(s))

    loaded = spec.load_benchmark(tiny_checkout)
    cell = spec.workload(loaded, "clf-new")
    assert spec.traffic(cell["traffic"], bench)["n_steps"] == 3
    names = [m["name"] for m in spec.cell_metrics(loaded, "clf-new", True)]
    assert names == ["steps_seen.dev"]
    reader = spec.metric_reader("steps_seen.dev", bench)

    class Ctx:
        steps = 7
    assert reader.read(Ctx) == 7.0

    from bench import run
    out = run.run_cell("clf-new", 5, 0.2, False, root=tiny_checkout,
                       require_tpu=False)
    assert set(out["metrics"]) == {"setup_s", "step_ms"}


def test_every_metric_has_a_reader():
    s = spec.load_benchmark()
    for m in s["end_to_end"] + s["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read), m["name"]


def test_every_cell_reports_setup_another_and_a_layer_metric():
    s = spec.load_benchmark()
    for w in s["workloads"]:
        e2e = {m["name"] for m in spec.cell_metrics(s, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert spec.cell_metrics(s, w["name"], True), w["name"]
        for m in spec.cell_metrics(s, w["name"], True):
            assert m["moves"] in e2e, (w["name"], m["name"])
