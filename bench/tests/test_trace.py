"""The trace reduction: exact numbers on a hand-made trace, and on a
trimmed trace recorded on a TPU v5e (``data/``)."""
import gzip
import json
from pathlib import Path

import pytest

from bench.lib.trace import Trace

DATA = Path(__file__).parent / "data"


def _meta(pid, name, tid=None, thread=None):
    if tid is None:
        return {"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": name}}
    return {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
            "args": {"name": thread}}


def _op(ts, dur, cat, scope="", hlo="", name="op"):
    return {"ph": "X", "pid": 3, "tid": 3, "ts": ts, "dur": dur,
            "name": name, "args": {"hlo_category": cat, "tf_op": scope,
                                   "long_name": hlo}}


def _host(ts, dur, name):
    return {"ph": "X", "pid": 7, "tid": 1, "ts": ts, "dur": dur,
            "name": name}


def hand_made():
    fwd, bwd = "jit(step)/jvp(obs:adjoint/fwd)/x", \
        "jit(step)/transpose(jvp(obs:adjoint/bwd))/y"
    return [
        _meta(3, "/device:TPU:0"), _meta(3, None, 3, "XLA Ops"),
        _meta(7, "/host:CPU"), _meta(7, None, 1, "main"),
        _host(100, 1000, "bench/window"),
        _op(50, 100, "convolution fusion", fwd),         # 100..150 counts
        _op(200, 300, "while", fwd),                     # container
        _op(200, 100, "loop fusion", fwd, name="f1"),
        _op(250, 100, "loop fusion", fwd, name="f2"),    # overlaps f1
        _op(400, 200, "copy-done", bwd,
            hlo="%copy-done = f32[8]{0:S(5)} copy-done(...)"),
        _op(700, 100, "convolution fusion", bwd),
        _op(1050, 100, "loop fusion", bwd),              # to 1100 counts
        _host(420, 50, "obs:spill/write_batch"),
        _host(450, 100, "obs:spill/prefetch"),           # 450..470 overlap
    ]


def test_hand_made_numbers():
    t = Trace(hand_made())
    assert t.window_s() == pytest.approx(1000e-6)
    # compute: 100..150, 200..350, 700..800, 1050..1100 -> 350 us
    assert t.busy_s() == pytest.approx(350e-6)
    assert t.scope_time_s(r"obs:\w+/fwd") == pytest.approx(200e-6)
    # bwd: copy-done 400..600 (a wait), 700..800, 1050..1100
    assert t.scope_time_s(r"obs:\w+/bwd") == pytest.approx(350e-6)
    assert t.host_copy_time_s() == pytest.approx(200e-6)
    assert t.host_time_s("obs:spill/") == pytest.approx(130e-6)
    b = t.breakdown(top=3)
    assert b["idle_gaps"][0] == ["device wait: copy-done",
                                 pytest.approx(350e-6)]
    assert [g[1] for g in b["idle_gaps"]] == sorted(
        (g[1] for g in b["idle_gaps"]), reverse=True)
    assert b["device_ops"][0][0] == "op"


def test_no_window_annotation_is_an_error():
    ev = [e for e in hand_made() if e.get("name") != "bench/window"]
    with pytest.raises(ValueError):
        Trace(ev)


@pytest.mark.parametrize("name", ["v5e_spill", "v5e_revolve_host"])
def test_recorded_v5e_trace(name):
    """One optimizer step of a cell, recorded on one TPU v5e and trimmed
    (host events other than annotations and most op arguments dropped),
    with the numbers the reducer gave for it when it was trimmed."""
    with gzip.open(DATA / f"{name}.json.gz", "rt") as fh:
        rec = json.load(fh)
    t = Trace(rec["traceEvents"])
    want = rec["expected"]
    assert t.window_s() == pytest.approx(want["window_s"])
    assert t.busy_s() == pytest.approx(want["busy_s"])
    fwd, bwd = t.scope_time_s(r"obs:\w+/fwd"), t.scope_time_s(r"obs:\w+/bwd")
    assert fwd == pytest.approx(want["fwd_s"])
    assert bwd == pytest.approx(want["bwd_s"])
    assert t.host_time_s("obs:spill/") == pytest.approx(want["spill_s"])
    assert t.host_copy_time_s() == pytest.approx(want["host_copy_s"])
    assert 0 < t.busy_s() < t.window_s()
    assert 0 < fwd and 0 < bwd and fwd + bwd <= t.window_s()
    b = t.breakdown()
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
    if name == "v5e_spill":      # the device waits on the spill callbacks
        assert t.busy_s() < 0.05 * t.window_s()
        assert t.host_time_s("obs:spill/") > 0.3 * t.window_s()
        assert b["idle_gaps"][0][0].startswith("device wait")
    else:                        # and on pinned-host copies
        assert t.host_copy_time_s() > 0.5 * t.window_s()
