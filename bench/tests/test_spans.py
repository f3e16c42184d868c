"""The readers of the program's own marks (``vf_ms``, ``ckpt_callbacks``,
``ckpt_transit_ms``): exact numbers on a hand-made trace, and on the
trimmed spill trace recorded on a TPU v5e (``data/``)."""
import gzip
import json
import types

import pytest

from bench.lib.spec import metric_reader
from bench.lib.trace import Trace
from bench.tests.test_trace import DATA, _host, _meta, _op

FWD = "jit(step)/jvp(obs:adjoint/fwd)/while/body/closed_call/"
BWD = "jit(step)/transpose(jvp(obs:adjoint/bwd))/while/body/closed_call/"


def hand_made():
    return [
        _meta(3, "/device:TPU:0"), _meta(3, None, 3, "XLA Ops"),
        _meta(7, "/host:CPU"), _meta(7, None, 1, "main"),
        _host(0, 1000, "bench/window"),
        # the field: 0..50 (clipped), 100..200, 300..420 -> 270 us
        _op(-50, 100, "convolution fusion", FWD + "obs:vf/conv"),
        _op(100, 320, "while", FWD + "obs:vf/while"),     # container
        _op(100, 100, "convolution fusion", FWD + "obs:vf/conv"),
        _op(200, 50, "loop fusion", FWD + "add"),         # stage combination
        _op(300, 50, "convolution fusion", BWD + "jvp(obs:vf)/conv"),
        _op(340, 80, "convolution fusion",
            BWD + "transpose(jvp(obs:vf))/conv"),
        _op(450, 50, "loop fusion", BWD + "obs:vfx/mul"),  # another scope
        # device waits: 500..800 and 950..1000 (clipped) -> 350 us
        _op(500, 200, "host recv-done", BWD + "recv-done"),
        _op(650, 150, "copy-done", BWD + "copy-done"),
        _op(950, 150, "host recv-done", BWD + "recv-done"),
        # store spans over the waits: 550..650 and 990..1000 -> 110 us
        _host(550, 50, "obs:spill/write_batch"),
        _host(580, 70, "obs:spill/prefetch"),
        _host(600, 20, "obs:spill/prefetch/gather"),  # child: not a callback
        _host(-20, 40, "obs:spill/dispatch"),         # starts before the window
        _host(990, 30, "obs:spill/free"),
        _host(700, 60, "bench/dispatch"),
    ]


def _read(metric, events, steps=2):
    ctx = types.SimpleNamespace(trace=Trace(events), steps=steps)
    return metric_reader(metric).read(ctx)


def test_vf_ms_hand_made():
    assert _read("vf_ms.dev", hand_made()) == pytest.approx(270e-3 / 2)


def test_ckpt_callbacks_hand_made():
    # write_batch, prefetch and free start inside the window
    assert _read("ckpt_callbacks.off", hand_made()) == 1.5


def test_ckpt_transit_ms_hand_made():
    assert _read("ckpt_transit_ms.off", hand_made()) == pytest.approx(
        (350 - 110) * 1e-3 / 2)


@pytest.mark.parametrize("metric", ["vf_ms.off", "ckpt_callbacks.off",
                                    "ckpt_transit_ms.off"])
def test_nothing_to_read_without_the_marks(metric):
    events = [e for e in hand_made()
              if "obs:vf" not in e.get("args", {}).get("tf_op", "")
              and not e["name"].startswith("obs:spill/")]
    assert _read(metric, events) is None


def test_recorded_v5e_spill():
    """One step of the older classifier, rk4 N_t=8, segment 3: each slot is
    above the callback payload cap, so 8 write_batch, 8 prefetch and 2
    dispatch callbacks.  Of 4,146.86 ms of device waits, 2,159.71 ms lie
    under the store's spans and 1,987.16 ms under none (a 1-us grid over
    the same intervals gives 4,146.863 / 2,159.708 / 1,987.155)."""
    with gzip.open(DATA / "v5e_spill.json.gz", "rt") as fh:
        events = json.load(fh)["traceEvents"]
    assert _read("ckpt_callbacks.off", events, steps=1) == 18.0
    transit = _read("ckpt_transit_ms.off", events, steps=1)
    assert transit == pytest.approx(1987.1556, abs=1e-3)
    host = _read("ckpt_host_ms.off", events, steps=1)
    assert transit + host <= 1e3 * Trace(events).window_s()
    assert _read("vf_ms.off", events, steps=1) is None  # recorded before obs:vf
