"""Cell ``cnf-miniboone-pnode`` at a small size on the CPU: the program's
log-density against the reference's change of variables, and the reader
of the trace-estimate scope (``trace_est_ms``) on a hand-made trace."""
import gzip
import json

import pytest

from bench.lib import compare, train_cell
from bench.tests.test_reference import _tiny
from bench.tests.test_spans import BWD, FWD, _read, hand_made
from bench.tests.test_trace import DATA, _host, _meta, _op


def test_cnf_program_matches_the_reference(tiny_checkout):
    """The program's ``cnf_log_prob`` against the reference's change of
    variables, log N(z) + int tr(df/dx): every reading at rounding level.
    The reference with the log-determinant's sign flipped is the negative
    case, which the gradient tells apart."""
    mod, cfg, t = _tiny(tiny_checkout, "cnf-miniboone",
                        "cnf-b1000-rk4x8-hutch-pnode")
    model = mod.build(cfg, t)
    seed = 21
    params, state = model.init(train_cell.seed_key(seed, train_cell.WEIGHTS))
    pool = model.batches(seed, train_cell.FIRST_STEPS)
    _, _, rec = train_cell.first_steps(model, params, state, pool)
    prog = train_cell.finish_record(model, seed, rec)

    right = compare.readings(prog, train_cell.reference(model, seed))
    assert max(right.values()) < 1e-4, right
    flipped = mod.build(cfg, t)
    flipped.ref_loss = lambda p, b, dt, half: mod.ref_loss(
        cfg, t, p, b, dt, half, logdet_sign=-1.0)
    wrong = compare.readings(prog, train_cell.reference(flipped, seed))
    assert wrong["grad_gap"] > 1e-2, wrong


def cnf_hand_made():
    """A CNF step's field with the trace estimate inside it."""
    trace = "obs:vf/obs:cnf/trace/"
    return [
        _meta(3, "/device:TPU:0"), _meta(3, None, 3, "XLA Ops"),
        _meta(7, "/host:CPU"), _meta(7, None, 1, "main"),
        _host(0, 1000, "bench/window"),
        _op(100, 100, "convolution fusion", FWD + "obs:vf/dot_general"),
        # the estimate: 200..260, 400..500 and 480..560 -> 220 us
        _op(200, 60, "convolution fusion", FWD + trace + "dot_general"),
        _op(400, 100, "convolution fusion",
            BWD + "jvp(obs:vf)/obs:cnf/trace/dot_general"),
        _op(480, 80, "loop fusion",
            BWD + "transpose(jvp(obs:vf))/obs:cnf/trace/mul"),
        _op(600, 50, "loop fusion", BWD + "obs:cnf/traces/mul"),  # not it
    ]


def test_trace_est_ms_hand_made():
    events = cnf_hand_made()
    assert _read("trace_est_ms.cnf", events) == pytest.approx(220e-3 / 2)
    assert _read("vf_ms.cnf", events) == pytest.approx(320e-3 / 2)


@pytest.mark.parametrize("events", [
    lambda: [e for e in cnf_hand_made()
             if "obs:cnf/trace/" not in e.get("args", {}).get("tf_op", "")],
    hand_made,
], ids=["scope_removed", "classifier"])
def test_trace_est_ms_nothing_without_the_scope(events):
    assert _read("trace_est_ms.cnf", events()) is None


def test_trace_est_ms_nothing_in_the_recorded_v5e_spill():
    with gzip.open(DATA / "v5e_spill.json.gz", "rt") as fh:
        events = json.load(fh)["traceEvents"]
    assert _read("trace_est_ms.cnf", events, steps=1) is None
