"""PR-7 observability tests.

The load-bearing property: attaching a FlightRecorder (``obs=``) must be a
pure debug effect — gradients bitwise-identical to the unobserved solve —
across adjoint policy x offload tier x (eager|jit), for the explicit
tableau family and both implicit theta-methods.  Plus: the adaptive trace
reconstructs the exact accepted/rejected sequence, spill traffic is
attributed per store and per segment, the planner's explain report is
consistent with candidate_costs, and the JSONL sink round-trips.  The
profiler marks: the ``obs:vf`` and ``obs:cnf/trace`` scopes are op
metadata only, and every spill callback opens one ``obs:spill/*`` span on
the profiler's clock.
"""
from __future__ import annotations

import contextlib
import gzip
import json
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.adaptive import odeint_adaptive
from repro.core import cnf, integrators
from repro.core.adjoint import odeint
from repro.core.implicit import odeint_implicit
from repro.mem import offload
from repro.mem.planner import candidate_costs, plan_odeint
from repro.models.ode_nets import cnf_vf, cnf_vf_init
from repro.obs import (FevalCounter, FlightRecorder, Gate, JitCounter,
                       MetricsRegistry, MetricsSink, StructuredLogger,
                       check_against_baseline, read_jsonl)

D = 3


def _vf(u, theta, t):
    return jnp.tanh(u * theta["a"]) + theta["b"] * jnp.sin(t)


def _problem():
    u0 = jnp.array([0.3, -0.7, 1.1])
    theta = {"a": jnp.array([0.5, 1.0, -0.4]), "b": jnp.array(0.2)}
    return u0, theta


def _bitwise(a, b) -> bool:
    return all(bool((x == y).all()) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------------------
# bitwise neutrality: obs on == obs off, policy x tier x (eager|jit)
# ---------------------------------------------------------------------------

EXPLICIT_METHODS = ("euler", "midpoint", "bosh3", "rk4", "dopri5")


@pytest.mark.parametrize("method", EXPLICIT_METHODS)
@pytest.mark.parametrize("policy,tier", [
    ("pnode", None), ("pnode", "spill"),
    ("revolve", None), ("revolve", "spill"),
    ("revolve2", None), ("revolve2", "spill"),
])
def test_obs_bitwise_explicit_jit(method, policy, tier):
    u0, theta = _problem()
    kw = dict(dt=0.1, n_steps=6, method=method, adjoint=policy,
              offload=tier)
    if policy.startswith("revolve"):
        kw["ncheck"] = 2

    def loss(th, obs=None):
        return jnp.sum(odeint(_vf, u0, th, obs=obs, **kw) ** 2)

    g_off = jax.jit(jax.grad(loss))(theta)
    rec = FlightRecorder()
    g_on = jax.jit(lambda th: jax.grad(lambda t: loss(t, obs=rec))(th))(theta)
    assert _bitwise(g_off, g_on)
    assert len(rec) > 0  # the recorder actually saw the solve


@pytest.mark.parametrize("policy", ["pnode", "revolve"])
def test_obs_bitwise_explicit_eager(policy):
    u0, theta = _problem()
    kw = dict(dt=0.1, n_steps=6, method="rk4", adjoint=policy)
    if policy == "revolve":
        kw["ncheck"] = 2

    def loss(th, obs=None):
        return jnp.sum(odeint(_vf, u0, th, obs=obs, **kw) ** 2)

    g_off = jax.grad(loss)(theta)
    rec = FlightRecorder()
    g_on = jax.grad(lambda t: loss(t, obs=rec))(theta)
    assert _bitwise(g_off, g_on)


@pytest.mark.parametrize("method", ["cn", "beuler"])
@pytest.mark.parametrize("policy,tier", [
    ("pnode", None), ("pnode", "spill"),
    ("revolve", None), ("revolve", "spill"),
    ("revolve2", None),
])
def test_obs_bitwise_implicit_jit(method, policy, tier):
    u0, theta = _problem()
    kw = dict(dt=0.05, n_steps=5, method=method, adjoint=policy,
              offload=tier, newton_iters=6, gmres_iters=8)
    if policy.startswith("revolve"):
        kw["ncheck"] = 2

    def loss(th, obs=None):
        return jnp.sum(odeint_implicit(_vf, u0, th, obs=obs, **kw) ** 2)

    g_off = jax.jit(jax.grad(loss))(theta)
    rec = FlightRecorder()
    g_on = jax.jit(lambda th: jax.grad(lambda t: loss(t, obs=rec))(th))(theta)
    assert _bitwise(g_off, g_on)
    # the stacked forward taps expand to exactly one record per step
    steps = rec.implicit_steps()
    assert [d["step"] for d in steps] == list(range(kw["n_steps"]))
    assert all(isinstance(d["iters"], int) for d in steps)


def test_obs_bitwise_adaptive_jit():
    u0, theta = _problem()

    def loss(th, obs=None):
        uf, _ = odeint_adaptive(_vf, u0, th, t0=0.0, t1=0.5, max_steps=64,
                                obs=obs)
        return jnp.sum(uf ** 2)

    g_off = jax.jit(jax.grad(loss))(theta)
    rec = FlightRecorder()
    g_on = jax.jit(lambda th: jax.grad(lambda t: loss(t, obs=rec))(th))(theta)
    assert _bitwise(g_off, g_on)


# ---------------------------------------------------------------------------
# adaptive trace reconstruction
# ---------------------------------------------------------------------------

def test_adaptive_trace_reconstructs_accept_reject_sequence():
    u0, theta = _problem()
    rec = FlightRecorder()

    def fwd(th):
        return odeint_adaptive(_vf, u0, th, t0=0.0, t1=0.5, max_steps=64,
                               obs=rec)

    _, info = jax.jit(fwd)(theta)
    steps = rec.adaptive_steps()
    # one tap per attempted step, ordered by the attempt counter each tap
    # carried (immune to debug-callback reordering)
    assert [d["attempt"] for d in steps] == list(range(len(steps)))
    acc, rej = rec.accepted_rejected()
    assert acc == int(info.n_accepted)
    assert rej == int(info.n_rejected)
    # accepted attempts advance t monotonically; every error norm on an
    # accepted attempt is <= 1
    accepted = [d for d in steps if d["accept"]]
    ts = [d["t"] for d in accepted]
    assert ts == sorted(ts)
    assert all(d["err_norm"] <= 1.0 for d in accepted)
    assert all(d["err_norm"] > 1.0 for d in steps if not d["accept"])


def test_adaptive_spill_trace_matches_store_counters():
    u0, theta = _problem()
    offload.reset_spill_stats()
    rec = FlightRecorder()

    def loss(th):
        uf, _ = odeint_adaptive(_vf, u0, th, t0=0.0, t1=0.5, max_steps=64,
                                offload="spill", offload_segment=8, obs=rec)
        return jnp.sum(uf ** 2)

    g = jax.jit(jax.grad(loss))
    jax.block_until_ready(g(theta))  # compile + warm
    offload.reset_spill_stats()
    rec.clear()
    jax.block_until_ready(g(theta))
    traffic = rec.spill_traffic()
    per_store = offload.per_store_spill_stats()
    # the flight recorder's per-store view must agree with the host-side
    # counters, event for event
    assert set(traffic) == set(per_store)
    for sid, t in traffic.items():
        for k in ("write_cb", "read_cb", "write_slots", "read_slots",
                  "write_bytes", "read_bytes"):
            assert t[k] == per_store[sid][k], (sid, k)
        # per-segment slots sum to the totals
        assert sum(s["write_slots"] for s in t["segments"].values()) \
            == t["write_slots"]
        assert sum(s["read_slots"] for s in t["segments"].values()) \
            == t["read_slots"]


# ---------------------------------------------------------------------------
# per-store spill counters (satellite: the global-dict fix)
# ---------------------------------------------------------------------------

def test_per_store_counters_and_aggregate_agree():
    offload.reset_spill_stats()
    s1 = offload.SpillStore()
    s2 = offload.SpillStore()
    x = jnp.arange(6.0)

    @jax.jit
    def roundtrip(v):
        t1 = s1.write_batch(s1.init_token(), 0, v.reshape(2, 3))
        t1, y = s1.prefetch(t1, 0, 2)
        t2 = s2.write_batch(s2.init_token(), 0, v.reshape(2, 3))
        return y.sum() + (t1 + t2) * 0.0

    jax.block_until_ready(roundtrip(x))
    agg = offload.spill_stats()
    per = offload.per_store_spill_stats()
    assert s1.store_id in per and s2.store_id in per
    assert per[s1.store_id]["write_cb"] == 1
    assert per[s1.store_id]["read_cb"] == 1
    assert per[s2.store_id]["write_cb"] == 1
    assert per[s2.store_id]["read_cb"] == 0
    for k in offload._STAT_KEYS:
        if k == "ram_bytes_peak":
            # high-water gauge: max-merged into the aggregate, not summed
            assert agg[k] == max(p[k] for p in per.values()), k
        else:
            assert agg[k] == sum(p[k] for p in per.values()), k
    offload.reset_spill_stats()
    assert all(v == 0 for v in offload.spill_stats().values())
    assert offload.per_store_spill_stats() == {}


# ---------------------------------------------------------------------------
# planner explain report
# ---------------------------------------------------------------------------

def test_explain_report_consistent_with_candidate_costs():
    u0 = jnp.ones((16,))
    theta = jnp.ones((4,))

    def f(u, th, t):
        return -u * th.sum() + t

    budget = 10 ** 9
    plan = plan_odeint(f, u0, theta, dt=0.1, n_steps=12, method="rk4",
                       mem_budget=budget, verify="model", explain=True)
    from repro.mem.model import f_activation_bytes, tree_bytes
    cands = candidate_costs(method="rk4", n_steps=12,
                            state_bytes=tree_bytes(u0),
                            theta_bytes=tree_bytes(theta),
                            f_act_bytes=f_activation_bytes(f, u0, theta,
                                                           0.0),
                            mem_budget=budget)
    # report rows mirror Plan.candidates one-to-one, in rank order
    assert len(plan.report) >= len(plan.candidates)
    for row, cand in zip(plan.report, plan.candidates):
        assert row.policy == cand.policy
        assert row.ncheck == cand.ncheck
        assert row.predicted_peak_bytes == int(cand.peak_bytes)
        assert row.extra_fevals == int(cand.extra_fevals)
    assert [c.policy for c in plan.candidates] == [c.policy for c in cands]
    # exactly one chosen row; every other row carries a reason
    chosen = [r for r in plan.report if r.chosen]
    assert len(chosen) == 1
    assert chosen[0].policy == plan.policy
    assert all(r.reason for r in plan.report)
    for r in plan.report:
        if not r.chosen:
            assert r.reason.startswith(("rejected", "skipped"))


def test_explain_report_rejects_every_candidate_under_tiny_budget():
    u0 = jnp.ones((64,))
    theta = jnp.ones(())

    def f(u, th, t):
        return -u * th

    plan = plan_odeint(f, u0, theta, dt=0.1, n_steps=20, method="rk4",
                       mem_budget=64, verify="model", explain=True)
    assert plan.offload == "spill"
    # every in-device candidate must state its rejection reason
    in_device = [r for r in plan.report if r.offload is None]
    assert len(in_device) == len(plan.candidates)
    assert all(not r.chosen and "rejected" in r.reason for r in in_device)
    assert plan.report[-1].offload == "spill" and plan.report[-1].chosen


def test_explain_off_keeps_report_empty():
    u0 = jnp.ones((8,))
    theta = jnp.ones(())

    def f(u, th, t):
        return -u * th

    plan = plan_odeint(f, u0, theta, dt=0.1, n_steps=8, method="rk4",
                       mem_budget=10 ** 9, verify="model")
    assert plan.report == ()


# ---------------------------------------------------------------------------
# JSONL sink round-trip + unified baseline checker
# ---------------------------------------------------------------------------

def test_metrics_sink_roundtrip(tmp_path):
    path = tmp_path / "metrics.jsonl"
    with MetricsSink(str(path)) as sink:
        sink.emit("train.step", step=0, loss=1.5,
                  grad_norm=float(jnp.asarray(2.0)))
        sink.emit("train.step", step=1, loss=1.25, nested={"a": [1, 2]})
    recs = read_jsonl(str(path))
    assert [r["event"] for r in recs] == ["train.step", "train.step"]
    assert [r["seq"] for r in recs] == [0, 1]
    assert recs[0]["loss"] == 1.5 and recs[1]["nested"] == {"a": [1, 2]}
    assert all("ts" in r for r in recs)


def test_flight_recorder_to_jsonl_roundtrip(tmp_path):
    rec = FlightRecorder()
    rec.record("odeint.solve", method="rk4", n_steps=4)
    rec.record("spill.write", _runtime=True, store="spill-0", base=0,
               slots=4, bytes=128)
    path = tmp_path / "trace.jsonl"
    n = rec.to_jsonl(str(path))
    assert n == 2
    back = read_jsonl(str(path))
    assert back[0]["kind"] == "odeint.solve" and not back[0]["runtime"]
    assert back[1]["kind"] == "spill.write" and back[1]["runtime"]
    assert json.dumps(back[1])  # fully JSON-serializable


def test_structured_logger_both_channels(tmp_path):
    lines = []
    path = tmp_path / "log.jsonl"
    with MetricsSink(str(path)) as sink:
        slog = StructuredLogger(log_fn=lines.append, sink=sink)
        slog.log("train.resume", "[train] resumed from step 3", step=3)
        slog.metric("train.step", step=3, loss=0.5)
    assert lines == ["[train] resumed from step 3"]
    recs = read_jsonl(str(path))
    assert recs[0]["event"] == "train.resume" and recs[0]["step"] == 3
    assert recs[1]["event"] == "train.step" and "msg" not in recs[1]


def test_unified_checker_gate_semantics():
    reg = MetricsRegistry()
    record = {"size": 24, "io": {"cb": 6}, "ok": True,
              "fused": {"rk4": {"bit": True}, "euler": {"bit": False}}}
    baseline = {"size": 24, "max_cb": 8}
    gates = [
        Gate("size", "size", "==", None, precondition=True),
        Gate("cb", "io.cb", "<=", None),
        Gate("ok", "ok", "truthy"),
        Gate("fused", "fused.*.bit", "truthy"),
    ]
    from repro.obs import BaselineRef
    gates[0] = Gate("size", "size", "==", BaselineRef("size"),
                    precondition=True)
    gates[1] = Gate("cb", "io.cb", "<=", BaselineRef("max_cb"))
    errs = check_against_baseline(record, gates, baseline, bench="t",
                                  registry=reg)
    # the euler fused gate fails; everything else passes
    assert len(errs) == 1 and "fused.euler.bit" in errs[0]
    counters = reg.snapshot()["counters"]
    assert counters["baseline.t.pass"] == 3
    assert counters["baseline.t.fail"] == 1
    # precondition short-circuit: wrong size returns only that message
    errs2 = check_against_baseline(dict(record, size=99), gates, baseline,
                                   bench="t2", registry=reg)
    assert len(errs2) == 1 and "[size]" in errs2[0]
    assert reg.snapshot()["counters"]["baseline.t2.skipped"] == 1
    # missing baseline file
    errs3 = check_against_baseline(record, gates, "/nonexistent/b.json")
    assert errs3 == ["baseline file missing: /nonexistent/b.json"]


def test_bench_gate_modules_use_unified_checker():
    import benchmarks.hotpath as hp
    import benchmarks.stiff_ensemble as se
    assert all(isinstance(g, Gate) for g in hp.GATES)
    assert all(isinstance(g, Gate) for g in se.GATES)
    # hotpath's FevalCounter is the promoted repro.obs one
    assert hp.FevalCounter is FevalCounter


# ---------------------------------------------------------------------------
# jit-safe counters
# ---------------------------------------------------------------------------

def test_jit_counter_counts_under_jit():
    c = JitCounter()

    @jax.jit
    def f(x):
        return c.tap(x) * 2.0

    jax.block_until_ready(f(jnp.ones(())))
    jax.block_until_ready(f(jnp.ones(())))
    # pure_callback results feed the computation, so block_until_ready
    # guarantees the host taps have run
    assert c.count == 2


def test_feval_counter_wraps_field():
    calls = FevalCounter(_vf)
    u0, theta = _problem()

    @jax.jit
    def solve(th):
        return odeint(calls, u0, th, dt=0.1, n_steps=4, method="euler")

    jax.block_until_ready(solve(theta))
    jax.effects_barrier()
    assert calls.count == 4  # euler: one f eval per step
    calls.reset()
    assert calls.count == 0


# ---------------------------------------------------------------------------
# profiler marks: the vector-field scope and the spill callbacks' spans
# ---------------------------------------------------------------------------

def _vf_grad(policy):
    u0, theta = _problem()
    kw = {"ncheck": 2} if policy == "revolve" else {}

    def loss(th):
        return jnp.sum(odeint(_vf, u0, th, dt=0.1, n_steps=6, method="rk4",
                              adjoint=policy, **kw) ** 2)
    return jax.jit(jax.grad(loss)), theta


@pytest.mark.parametrize("policy", ["pnode", "revolve"])
def test_vf_scope_marks_both_sweeps(policy):
    g, theta = _vf_grad(policy)
    text = g.lower(theta).as_text(debug_info=True)
    assert re.search(r"obs:vf/(tanh|sin|mul)", text)    # forward
    assert "transpose(jvp(obs:vf))" in text              # reverse
    # fwd_sweep_ms/rev_sweep_ms read obs:<name>/fwd|bwd: vf must not match
    assert not re.search(r"obs:vf/(fwd|bwd)", text)
    # on the compiled ops, the field sits inside the policy's sweep scopes
    names = re.findall(r'op_name="([^"]*)"',
                       g.lower(theta).compile().as_text())
    assert any(re.search(r"obs:\w+/fwd.*/obs:vf/", n) for n in names)
    assert any(re.search(r"obs:\w+/bwd.*/transpose\(jvp\(obs:vf\)\)/", n)
               for n in names)


@pytest.mark.parametrize("policy", ["pnode", "revolve"])
def test_vf_scope_is_metadata_only(policy, monkeypatch):
    g, theta = _vf_grad(policy)
    hlo, grad = g.lower(theta).as_text(), g(theta)
    monkeypatch.setattr(integrators, "scope",
                        lambda name: contextlib.nullcontext())
    g0, _ = _vf_grad(policy)
    lowered = g0.lower(theta)
    assert "obs:vf" not in lowered.as_text(debug_info=True)
    assert lowered.as_text() == hlo
    assert _bitwise(g0(theta), grad)


def _cnf_grad(trace):
    theta = cnf_vf_init(jax.random.PRNGKey(0), D, hidden=(8, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, D))
    probe = jax.random.rademacher(jax.random.PRNGKey(2), (5, D), x.dtype)

    def loss(th):
        return -jnp.mean(cnf.cnf_log_prob(
            cnf_vf, x, th, dt=0.25, n_steps=4, method="rk4",
            adjoint="pnode", trace=trace, probe=probe))
    return jax.jit(jax.value_and_grad(loss)), theta


@pytest.mark.parametrize("trace", ["hutchinson", "exact"])
def test_cnf_trace_scope_inside_the_field_in_both_sweeps(trace):
    g, theta = _cnf_grad(trace)
    assert "obs:cnf/trace" in g.lower(theta).as_text(debug_info=True)
    names = re.findall(r'op_name="([^"]*)"',
                       g.lower(theta).compile().as_text())
    # trace_est_ms reads obs:cnf/trace, vf_ms the obs:vf around it
    assert any(re.search(r"obs:\w+/fwd\).*/obs:vf/obs:cnf/trace/", n)
               for n in names)
    assert any(re.search(r"obs:\w+/bwd\).*/transpose\(jvp\(obs:vf\)\)/"
                         r"obs:cnf/trace/", n) for n in names)


@pytest.mark.parametrize("trace", ["hutchinson", "exact"])
def test_cnf_trace_scope_is_metadata_only(trace, monkeypatch):
    g, theta = _cnf_grad(trace)
    hlo, out = g.lower(theta).as_text(), g(theta)
    monkeypatch.setattr(cnf, "scope", lambda name: contextlib.nullcontext())
    g0, _ = _cnf_grad(trace)
    lowered = g0.lower(theta)
    assert "obs:cnf/trace" not in lowered.as_text(debug_info=True)
    assert lowered.as_text() == hlo
    assert _bitwise(g0(theta), out)


SPILL_SPANS = {f"obs:spill/{n}" for n in
               ("write", "write_batch", "read", "prefetch", "dispatch",
                "free")}


@pytest.mark.parametrize("policy,one_slot_per_callback,want", [
    ("pnode", False, 3 + 3 + 2),   # per segment: write, prefetch; dispatch
    ("pnode", True, 8 + 8 + 2),    # the payload cap lets one slot through
    ("revolve", False, None),
])
def test_spill_callbacks_open_one_span_each(policy, one_slot_per_callback,
                                            want, tmp_path, monkeypatch):
    u0, theta = _problem()
    if one_slot_per_callback:   # the largest leaf of a slot: 4 stages x D
        monkeypatch.setattr(offload, "_CB_PAYLOAD_CAP",
                            4 * D * u0.dtype.itemsize)
    kw = ({"ncheck": 2} if policy == "revolve"
          else {"offload_segment": 3})

    def loss(th):
        return jnp.sum(odeint(_vf, u0, th, dt=0.1, n_steps=8, method="rk4",
                              adjoint=policy, offload="spill", **kw) ** 2)

    g = jax.jit(jax.grad(loss))
    jax.block_until_ready(g(theta))  # compile + warm
    before = offload.spill_stats()
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(g(theta))
    after = offload.spill_stats()
    calls = sum(after[k] - before[k]
                for k in ("write_cb", "read_cb", "dispatch_cb", "free_cb"))
    (path,) = tmp_path.glob("**/*.trace.json.gz")
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    spans = [e["name"] for e in events
             if e.get("ph") == "X" and e["name"].startswith("obs:spill/")]
    assert set(spans) <= SPILL_SPANS
    assert len(spans) == calls > 0
    if want is not None:
        assert calls == want
