"""repro.serve (PR 10): the continuous-batching inference service and the
per-request checkpoint key scheme it rides on.

The load-bearing assertions are *bitwise*: a batched offloaded solve
(vmapped odeint with lane-keyed spill/disk checkpoints) must reproduce
the unbatched per-request loop exactly — across tiers, across the
RAM/disk split, with padding lanes in the batch, and across changing
batch compositions through one compiled program.  Scheduler tests prove
FIFO-with-aging cannot starve a request under sustained high-priority
load, and store tests prove departures free their slots."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adaptive import odeint_adaptive
from repro.core.adjoint import odeint
from repro.core.cnf import change_of_variables, exact_trace_vf
from repro.mem.offload import make_store
from repro.mem.planner import plan_odeint
from repro.models.ode_nets import cnf_vf, cnf_vf_init
from repro.obs import FlightRecorder, MetricsRegistry
from repro.serve import (AdmissionError, BucketSpec, ODEEngine,
                         RequestQueue)

DIM = 3
DT, N_STEPS, SEG = 0.1, 8, 4


@pytest.fixture(scope="module", autouse=True)
def _f32_regime():
    # the serve stack targets the f32 regime; other test modules flip the
    # global x64 flag at import (collection order is alphabetical), so pin
    # it off for this whole module — module fixtures included
    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def theta():
    return cnf_vf_init(jax.random.PRNGKey(0), DIM, hidden=(8, 8))


@pytest.fixture(scope="module")
def xs():
    rng = np.random.default_rng(7)
    return rng.normal(size=(5, DIM)).astype(np.float32)


def _logp_ref(**kw):
    """Unbatched reference density (the engine's change of variables).  Takes
    theta as a traced ARGUMENT like the engine's compiled programs do —
    closing over it would let XLA constant-fold differently and shift the
    last ulp."""
    aug = exact_trace_vf(cnf_vf, DIM)

    def logp(th, x_):
        z, dl = odeint(aug, (x_, jnp.zeros((), x_.dtype)), th,
                       dt=DT, n_steps=N_STEPS, method="rk4",
                       adjoint="pnode", **kw)
        return change_of_variables(z, dl)

    return logp


# -- queue: admission -------------------------------------------------------

def test_admission_rejections():
    reg = MetricsRegistry()
    q = RequestQueue(kinds=("density",), dim=DIM, max_payload_bytes=64,
                     registry=reg)
    with pytest.raises(AdmissionError):
        q.submit("nope", np.zeros(DIM, np.float32))
    with pytest.raises(AdmissionError):
        q.submit("density", np.zeros(DIM + 1, np.float32))  # wrong dim
    with pytest.raises(AdmissionError):
        q.submit("density", np.zeros(100, np.float64))  # over byte cap
    with pytest.raises(AdmissionError):
        q.submit("density", np.array([1.0, np.nan, 0.0], np.float32))
    with pytest.raises(AdmissionError):
        q.submit("density", np.array(["a"] * DIM))  # non-numeric
    assert reg.counter("serve.rejected") == 5
    assert q.depth() == 0
    q.submit("density", np.zeros(DIM, np.float32))
    assert reg.counter("serve.submitted") == 1
    assert q.depth() == 1


# -- queue: scheduling ------------------------------------------------------

def test_fifo_aging_no_starvation():
    """A zero-priority request survives a sustained stream of
    high-priority arrivals: its aging score grows without bound, so it is
    scheduled within (max_priority/aging)+1 ticks."""
    q = RequestQueue(kinds=("k",), dim=1, aging=1.0)
    victim = None
    victim_tk = q.submit("k", np.zeros(1, np.float32), rid="victim")
    served = []
    for i in range(20):
        q.submit("k", np.zeros(1, np.float32), priority=5.0, rid=f"vip{i}")
        batch = q.next_batch(1)
        served.extend(r.rid for r, _ in batch)
        if "victim" in served:
            victim = i
            break
    assert victim is not None and victim <= 6, served
    assert not victim_tk.done()  # scheduled, not yet resolved
    # ties broken by arrival order: same-priority requests serve FIFO
    q2 = RequestQueue(kinds=("k",), dim=1, aging=1.0)
    for i in range(4):
        q2.submit("k", np.zeros(1, np.float32), rid=f"r{i}")
    got = [r.rid for r, _ in q2.next_batch(4)]
    assert got == ["r0", "r1", "r2", "r3"]


def test_aging_zero_can_starve():
    """Control: with aging disabled, strict priority DOES starve — the
    aging term is the no-starvation mechanism, not an accident."""
    q = RequestQueue(kinds=("k",), dim=1, aging=0.0)
    q.submit("k", np.zeros(1, np.float32), rid="victim")
    served = []
    for i in range(20):
        q.submit("k", np.zeros(1, np.float32), priority=5.0, rid=f"vip{i}")
        served.extend(r.rid for r, _ in q.next_batch(1))
    assert "victim" not in served


def test_kind_homogeneous_batches():
    q = RequestQueue(kinds=("a", "b"), dim=1, aging=1.0)
    for i in range(3):
        q.submit("a", np.zeros(1, np.float32), rid=f"a{i}")
        q.submit("b", np.zeros(1, np.float32), rid=f"b{i}")
    batch = q.next_batch(8)
    kinds = {r.kind for r, _ in batch}
    assert len(kinds) == 1 and len(batch) == 3


def test_bucket_spec():
    b = BucketSpec((1, 2, 4, 8))
    assert [b.bucket_for(n) for n in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 8]
    assert b.max_size == 8
    with pytest.raises(ValueError):
        BucketSpec((0, 2))


# -- the per-request key scheme: bitwise vs the unbatched loop --------------

@pytest.mark.parametrize("tier_kw", [
    dict(offload="spill"),
    dict(offload="disk"),
    dict(offload="spill", snaps_in_ram=3),
], ids=["spill", "disk", "split"])
def test_engine_bitwise_fixed(theta, xs, tier_kw, tmp_path):
    """Batched (vmapped, lane-keyed, jitted) density and score through the
    engine == the unbatched per-request loop, bit for bit — including the
    padding lanes a non-full bucket adds."""
    eng = ODEEngine(cnf_vf, theta, dim=DIM, dt=DT, n_steps=N_STEPS,
                    offload_segment=SEG, buckets=BucketSpec((4,)),
                    spool_dir=str(tmp_path), **tier_kw)
    t_d = [eng.submit("density", x) for x in xs[:3]]  # 3 lanes + 1 pad
    eng.run()
    t_s = [eng.submit("score", x) for x in xs[:3]]
    eng.run()
    logp = jax.jit(_logp_ref())
    score = jax.jit(jax.grad(_logp_ref(), argnums=1))
    for tk, x in zip(t_d, xs[:3]):
        assert np.array_equal(
            np.asarray(tk.result(5), np.float32),
            np.asarray(logp(theta, jnp.asarray(x)), np.float32))
    for tk, x in zip(t_s, xs[:3]):
        assert np.array_equal(tk.result(5),
                              np.asarray(score(theta, jnp.asarray(x))))
    census = eng.slot_census()
    assert not any(census.values()), census


def test_engine_bitwise_across_compositions(theta, xs):
    """One compiled bucket program serves CHANGING batch compositions:
    lane keys are consulted at callback execution time, so re-keying does
    not retrace and every composition stays bitwise."""
    eng = ODEEngine(cnf_vf, theta, dim=DIM, dt=DT, n_steps=N_STEPS,
                    offload="spill", offload_segment=SEG,
                    buckets=BucketSpec((2,)))
    score = jax.jit(jax.grad(_logp_ref(), argnums=1))
    # three rounds through the same (score, bucket=2) program
    for lo, hi in ((0, 2), (2, 4), (4, 5)):  # last round: 1 lane + pad
        ts = [eng.submit("score", x) for x in xs[lo:hi]]
        eng.run()
        for tk, x in zip(ts, xs[lo:hi]):
            assert np.array_equal(
                tk.result(5), np.asarray(score(theta, jnp.asarray(x))))
    assert len(eng._fns) == 1  # one compiled program served all rounds


def test_engine_bitwise_adaptive(theta, xs):
    """The adaptive per-request loop path: engine results == direct
    odeint_adaptive calls (density and score)."""
    eng = ODEEngine(cnf_vf, theta, dim=DIM, dt=DT, n_steps=N_STEPS,
                    offload="spill", offload_segment=SEG, adaptive=True,
                    max_steps=64)
    aug = exact_trace_vf(cnf_vf, DIM)
    t1 = DT * N_STEPS

    # reference takes theta as a traced ARGUMENT like the engine does —
    # closing over it would let XLA constant-fold differently and shift
    # the last ulp
    def logp(th, x_):
        (z, dl), _ = odeint_adaptive(
            aug, (x_, jnp.zeros((), x_.dtype)), th, t0=0.0, t1=t1,
            rtol=1e-6, atol=1e-6, max_steps=64, offload="spill",
            offload_segment=SEG)
        return change_of_variables(z, dl)

    td = [eng.submit("density", x) for x in xs[:2]]
    ts = [eng.submit("score", x) for x in xs[:2]]
    eng.run()
    for tk, x in zip(td, xs[:2]):
        ref = np.asarray(jax.jit(logp)(theta, jnp.asarray(x)))
        assert np.array_equal(np.asarray(tk.result(5), ref.dtype),
                              np.atleast_1d(ref))
    for tk, x in zip(ts, xs[:2]):
        ref = np.asarray(jax.jit(jax.grad(logp, argnums=1))(
            theta, jnp.asarray(x)))
        assert np.array_equal(tk.result(5), ref)


def test_engine_classify_head(theta, xs):
    """Classifier kind: integrate the raw field, apply the readout; the
    forward-only path writes zero checkpoints.

    The bitwise reference is the *batched no-offload* program: the claim
    under test is that the lane-keyed spill store perturbs nothing, not
    that XLA lowers a batched matmul identically to a row-wise one (with
    the x64 flag on, CPU dot_general for (B,d)@(d,k) can differ from
    (d,)@(d,k) in the last ulp — a lowering artifact independent of this
    subsystem).  The ODE transport itself IS bitwise lane-vs-single,
    asserted separately on uT before the head."""
    W = jnp.asarray(np.random.default_rng(0).normal(size=(DIM, 2)),
                    jnp.float32)
    eng = ODEEngine(cnf_vf, theta, dim=DIM, dt=DT, n_steps=N_STEPS,
                    offload="spill", offload_segment=SEG,
                    head=lambda u: u @ W, buckets=BucketSpec((2,)))

    def uT_one(th, x_):  # theta as a traced argument, like the engine
        return odeint(cnf_vf, x_, th, dt=DT, n_steps=N_STEPS,
                      method="rk4", adjoint="pnode")

    def batched_ref(th, xb):  # same vmap+head shape, no offload store
        return jax.vmap(lambda x_: uT_one(th, x_) @ W)(xb)

    ts = [eng.submit("classify", x) for x in xs[:2]]
    eng.run()
    refb = np.asarray(jax.jit(batched_ref)(theta, jnp.asarray(xs[:2])))
    # offloaded batched logits == no-offload batched logits, bitwise
    for i, tk in enumerate(ts):
        assert np.array_equal(tk.result(5), refb[i])
    # and the transport under the head is bitwise lane-vs-single
    uTb = np.asarray(jax.jit(jax.vmap(uT_one, in_axes=(None, 0)))(
        theta, jnp.asarray(xs[:2])))
    for i in range(2):
        assert np.array_equal(
            uTb[i], np.asarray(jax.jit(uT_one)(theta, jnp.asarray(xs[i]))))
    census = eng.slot_census()
    assert not any(census.values()), census


# -- callback bounds --------------------------------------------------------

def test_callbacks_independent_of_lane_count(theta, xs):
    """The point of lane-keyed batching: host callbacks per SOLVE are
    O(n_steps/segment) regardless of how many requests share the batch —
    so callbacks per REQUEST shrink as occupancy grows."""
    n_seg = math.ceil(N_STEPS / SEG)

    def run(n_req):
        reg = MetricsRegistry()
        eng = ODEEngine(cnf_vf, theta, dim=DIM, dt=DT, n_steps=N_STEPS,
                        offload="spill", offload_segment=SEG,
                        buckets=BucketSpec((4,)), registry=reg)
        eng.warmup(kinds=("score",))
        store = eng._store(4)
        before = dict(store.stats)
        for x in xs[:n_req]:
            eng.submit("score", x)
        eng.run()
        return {k: store.stats[k] - before.get(k, 0)
                for k in ("write_cb", "read_cb", "dispatch_cb")}

    solo = run(1)
    batched = run(4)
    # same per-solve callback structure whether 1 or 4 requests rode it
    assert batched == solo
    assert solo["write_cb"] == n_seg
    assert solo["read_cb"] + solo["dispatch_cb"] <= 2 * (n_seg + 1)
    # per-request cost: 4x cheaper at occupancy 4
    per_req_solo = sum(solo.values()) / 1
    per_req_batched = sum(batched.values()) / 4
    assert per_req_batched == per_req_solo / 4


# -- departures free their slots -------------------------------------------

def test_departure_frees_slots(theta, xs):
    """Run a lane-keyed batched grad holding the store open, then retire
    requests one by one: each departure frees exactly its own slots and
    the census returns to empty."""
    store = make_store("spill")
    aug = exact_trace_vf(cnf_vf, DIM)

    def score_b(xb):
        def one(x_):
            def logp(x__):
                z, dl = odeint(aug, (x__, jnp.zeros((), x__.dtype)), theta,
                               dt=DT, n_steps=N_STEPS, method="rk4",
                               adjoint="pnode", offload="spill",
                               offload_segment=SEG, offload_store=store)
                return change_of_variables(z, dl)
            return jax.grad(logp)(x_)
        return jax.vmap(one)(xb)

    rids = ("req-a", "req-b", None)  # 2 live lanes + 1 padding
    store.lane_keys = rids
    g = jax.block_until_ready(jax.jit(score_b)(jnp.asarray(xs[:3])))
    assert np.all(np.isfinite(np.asarray(g)[:2]))
    census0 = store.slot_census()
    assert census0["ram"] > 0
    assert store.request_slots("req-a") > 0
    assert store.request_slots("req-b") > 0
    n_a = store.free_request("req-a")  # mid-batch departure
    assert n_a > 0
    assert store.request_slots("req-a") == 0
    assert store.request_slots("req-b") > 0  # batch-mate untouched
    store.free_request("req-b")
    census = store.slot_census()
    assert not any(census.values()), census
    # padding lanes never stored anything to begin with
    assert store.free_request(None) == 0


# -- planner: batched working set ------------------------------------------

def test_plan_odeint_batch_pricing():
    u0 = jnp.zeros(DIM)
    th = jnp.zeros(DIM)
    f = lambda u, t_, t: u
    kw = dict(dt=DT, n_steps=N_STEPS, method="rk4", verify="model")
    p1 = plan_odeint(f, u0, th, **kw)
    p8 = plan_odeint(f, u0, th, batch=8, **kw)
    assert p8.predicted.peak_bytes > p1.predicted.peak_bytes
    # ram_budget split: lanes multiply the slot bytes, so the same RAM
    # budget holds ~1/8 the steps in RAM
    ram = None
    p1r = plan_odeint(f, u0, th, ram_budget=N_STEPS * DIM * 4 * 6, **kw)
    p8r = plan_odeint(f, u0, th, ram_budget=N_STEPS * DIM * 4 * 6,
                      batch=8, **kw)
    del ram
    assert p1r.offload in ("spill", "disk")
    assert p8r.offload in ("spill", "disk")
    in_ram_1 = p1r.snaps_in_ram if p1r.snaps_in_ram is not None else N_STEPS
    in_ram_8 = p8r.snaps_in_ram if p8r.snaps_in_ram is not None else N_STEPS
    assert in_ram_8 < in_ram_1
    with pytest.raises(ValueError):
        plan_odeint(f, u0, th, batch=0, **kw)


def test_engine_planner_integration(theta, xs):
    """A budget-configured engine routes through plan_odeint and still
    serves bitwise results."""
    eng = ODEEngine(cnf_vf, theta, dim=DIM, dt=DT, n_steps=N_STEPS,
                    offload_segment=SEG, ram_budget=1,
                    buckets=BucketSpec((2,)))
    assert eng.plan is not None and eng.plan.policy == "pnode"
    assert eng.offload == "disk"  # 1-byte RAM budget: everything to disk
    tk = eng.submit("score", xs[0])
    eng.run()
    ref = jax.jit(jax.grad(_logp_ref(), argnums=1))(
        theta, jnp.asarray(xs[0]))
    assert np.array_equal(tk.result(5), np.asarray(ref))


# -- bounded compile cache --------------------------------------------------

def test_compile_cache_bounded(theta, xs):
    eng = ODEEngine(cnf_vf, theta, dim=DIM, dt=DT, n_steps=N_STEPS,
                    offload="spill", offload_segment=SEG,
                    buckets=BucketSpec((1, 2)))
    n = eng.warmup()
    assert n == len(ODEEngine.KINDS) * 2
    # traffic across many compositions never grows the cache
    for i in range(3):
        eng.submit("density", xs[i % len(xs)])
        eng.run()
    assert len(eng._fns) <= len(ODEEngine.KINDS) * 2


# -- flight recorder ---------------------------------------------------------

def test_engine_records_to_flight_recorder(tmp_path, theta, xs):
    from repro.obs import read_jsonl
    rec = FlightRecorder()
    eng = ODEEngine(cnf_vf, theta, dim=DIM, dt=DT, n_steps=N_STEPS,
                    offload="spill", offload_segment=SEG,
                    buckets=BucketSpec((2,)), obs=rec)
    eng.submit("score", xs[0])
    eng.submit("density", xs[1])
    eng.run()
    evs = rec.events()
    assert any(e.kind.startswith("spill.") for e in evs)
    assert any(e.kind.startswith("queue.") for e in evs)
    assert any(e.kind == "serve.batch" for e in evs)
    assert all(e.ts > 0 for e in evs)  # wall-clock stamped
    p = tmp_path / "events.jsonl"
    assert rec.to_jsonl(str(p)) == len(evs)
    assert [r["kind"] for r in read_jsonl(str(p))] == [e.kind for e in evs]


# -- serve driver accounting (satellite: warm-up vs steady state) -----------

def test_serve_stats_accounting():
    from repro.launch.serve import _stats_from_log
    log = [
        {"op": "prefill", "wall_s": 2.0, "tokens": 4, "compile": True,
         "lanes": 4},
        {"op": "decode", "wall_s": 3.0, "tokens": 8, "steps": 2,
         "compile": True, "lanes": 4},
        {"op": "decode", "wall_s": 0.5, "tokens": 8, "steps": 2,
         "compile": False, "lanes": 4},
        {"op": "decode", "wall_s": 0.5, "tokens": 8, "steps": 2,
         "compile": False, "lanes": 4},
    ]
    s = _stats_from_log(log, tokens_total=4 * 7)
    assert s["prefill_s"] == 2.0
    assert s["decode_s"] == 4.0
    # compile-time decode lumped into warm-up, not steady state
    assert s["warmup_s"] == 5.0
    assert s["steady_s"] == 1.0
    assert s["tok_per_s_steady"] == 16 / 1.0
    # the first (prefill-sampled) token counts in end-to-end throughput
    assert s["tok_per_s"] == pytest.approx(28 / 6.0)
