"""Multi-tier checkpointing (PR 9): the file-backed disk spill tier, the
dolfin-adjoint ``snaps_in_ram`` RAM/disk slot split, truly-async segment
prefetch, the segment-flushed adaptive forward sweep, and the planner's
RAM/disk budget split.

The load-bearing assertions are *bitwise*: every new storage medium and
every async path must reproduce the device-tier gradient exactly — the
paper's reproducibility contract is tier-invariant."""
import glob
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adjoint import odeint
from repro.core.implicit import odeint_implicit
from repro.ft import FaultPlan, FaultSpec
from repro.mem.model import policy_cost, slot_bytes
from repro.mem.offload import (_DISK_PREFIX, make_store, reset_spill_stats,
                               spill_stats)
from repro.mem.planner import plan_odeint

jax.config.update("jax_enable_x64", True)

D = 3
U0 = jnp.array([0.1, -0.4, 0.9])
TH = jnp.linspace(0.5, 1.5, D)
N_STEPS = 21


def _f(u, th, t):
    return jnp.sin(u) * th + 0.1 * jnp.cos(t)


def _grad(**kw):
    def loss(th):
        uf = odeint(_f, U0, th, dt=0.02, n_steps=N_STEPS, **kw)
        return jnp.sum(uf ** 2)

    return np.asarray(jax.jit(jax.grad(loss))(TH))


@pytest.fixture(scope="module")
def g_dev():
    return _grad()


# ---------------------------------------------------------------------------
# disk tier + RAM/disk split: bitwise vs the device oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(offload="disk"),
    dict(offload="disk", offload_segment=8),
    dict(offload="spill", snaps_in_ram=0),
    dict(offload="spill", snaps_in_ram=3),
    dict(offload="spill", snaps_in_ram=10_000),  # split never triggers
])
def test_disk_and_split_grads_bitwise(kw, g_dev):
    assert np.array_equal(_grad(**kw), g_dev)


@pytest.mark.parametrize("kw", [
    dict(adjoint="revolve", ncheck=5, offload="disk"),
    dict(adjoint="revolve2", ncheck=5, offload="spill", snaps_in_ram=2),
])
def test_revolve_slots_on_disk_bitwise(kw, g_dev):
    assert np.array_equal(_grad(**kw), g_dev)


def test_disk_tier_actually_hits_disk():
    reset_spill_stats()
    _grad(offload="disk")
    st = spill_stats()
    assert st["disk_write_bytes"] > 0
    assert st["disk_read_bytes"] == st["disk_write_bytes"]
    assert st["ram_bytes_peak"] == 0  # nothing RAM-resident on pure disk


def test_split_caps_ram_resident_bytes():
    # slot = the state; 3 slots in RAM, the rest on disk.
    # routing is whole-batch: the segment must fit the RAM cap for any
    # batch to stay resident, so use segment=2 < snaps_in_ram=3
    cap = 3 * slot_bytes("rk4", U0.size * U0.dtype.itemsize)
    reset_spill_stats()
    _grad(offload="spill", snaps_in_ram=3, offload_segment=2)
    st = spill_stats()
    assert 0 < st["ram_bytes_peak"] <= cap
    assert st["disk_write_bytes"] > 0


def test_offload_dir_pins_files_and_sweeps_stale(tmp_path):
    # a dead run's stale segment file must be swept on store init
    stale = tmp_path / (_DISK_PREFIX + "deadbeef.npz")
    stale.write_bytes(b"not a real npz")
    st = make_store("disk", disk_dir=str(tmp_path))
    assert st.swept_files == 1
    assert not stale.exists()

    g = _grad(offload="disk", offload_dir=str(tmp_path))
    assert np.array_equal(g, _grad())
    # the run's own files are cleaned up with the store; the caller-owned
    # directory survives
    assert tmp_path.exists()


def test_disk_files_cleaned_up_on_store_gc(tmp_path):
    st = make_store("disk", disk_dir=str(tmp_path))
    tok = st.init_token()
    rows = jnp.arange(8.0).reshape(4, 2)
    tok = st.write_batch(tok, 0, rows)
    jax.block_until_ready(tok)
    assert len(glob.glob(str(tmp_path / (_DISK_PREFIX + "*.npz")))) == 1
    del st, tok
    import gc
    # the dispatch cache pins the store via its callback closures — the
    # finalize fires once the last reference (cache entry) is gone
    jax.clear_caches()
    gc.collect()
    assert glob.glob(str(tmp_path / (_DISK_PREFIX + "*.npz"))) == []


# ---------------------------------------------------------------------------
# store-level: remainder zero-fill, split census, token ordering
# ---------------------------------------------------------------------------

def test_disk_remainder_segment_zero_fill_roundtrip():
    # 5 slots written, segment reads of 4: the second read's tail (slots
    # 6,7) was never written and must come back zero-filled, not garbage
    st = make_store("disk")
    tok = st.init_token()
    rows = jnp.arange(10.0).reshape(5, 2)
    tok = st.write_batch(tok, 0, rows)
    tok, seg0 = st.prefetch(tok, 0, 4)
    tok, seg1 = st.prefetch(tok, 4, 4)
    jax.block_until_ready((seg0, seg1))
    assert np.array_equal(np.asarray(seg0), np.asarray(rows[:4]))
    assert np.array_equal(np.asarray(seg1[0]), np.asarray(rows[4]))
    assert np.all(np.asarray(seg1[1:]) == 0.0)


def test_split_store_census_routes_overflow_to_disk():
    st = make_store("spill", snaps_in_ram=3)
    tok = st.init_token()
    tok = st.write_batch(tok, 0, jnp.ones((3, 2)))
    tok = st.write_batch(tok, 3, jnp.ones((4, 2)) * 2)
    jax.block_until_ready(tok)
    census = st.slot_census()
    assert census == {"ram": 3, "disk": 4, "disk_files": 1}
    tok, seg = st.prefetch(tok, 3, 4)
    jax.block_until_ready(seg)
    assert np.all(np.asarray(seg) == 2.0)


def test_async_prefetch_token_ordering_snapshot():
    """Regression (satellite): an issued background gather must serve the
    bytes as of ISSUE time — a write that lands between issue and wait
    cannot leak into the already-dispatched read (the token chain orders
    the callbacks; the executor job snapshots under the I/O lock)."""
    st = make_store("spill")
    tok = st.init_token()
    first = jnp.arange(8.0).reshape(4, 2)
    tok = st.write_batch(tok, 0, first)
    tok = st.prefetch_issue(tok, 0, 4)
    # overwrite the same slots AFTER the issue, BEFORE the wait
    tok = st.write_batch(tok, 0, first * 100.0)
    tok, seg = st.prefetch(tok, 0, 4)
    jax.block_until_ready(seg)
    assert np.array_equal(np.asarray(seg), np.asarray(first))
    stats = st.stats
    assert stats["dispatch_cb"] == 1
    assert stats["prefetch_hit_cb"] == 1


def test_reverse_sweep_pipelines_prefetch():
    # the scanned bwd issues segment k-1 while adjointing segment k: with
    # >1 full segment every wait but possibly the first is an async hit
    reset_spill_stats()
    _grad(offload="spill", offload_segment=4)
    st = spill_stats()
    assert st["dispatch_cb"] >= 1
    assert st["prefetch_hit_cb"] == st["dispatch_cb"]
    # dispatches are token-only: data callbacks stay O(N/seg)
    n_segments = -(-N_STEPS // 4)
    assert st["read_cb"] == n_segments


# ---------------------------------------------------------------------------
# disk-tier fault injection: CRC + resilient recompute stays bitwise
# ---------------------------------------------------------------------------

def _imp_grad(plan=None, resilient=False, **kw):
    def loss(th):
        uf = odeint_implicit(_f, U0, th, dt=0.05, n_steps=12, method="cn",
                             adjoint="pnode", offload_segment=4,
                             newton_iters=8, newton_tol=1e-12,
                             fault_plan=plan, resilient=resilient, **kw)
        return jnp.sum(uf ** 2)

    return np.asarray(jax.jit(jax.grad(loss))(jnp.asarray(0.7)))


def test_disk_corruption_resilient_recompute_bitwise():
    clean = _imp_grad(offload="disk")
    plan = FaultPlan([FaultSpec("spill.write", 1, "corrupt")])
    reset_spill_stats()
    g = _imp_grad(offload="disk", plan=plan, resilient=True)
    assert np.array_equal(g, clean)
    assert spill_stats()["integrity_fail"] >= 1
    assert plan.fired_count("spill.write") == 1


def test_split_tier_corruption_resilient_bitwise():
    clean = _imp_grad(offload="spill")
    plan = FaultPlan([FaultSpec("spill.write", 2, "corrupt")])
    g = _imp_grad(offload="spill", snaps_in_ram=1, plan=plan,
                  resilient=True)
    assert np.array_equal(g, clean)


# ---------------------------------------------------------------------------
# cost model + planner: the RAM/disk split is solved, priced, explained
# ---------------------------------------------------------------------------

def test_cost_model_prices_the_split():
    sb = slot_bytes("rk4", 100)
    full = policy_cost("pnode", method="rk4", n_steps=64, state_bytes=100,
                       offload="spill")
    assert full.ram_bytes == full.ckpt_bytes and full.disk_bytes == 0
    split = policy_cost("pnode", method="rk4", n_steps=64, state_bytes=100,
                        offload="spill", snaps_in_ram=10)
    assert split.ram_bytes == 10 * sb
    assert split.disk_bytes == split.ckpt_bytes - 10 * sb
    disk = policy_cost("pnode", method="rk4", n_steps=64, state_bytes=100,
                       offload="disk")
    assert disk.ram_bytes == 0 and disk.disk_bytes == disk.ckpt_bytes
    # disk bandwidth < RAM bandwidth: all-disk must price slower than
    # all-RAM at equal bytes
    assert disk.io_seconds > full.io_seconds
    # offloaded peaks exclude the checkpoint set regardless of medium
    assert disk.peak_bytes == disk.work_bytes


def test_planner_solves_snaps_split_under_ram_budget():
    sb = slot_bytes("rk4", U0.size * 8)
    p = plan_odeint(_f, U0, TH, dt=0.02, n_steps=64, ram_budget=10 * sb,
                    verify="model", explain=True)
    assert (p.policy, p.offload) == ("pnode", "spill")
    assert p.snaps_in_ram == 10 and p.snaps_on_disk == 54
    assert p.fits
    assert p.report[-1].snaps_in_ram == 10

    # zero-slot RAM budget degenerates to the pure disk tier
    p0 = plan_odeint(_f, U0, TH, dt=0.02, n_steps=64, ram_budget=sb - 1,
                     verify="model")
    assert p0.offload == "disk" and p0.snaps_in_ram is None
    assert p0.snaps_on_disk == 64

    # overflow beyond the disk budget is flagged, not hidden
    pbad = plan_odeint(_f, U0, TH, dt=0.02, n_steps=64, ram_budget=10 * sb,
                       disk_budget=sb, verify="model")
    assert not pbad.fits


def test_auto_with_ram_budget_end_to_end_bitwise(g_dev):
    sb = slot_bytes("rk4", U0.size * 8)
    g = _grad(adjoint="auto", ram_budget=4 * sb, mem_verify="model")
    assert np.array_equal(g, g_dev)
    g0 = _grad(adjoint="auto", ram_budget=0, mem_verify="model")
    assert np.array_equal(g0, g_dev)


def test_budget_knobs_require_auto():
    with pytest.raises(ValueError, match="ram_budget"):
        _grad(adjoint="pnode", ram_budget=1 << 20)
    with pytest.raises(ValueError, match="snaps_in_ram"):
        _grad(offload="host", snaps_in_ram=2)
    with pytest.raises(ValueError, match="offload_dir"):
        _grad(offload="host", offload_dir="/tmp/x")


# ---------------------------------------------------------------------------
# adaptive forward staging ring: O(N/seg) callbacks, tiers bitwise
# ---------------------------------------------------------------------------

def test_adaptive_disk_and_split_bitwise():
    from repro.core.adaptive import odeint_adaptive

    def loss(th, **kw):
        uf, _ = odeint_adaptive(_f, U0, th, t0=0.0, t1=1.0, rtol=1e-8,
                                atol=1e-8, max_steps=128, **kw)
        return jnp.sum(uf ** 2)

    g_dev = np.asarray(jax.jit(jax.grad(loss))(TH))
    for kw in (dict(offload="disk"), dict(offload="spill", snaps_in_ram=2),
               dict(offload="disk", offload_segment=5)):
        g = np.asarray(
            jax.jit(jax.grad(lambda t, kw=kw: loss(t, **kw)))(TH))
        assert np.array_equal(g, g_dev), kw


# ---------------------------------------------------------------------------
# launch drift guard (satellite): zero/absent prediction -> drift=null
# ---------------------------------------------------------------------------

def test_train_peak_drift_guard_zero_prediction(tmp_path):
    from repro.configs.base import ShapeCell, reduced
    from repro.configs.registry import get_arch
    from repro.launch.train import train
    from repro.obs.sink import MetricsSink, read_jsonl

    cfg = reduced(get_arch("smollm-135m"), n_layers=2)
    cell = ShapeCell("t", 32, 2, "train")
    path = tmp_path / "metrics.jsonl"
    with MetricsSink(str(path)) as sink:
        # predicted_peak_bytes=0 (planner skipped / dryrun): must not
        # divide by zero — the compile record still lands, drift=null
        train(cfg, cell, steps=2, sink=sink, predicted_peak_bytes=0,
              log_fn=lambda *_: None)
    recs = [r for r in read_jsonl(str(path)) if r["event"] == "train.compile"]
    assert len(recs) == 1
    assert recs[0]["drift"] is None
