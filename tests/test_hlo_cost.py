"""Trip-count-aware HLO cost accounting: equality with cost_analysis() on
loop-free graphs; correct trip multiplication on scanned graphs (where
cost_analysis undercounts); collective accounting inside loops."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze, peak_live_bytes
from tests.util import run_with_devices

D = 128


def _flops_of(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    c = compiled.cost_analysis()
    mine = analyze(compiled.as_text())
    return float(c.get("flops", 0.0)), mine


def test_matches_cost_analysis_loop_free():
    w = jax.random.normal(jax.random.PRNGKey(0), (D, D))
    x = jax.random.normal(jax.random.PRNGKey(1), (D, D))

    def fn(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    xla_flops, mine = _flops_of(fn, x, w)
    assert mine.flops == pytest.approx(4 * 2 * D ** 3, rel=0.01)
    assert mine.flops == pytest.approx(xla_flops, rel=0.05)


def test_scan_trip_count_multiplied():
    w = jax.random.normal(jax.random.PRNGKey(0), (D, D))
    x = jax.random.normal(jax.random.PRNGKey(1), (D, D))

    def fn(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    xla_flops, mine = _flops_of(fn, x, w)
    assert xla_flops == pytest.approx(2 * D ** 3, rel=0.01)  # the known bug
    assert mine.flops == pytest.approx(10 * 2 * D ** 3, rel=0.01)  # fixed


def test_nested_scan():
    w = jax.random.normal(jax.random.PRNGKey(0), (D, D))
    x = jax.random.normal(jax.random.PRNGKey(1), (D, D))

    def fn(x, w):
        def inner(c, _):
            return jnp.tanh(c @ w), None

        def outer(c, _):
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None

        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    _, mine = _flops_of(fn, x, w)
    assert mine.flops == pytest.approx(15 * 2 * D ** 3, rel=0.01)


def test_dot_general_batched():
    a = jax.random.normal(jax.random.PRNGKey(0), (8, 32, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 16))
    _, mine = _flops_of(lambda a, b: jnp.einsum("bij,bjk->bik", a, b), a, b)
    assert mine.flops == pytest.approx(2 * 8 * 32 * 64 * 16, rel=0.01)


def test_bytes_scale_with_trip_count():
    w = jax.random.normal(jax.random.PRNGKey(0), (D, D))
    x = jax.random.normal(jax.random.PRNGKey(1), (D, D))

    def make(n):
        def fn(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, x, None, length=n)
            return out
        return fn

    _, c5 = _flops_of(make(5), x, w)
    _, c10 = _flops_of(make(10), x, w)
    assert c10.bytes == pytest.approx(2 * c5.bytes, rel=0.1)


def test_peak_live_bytes_sees_largest_intermediate():
    """The liveness sweep must at least account for the biggest live value
    and stay within a small factor of XLA's own buffer accounting."""
    w = jax.random.normal(jax.random.PRNGKey(0), (D, D))
    x = jax.random.normal(jax.random.PRNGKey(1), (D, D))

    def fn(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    compiled = jax.jit(fn).lower(x, w).compile()
    peak = peak_live_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    xla = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert peak >= D * D * 4  # one live matrix, at minimum
    assert xla * 0.5 <= peak <= xla * 6, (peak, xla)


def test_peak_live_bytes_sees_scan_stacked_residuals():
    """A scan that stacks residuals must dominate the peak (this is the
    structure of the naive/pnode reverse passes the planner compares)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (D, D))
    x = jax.random.normal(jax.random.PRNGKey(1), (D, D))

    def make(n):
        def fn(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), c
            return jax.lax.scan(body, x, None, length=n)
        return fn

    peaks = []
    for n in (4, 16):
        compiled = jax.jit(make(n)).lower(x, w).compile()
        peaks.append(peak_live_bytes(compiled.as_text()))
        assert peaks[-1] >= n * D * D * 4  # the stacked ys buffer
    assert peaks[1] > 2 * peaks[0]  # grows with trip count


@pytest.mark.slow
def test_collectives_inside_scan_multiplied():
    out = run_with_devices("""
from jax.sharding import PartitionSpec as P
from repro.launch.hlo_cost import analyze
mesh = jax.make_mesh((8,), ("d",))
x = jnp.ones((8, 64), jnp.float32)

def inner(x):
    def body(c, _):
        return jax.lax.psum(c, "d"), None
    out, _ = jax.lax.scan(body, x, None, length=7)
    return out

fn = jax.shard_map(inner, mesh=mesh, in_specs=(P("d"),), out_specs=P("d"),
                   check_vma=False)
compiled = jax.jit(fn).lower(x).compile()
c = analyze(compiled.as_text())
per_step = 1 * 64 * 4   # one (1,64) f32 shard all-reduced per step
total = c.collective_bytes["all-reduce"]
assert abs(total - 7 * per_step) / (7 * per_step) < 0.05, total
print("COLL_OK", total)
""")
    assert "COLL_OK" in out
