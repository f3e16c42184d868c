"""The segmented sweep's store-call order (``repro.core.sweeps``), for both
steppers: the forward writes one segment per ``write_batch``; the reverse
waits on the trailing partial segment first, issues each earlier segment's
background gather right after the wait before it, and warms the pipeline
with an issue of its own only when there is no partial segment.  A
recording store stands in for the spill store, so the log is the order in
which the token chain ran the callbacks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import tree_util as jtu

import repro.mem.offload as offload_mod
from repro.core.adjoint import odeint
from repro.core.implicit import odeint_implicit

_TOK = jax.ShapeDtypeStruct((), jnp.float32)


class _RecordingStore:
    """Segment-batched store that keeps rows in a dict and logs every
    callback as ``(kind, base)``."""

    tier = "spill"

    def __init__(self):
        self.log, self.rows, self._obs = [], {}, None

    def bind_obs(self, recorder):
        self._obs = recorder

    def init_token(self):
        return jnp.zeros((), jnp.float32)

    def write_batch(self, token, base, tree):
        leaves, self._treedef = jtu.tree_flatten(tree)
        self._sds = [jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
                     for x in leaves]

        def cb(tok, b, *xs):
            self.log.append(("write", int(b)))
            for i in range(xs[0].shape[0]):
                self.rows[int(b) + i] = [np.asarray(x[i]) for x in xs]
            return np.zeros((), np.float32)

        return jax.pure_callback(cb, _TOK, token, base, *leaves)

    def prefetch_issue(self, token, base, seg):
        def cb(tok, b):
            self.log.append(("issue", int(b)))
            return np.zeros((), np.float32)

        return jax.pure_callback(cb, _TOK, token, base)

    def prefetch(self, token, base, seg):
        def cb(tok, b):
            self.log.append(("wait", int(b)))
            rows = [self.rows[int(b) + i] for i in range(seg)]
            return (np.zeros((), np.float32),) + tuple(
                np.stack([r[j] for r in rows]) for j in range(len(self._sds)))

        out = jax.pure_callback(
            cb, (_TOK,) + tuple(jax.ShapeDtypeStruct((seg,) + s.shape,
                                                     s.dtype)
                                for s in self._sds), token, base)
        return out[0], jtu.tree_unflatten(self._treedef, out[1:])


def _f(u, th, t):
    return jnp.tanh(th * u) - 0.5 * u


def _solve(stepper, **kw):
    u0 = jnp.linspace(0.1, 0.6, 4)
    if stepper == "rk":
        return lambda th: jnp.sum(odeint(_f, u0, th, dt=0.05, **kw) ** 2)
    return lambda th: jnp.sum(odeint_implicit(
        _f, u0, th, dt=0.05, method="cn", newton_tol=1e-5, **kw) ** 2)


@pytest.mark.parametrize("stepper", ["rk", "theta"])
@pytest.mark.parametrize("n_steps,segment", [(8, 3), (9, 3)],
                         ids=["remainder", "no_remainder"])
def test_segmented_store_call_order(monkeypatch, stepper, n_steps, segment):
    store = _RecordingStore()
    monkeypatch.setattr(offload_mod, "make_store", lambda *a, **k: store)
    th = jnp.float32(0.8)
    g = jax.jit(jax.grad(_solve(stepper, n_steps=n_steps, offload="spill",
                                offload_segment=segment)))(th)
    g_dense = jax.jit(jax.grad(_solve(stepper, n_steps=n_steps)))(th)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_dense))

    writes = [b for kind, b in store.log if kind == "write"]
    assert writes == list(range(0, n_steps, segment))
    rev = [e for e in store.log if e[0] != "write"]
    assert store.log == [("write", b) for b in writes] + rev
    n_full, rem = divmod(n_steps, segment)
    # waits: the trailing partial segment first, then the full ones down
    assert [b for kind, b in rev if kind == "wait"] == (
        [n_full * segment] if rem else []) + [
            s * segment for s in reversed(range(n_full))]
    # the warm-up issue comes only when there is no partial segment
    assert (rev[0] == ("issue", (n_full - 1) * segment)) == (rem == 0)
    assert rev[0][0] == ("wait" if rem else "issue")
    # every other issue directly follows the wait of the segment after it
    for k, (kind, b) in enumerate(rev[1:], start=1):
        if kind == "issue":
            assert rev[k - 1] == ("wait", b + segment)
    # each segment is issued once and waited on once, issue first
    for b in range(0, n_steps - rem, segment):
        assert rev.index(("issue", b)) < rev.index(("wait", b))
    assert rev[-1] == ("wait", 0)
