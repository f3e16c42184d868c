"""fwd_sweep_ms.{dev,off} (ms): device time per optimizer step of the ops
traced under an adjoint policy's forward-sweep scope (``obs:<policy>/fwd``,
``core/adjoint.py``)."""
SCOPE = r"obs:[A-Za-z0-9_]+/fwd"


def read(ctx):
    s = ctx.trace.scope_time_s(SCOPE)
    return 1e3 * s / ctx.steps if s > 0 else None
