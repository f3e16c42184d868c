"""rev_sweep_ms.{dev,off} (ms): device time per optimizer step of the ops
traced under an adjoint policy's reverse-sweep scope (``obs:<policy>/bwd``,
``core/adjoint.py``)."""
SCOPE = r"obs:[A-Za-z0-9_]+/bwd"


def read(ctx):
    s = ctx.trace.scope_time_s(SCOPE)
    return 1e3 * s / ctx.steps if s > 0 else None
