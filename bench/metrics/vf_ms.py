"""vf_ms.{dev,off} (ms): device time per optimizer step of the ops traced
under the vector-field scope (``obs:vf``, ``core/integrators.py``): the
field's forward evaluations, its linearisations (``jvp(obs:vf)``) and
their transposes (``transpose(jvp(obs:vf))``), as the union of their
intervals in the window.

Approximate by the fusions XLA makes across the scope's edge: a fusion
takes its root op's name, so an RK stage combination fused into the
field's first op counts here, and a field op fused into a stage
combination does not.  A program without the scope reads nothing."""
SCOPE = r"obs:vf\b"


def read(ctx):
    s = ctx.trace.scope_time_s(SCOPE)
    return 1e3 * s / ctx.steps if s > 0 else None
