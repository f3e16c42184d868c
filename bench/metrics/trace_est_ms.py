"""trace_est_ms.cnf (ms): device time per optimizer step of the ops traced
under the CNF's trace-estimate scope (``obs:cnf/trace``,
``core/cnf.py``): the Hutchinson probe's vector-Jacobian product and its
sum, in the forward sweep (``obs:vf/obs:cnf/trace``), in the reverse
sweep's linearisation (``jvp(obs:vf)/obs:cnf/trace``) and its transpose
(``transpose(jvp(obs:vf))/obs:cnf/trace``), as the union of their
intervals in the window.  The scope lies inside ``obs:vf``, so the reading
is a part of ``vf_ms``.

Approximate, as ``vf_ms`` is, by the fusions XLA makes across the scope's
edge: a fusion takes its root op's name.  A program without the scope
reads nothing."""
SCOPE = r"obs:cnf/trace\b"


def read(ctx):
    s = ctx.trace.scope_time_s(SCOPE)
    return 1e3 * s / ctx.steps if s > 0 else None
