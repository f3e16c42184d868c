"""Within an implicit checkpoint policy the gradients must be
**bitwise-identical across every offload tier** (device / host / spill),
in both eager and jit execution — the store moves checkpoints, never
changes a single arithmetic op."""
import jax
import pytest

from tests.reverse_problem import (IMPLICIT_MATRIX, _assert_bitwise,
                                   _implicit_grads)

jax.config.update("jax_enable_x64", True)


@pytest.mark.parametrize("method", ["cn", "beuler"])
@pytest.mark.parametrize("policy,ncheck,tiers",
                         IMPLICIT_MATRIX,
                         ids=[p for p, _, _ in IMPLICIT_MATRIX])
def test_implicit_bitwise_across_offload_tiers(method, policy, ncheck,
                                               tiers):
    """Within a policy the offload tier must not change one bit of the
    gradient — eager and jit each compared against their own device-tier
    anchor (XLA fusion may round eager and jit differently, but tiers
    within a mode run the identical op sequence)."""
    kw = {"ncheck": ncheck} if ncheck is not None else {}
    for jit in (False, True):
        anchor = _implicit_grads(method, policy, jit=jit, **kw)
        for tier in tiers[1:]:
            g = _implicit_grads(method, policy, jit=jit, offload=tier, **kw)
            _assert_bitwise(g, anchor,
                            f"{method}/{policy}/offload={tier}/jit={jit}")
