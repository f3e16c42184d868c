"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (VMEM
overflow, misaligned blocks).  The topology is described inside a module
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.  Keep these tests in
this one file so that a single worker loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ops import fused_lincomb


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep the cache out of it; and
    # the LM's kernels run without x64 on the chip, while other test files
    # in the same worker may have turned it on for the whole process
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    with jax.enable_x64(False):
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("shape,n_terms,traced_scale", [
    # the ODE classifier's state at CIFAR-10 shape (batch 128, 32 channels):
    # rk4's four-term combine, fixed step
    ((128, 32, 32, 32), 4, False),
    # twice the channels, three terms, adaptive (traced) step size
    ((128, 32, 32, 64), 3, True),
    # a leaf that fits one block runs gridless on its flat vector
    ((8, 32, 32, 8), 4, False),
])
def test_fused_lincomb_compiles_for_v5e(one_chip, shape, n_terms,
                                        traced_scale):
    weights = [0.5, -0.25, 1 / 3, 2.0][:n_terms]

    def fn(base, h, *terms):
        scale = h if traced_scale else 0.1
        return fused_lincomb(base, terms, weights, scale=scale,
                             interpret=False)

    f32 = jnp.float32
    compiled = _compile(fn, one_chip, (shape, f32), ((), f32),
                        *[(shape, f32)] * n_terms)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_lincomb_compiles_for_v5e_under_x64(one_chip):
    """ODE users often turn x64 on; the kernel's block indices must stay
    int32 for Mosaic all the same."""
    def fn(base, h, *terms):
        return fused_lincomb(base, terms, [0.5, -0.25, 2.0], scale=h,
                             interpret=False)

    shape, f32 = (128, 32, 32, 32), jnp.float32
    with jax.enable_x64(True):
        compiled = _compile(fn, one_chip, (shape, f32), ((), f32),
                            *[(shape, f32)] * 3)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    """SmolLM-135M widths: 9 query heads over 3 kv heads of 64, 2048
    tokens, bf16, 512-wide blocks."""
    bf16 = jnp.bfloat16

    def fn(q, k, v):
        return flash_attention_bhsd(q, k, v, causal=True, block_q=512,
                                    block_k=512, interpret=False)

    compiled = _compile(fn, one_chip, ((8, 9, 2048, 64), bf16),
                        ((8, 3, 2048, 64), bf16), ((8, 3, 2048, 64), bf16))
    assert "tpu_custom_call" in compiled.as_text()
