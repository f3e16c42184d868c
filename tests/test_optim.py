"""AdamW + gradient compression: convergence, clipping, schedule shape,
bf16/int8 wire compression with error feedback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.adamw import AdamW
from repro.optim.compress import (bf16_compress, bf16_decompress,
                                  int8_compress, int8_decompress, int8_init,
                                  wire_bytes)


def test_adamw_converges_quadratic():
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"x": jnp.zeros(3)}
    opt = AdamW(lr=0.1, weight_decay=0.0, warmup_steps=1, total_steps=300,
                min_lr_frac=1.0)
    state = opt.init(params)

    def loss(p):
        return jnp.sum((p["x"] - target) ** 2)

    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state, _ = opt.update(g, state, params)
    np.testing.assert_allclose(np.asarray(params["x"]), np.asarray(target),
                               atol=1e-2)


def test_global_norm_clip():
    opt = AdamW(clip_norm=1.0)
    params = {"x": jnp.zeros(4)}
    state = opt.init(params)
    g = {"x": 1e6 * jnp.ones(4)}
    _, _, metrics = opt.update(g, state, params)
    assert float(metrics["grad_norm"]) > 1e5  # reported pre-clip


def test_schedule_warmup_cosine():
    opt = AdamW(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    lrs = [float(opt.schedule(jnp.int32(s))) for s in range(100)]
    assert lrs[0] < 0.2                       # warmup starts low
    assert abs(max(lrs) - 1.0) < 0.05         # reaches peak
    assert lrs[-1] < 0.2                      # decays to ~min_lr_frac
    assert lrs[-1] > 0.09


def test_moments_stay_fp32_under_bf16_params():
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    opt = AdamW()
    state = opt.init(params)
    assert state.m["w"].dtype == jnp.float32
    g = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    new_params, new_state, _ = opt.update(g, state, params)
    assert new_params["w"].dtype == jnp.bfloat16
    assert new_state.v["w"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_bf16_roundtrip_error_small():
    g = {"a": jax.random.normal(jax.random.PRNGKey(0), (256,))}
    back = bf16_decompress(bf16_compress(g))
    rel = float(jnp.max(jnp.abs(back["a"] - g["a"]))
                / jnp.max(jnp.abs(g["a"])))
    assert rel < 0.01
    assert wire_bytes(g, "bf16") == 256 * 2
    assert wire_bytes(g, "int8") == 256


def test_int8_error_feedback_reduces_bias():
    """With error feedback, the *accumulated* quantized sum tracks the true
    sum far better than independent quantization."""
    key = jax.random.PRNGKey(1)
    grads = [{"g": 0.01 * jax.random.normal(jax.random.fold_in(key, i),
                                            (512,))} for i in range(50)]

    res = int8_init(grads[0])
    acc_ef = jnp.zeros(512)
    acc_naive = jnp.zeros(512)
    acc_true = jnp.zeros(512)
    for g in grads:
        q, res = int8_compress(g, res)
        acc_ef = acc_ef + int8_decompress(q)["g"]
        qn, _ = int8_compress(g, int8_init(g))
        acc_naive = acc_naive + int8_decompress(qn)["g"]
        acc_true = acc_true + g["g"]

    err_ef = float(jnp.linalg.norm(acc_ef - acc_true))
    err_naive = float(jnp.linalg.norm(acc_naive - acc_true))
    assert err_ef < err_naive
    assert err_ef < 0.05 * float(jnp.linalg.norm(acc_true))


def test_int8_quantization_range():
    from repro.optim.compress import int8_dequantize, int8_quantize
    g = jnp.array([-3.0, 0.0, 1.5, 3.0])
    q, s = int8_quantize(g)
    assert q.dtype == jnp.int8
    assert int(q[3]) == 127
    np.testing.assert_allclose(np.asarray(int8_dequantize(q, s)),
                               np.asarray(g), atol=0.05)


def test_compressed_psum_matches_uncompressed():
    """On a size-1 axis, every scheme must be (near-)identity; exercised with
    a real multi-axis psum in the multi-device subprocess test."""
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import compressed_psum

    mesh = jax.make_mesh((1,), ("pod",))
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64,))}

    for scheme in ("none", "bf16", "int8"):
        fn = jax.shard_map(
            lambda gg: compressed_psum(gg, "pod", scheme), mesh=mesh,
            in_specs=(P(),), out_specs=P(), check_vma=False)
        out = fn(g)
        tol = {"none": 1e-7, "bf16": 1e-2, "int8": 3e-2}[scheme]
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(g["w"]), rtol=tol, atol=tol)
