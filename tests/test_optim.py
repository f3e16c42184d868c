"""AdamW + gradient compression: convergence, clipping, schedule shape,
bf16/int8 wire compression with error feedback."""
import json

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
# packed state layout
# ---------------------------------------------------------------------------

def _per_leaf_update(opt, grads, state, params):
    """The leaf-for-leaf AdamW that the packed layout replaced: state is
    (step, m tree, v tree)."""
    step0, m, v = state
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt.clip_norm / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    step = step0 + 1
    lr = opt.schedule(step0)
    b1c = 1 - opt.b1 ** step.astype(jnp.float32)
    b2c = 1 - opt.b2 ** step.astype(jnp.float32)
    m = jax.tree.map(lambda m_, g: opt.b1 * m_ + (1 - opt.b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: opt.b2 * v_ + (1 - opt.b2) * g * g, v,
                     grads)

    def upd(p, m_, v_):
        u = (m_ / b1c) / (jnp.sqrt(v_ / b2c) + opt.eps) \
            + opt.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), (step, m, v)


def _mixed_tree(key):
    """Small leaves, leaves at and above the packing size, bf16 leaves."""
    shapes = {"big": ((257, 256), jnp.float32),       # own buffers
              "edge": ((256, 256), jnp.float32),      # 65536: packed
              "bias": ((7,), jnp.float32),
              "blk": {"w": ((33, 65), jnp.bfloat16),
                      "scale": ((), jnp.float32),
                      "wide": ((300, 300), jnp.bfloat16)},
              "attn": {"wq": ((16, 2, 8), jnp.float32)},  # role: own
              "norm": ((64,), jnp.bfloat16)}
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[0], tuple))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, s).astype(d) for k, (s, d) in zip(keys, leaves)])


def _assert_trees_close(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        tol = 1e-6 if x.dtype == jnp.float32 else 1e-2
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=tol, atol=tol)


def test_packed_state_matches_per_leaf_adamw():
    params = _mixed_tree(jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-2, warmup_steps=2, total_steps=10, clip_norm=50.0)
    state = opt.init(params)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    ref_state = (jnp.zeros((), jnp.int32), zeros, zeros)
    ref_params = params
    update = jax.jit(opt.update)
    ref_update = jax.jit(lambda g, s, p: _per_leaf_update(opt, g, s, p))
    for i in range(5):
        g = _mixed_tree(jax.random.PRNGKey(100 + i))
        params, state, _ = update(g, state, params)
        ref_params, ref_state = ref_update(g, ref_state, ref_params)
    assert int(state.step) == int(ref_state[0]) == 5
    _assert_trees_close(params, ref_params)
    _assert_trees_close(state.m, ref_state[1])
    _assert_trees_close(state.v, ref_state[2])


def _odenet_mnist_shapes():
    """The 24 leaves of ``bench/models/odenet-mnist.py:init_params`` at
    the published widths (64 channels, 1 input channel, 10 classes)."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    conv = lambda k, ci, co: {"w": f32(k, k, ci, co), "b": f32(co)}
    norm = lambda c: {"scale": f32(c), "bias": f32(c)}
    return {"down": {"conv1": conv(3, 1, 64), "norm1": norm(64),
                     "conv2": conv(4, 64, 64), "norm2": norm(64),
                     "conv3": conv(4, 64, 64)},
            "ode": {"norm1": norm(64), "conv1": conv(3, 65, 64),
                    "norm2": norm(64), "conv2": conv(3, 65, 64),
                    "norm3": norm(64)},
            "head": {"norm": norm(64), "w": f32(64, 10), "b": f32(10)}}


def test_odenet_moments_are_packed():
    """All 24 leaves of the ODE-Net classifier have at most 65,536
    elements (the two 4x4x64x64 down-sampler convs exactly that) and no
    sharding role, so the state is the step and one m and one v buffer."""
    shapes = _odenet_mnist_shapes()
    assert len(jax.tree.leaves(shapes)) == 24
    state = jax.eval_shape(AdamW().init, shapes)
    assert len(jax.tree.leaves(state)) == 3
    assert state.layout.n_packed == 24
    assert state.packed_m.shape == (sum(l.size for l in
                                        jax.tree.leaves(shapes)),)


@pytest.mark.parametrize("name,shape", [
    ("misc", (255, 257)),      # 65,535 elements
    ("misc", (256, 256)),      # 65,536
    ("misc", (256, 257)),      # 65,792
    ("scale", (64,)),
    ("wq", (64, 4, 16)),       # small, sharded by the attention rule
    ("table", (256, 64)),      # small, sharded by the embedding rule
    ("w_gate", (64, 128)),     # small, sharded by the column rule
])
def test_packed_leaves_are_the_replicated_leaves(name, shape):
    """A leaf's moments pack exactly where ``param_specs`` replicates it
    on a 2x2 mesh (every dim here divides by 2, so a rule that shards
    the leaf does)."""
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as shd

    mesh = jax.sharding.AbstractMesh(
        (2, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shapes = {"blk": {name: jax.ShapeDtypeStruct(shape, jnp.float32)}}
    spec = shd.param_specs(None, shapes, mesh)["blk"][name]
    replicated = spec == P(*(None,) * len(shape))
    state = jax.eval_shape(AdamW().init, shapes)
    assert len(jax.tree.leaves(state)) == (3 if replicated else 5)
    ospecs = shd.opt_state_specs(shd.param_specs(None, shapes, mesh), state)
    own = jax.tree.leaves(ospecs, is_leaf=lambda x: isinstance(x, P))[3:]
    assert own == ([] if replicated else [spec, spec])


def _arch_names():
    from repro.configs.registry import ARCHS
    return sorted(ARCHS)


@pytest.mark.parametrize("arch", _arch_names())
def test_arch_packing_keeps_sharded_moments(arch):
    """For every architecture, full and reduced: no leaf that packs has
    exactly ``_REPLICATE_MAX`` elements (so the inclusive bound replicates
    no LM leaf that the exclusive one sharded), and on a 2x2 mesh every
    packed leaf's param replicates while every other leaf's moments take
    its param's spec."""
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import reduced
    from repro.configs.registry import get_arch
    from repro.dist import sharding as shd
    from repro.models import lm

    mesh = jax.sharding.AbstractMesh(
        (2, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    is_p = lambda x: isinstance(x, P)
    for cfg in (get_arch(arch), reduced(get_arch(arch))):
        shapes = jax.eval_shape(lambda: lm.init_params(
            cfg, jax.random.PRNGKey(0)))
        leaves = jax.tree_util.tree_leaves_with_path(shapes)
        packed = [shd.replicated_leaf(path, l.shape) for path, l in leaves]
        assert not any(p and l.size == shd._REPLICATE_MAX
                       for p, (_, l) in zip(packed, leaves))
        pspecs = jax.tree.leaves(shd.param_specs(cfg, shapes, mesh),
                                 is_leaf=is_p)
        assert all(s == P(*(None,) * len(s)) for s, p in zip(pspecs, packed)
                   if p)
        state = jax.eval_shape(AdamW().init, shapes)
        assert state.layout.n_packed == sum(packed)
        own = [s for s, p in zip(pspecs, packed) if not p]
        ospecs = shd.opt_state_specs(
            shd.param_specs(cfg, shapes, mesh), state)
        assert jax.tree.leaves(ospecs, is_leaf=is_p) == [P()] * 3 + own + own


def test_bare_array_params_pack():
    """A single array is a tree with an empty path: it packs by size."""
    opt = AdamW(lr=0.1, warmup_steps=1, clip_norm=10.0)
    params = jnp.ones((5,))
    state = opt.init(params)
    assert len(jax.tree.leaves(state)) == 3
    params, state, _ = opt.update(jnp.full((5,), 0.5), state, params)
    assert state.m.shape == (5,)
    np.testing.assert_allclose(np.asarray(state.m), 0.05, rtol=1e-6)


def test_state_moments_unpack_and_repack():
    params = _mixed_tree(jax.random.PRNGKey(1))
    opt = AdamW()
    state = opt.init(params)
    tree = jax.tree.structure(params)
    assert jax.tree.structure(state.m) == tree
    assert jax.tree.structure(state.v) == tree
    m = jax.tree.map(lambda p: jnp.full(p.shape, 2.0, jnp.float32), params)
    v = jax.tree.map(lambda p: jnp.arange(p.size, dtype=jnp.float32)
                     .reshape(p.shape), params)
    new = state._replace(m=m, v=v)
    assert len(jax.tree.leaves(new)) == len(jax.tree.leaves(state))
    _assert_trees_close(new.m, m)
    _assert_trees_close(new.v, v)
    assert new.step is state.step


def test_opt_state_specs_follow_the_packed_structure():
    from jax.sharding import PartitionSpec as P
    from repro.configs.registry import get_arch
    from repro.dist import sharding as shd
    from repro.models import lm

    cfg = get_arch("smollm-135m")
    mesh = jax.sharding.AbstractMesh(
        (16, 16), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shapes = jax.eval_shape(lambda: lm.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    pspecs = shd.param_specs(cfg, shapes, mesh)
    opt_shape = jax.eval_shape(AdamW().init, shapes)
    ospecs = shd.opt_state_specs(pspecs, opt_shape)
    is_p = lambda x: isinstance(x, P)
    assert (jax.tree.structure(ospecs, is_leaf=is_p)
            == jax.tree.structure(opt_shape))
    specs = jax.tree.leaves(ospecs, is_leaf=is_p)
    assert specs[:3] == [P(), P(), P()]
    # every unpacked leaf's moments keep its param's (sharded) spec
    own = [s for s, (path, l) in zip(
        jax.tree.leaves(pspecs, is_leaf=is_p),
        jax.tree_util.tree_leaves_with_path(shapes))
        if not shd.replicated_leaf(path, l.shape)]
    assert own and specs[3:] == own + own
    assert any(any(a is not None for a in s) for s in own)


def test_packed_state_checkpoint_roundtrip(tmp_path):
    from repro.ckpt import load_checkpoint, save_checkpoint
    params = _mixed_tree(jax.random.PRNGKey(2))
    opt = AdamW()
    state = opt.init(params)
    params, state, _ = opt.update(_mixed_tree(jax.random.PRNGKey(3)),
                                  state, params)
    path = save_checkpoint(tmp_path, 1, {"opt_state": state})
    keys = json.loads((path / "tree.json").read_text())["leaves"]
    assert sorted(keys) == sorted(
        f"opt_state::{k}" for k in ("step", "packed_m", "packed_v",
                                    "m/big", "m/blk/wide", "m/attn/wq",
                                    "v/big", "v/blk/wide", "v/attn/wq"))
    back, step = load_checkpoint(tmp_path, {"opt_state": opt.init(params)})
    assert step == 1
    back = back["opt_state"]
    assert jax.tree.structure(back) == jax.tree.structure(state)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(state)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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
