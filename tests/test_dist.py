"""Distribution: sharding-rule unit tests on an abstract mesh (no devices
needed) + multi-device integration tests in subprocesses with a forced CPU
device count (sharded train step, pipeline parallelism, compressed psum,
small-mesh dry-run)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import ShapeCell, reduced
from repro.configs.registry import get_arch
from repro.dist import sharding as shd
from repro.models import lm
from tests.util import run_with_devices

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _specs(arch, mesh=MESH):
    cfg = get_arch(arch)
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, shapes, shd.param_specs(cfg, shapes, mesh)


def _assert_divisible(shapes, specs, mesh):
    ok = True

    def check(path, leaf, spec):
        nonlocal ok
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            if leaf.shape[d] % n != 0:
                ok = False
                raise AssertionError(f"{path}: dim {d} ({leaf.shape[d]}) "
                                     f"not divisible by {ax} ({n})")

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), shapes, specs,
        is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", ["smollm-135m", "mixtral-8x7b", "dbrx-132b",
                                  "rwkv6-7b", "recurrentgemma-9b",
                                  "whisper-medium", "gemma3-4b"])
def test_param_specs_divisible(arch):
    cfg, shapes, specs = _specs(arch)
    _assert_divisible(shapes, specs, MESH)


def test_large_weights_actually_sharded():
    """The big leaves (embeddings, FFN) must not silently replicate —
    replication of dbrx's 6144x10752x16 experts would never fit 16 GB."""
    cfg, shapes, specs = _specs("dbrx-132b")
    flat = jax.tree_util.tree_leaves_with_path(
        jax.tree_util.tree_map(lambda s: s, specs),
        is_leaf=lambda x: isinstance(x, P))
    shapes_flat = jax.tree_util.tree_leaves(shapes)
    total_repl = 0
    for (path, spec), shape in zip(flat, shapes_flat):
        n_elem = 1
        for d in shape.shape:
            n_elem *= d
        shard_factor = 1
        for ax in spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shard_factor *= MESH.shape[a]
        if n_elem > 1e6 and shard_factor == 1:
            raise AssertionError(f"large leaf replicated: {path} {shape}")
        total_repl += n_elem // shard_factor
    # per-device param bytes must be < 2 GB (bf16) for dbrx on 256 chips
    assert total_repl * 2 < 2e9, total_repl


def test_expert_parallelism_when_divisible():
    """dbrx (16 experts on model=16) -> EP; mixtral (8 experts) -> TP
    within experts (d_ff sharded)."""
    _, shapes_d, specs_d = _specs("dbrx-132b")
    _, shapes_m, specs_m = _specs("mixtral-8x7b")

    def moe_spec(specs):
        out = {}

        def walk(path, spec):
            key = "/".join(str(getattr(p, "key", p)) for p in path)
            if "w_gate" in key or "w_down" in key:
                out[key] = spec

        jax.tree_util.tree_map_with_path(
            walk, specs, is_leaf=lambda x: isinstance(x, P))
        return out

    d = moe_spec(specs_d)
    m = moe_spec(specs_m)
    # leaves live under the stacked 'scan' axis: dims are (scan, E, in, out),
    # so the expert dim is index 1
    assert any(len(s) > 1 and s[1] == "model" for s in d.values()), d  # EP
    assert all(not (len(s) > 1 and s[1] == "model") for s in m.values()), m
    assert any("model" in [a for a in s if a] for s in m.values()), m


def test_multipod_mesh_batch_specs():
    cfg = get_arch("smollm-135m")
    cell = ShapeCell("train_4k", 4096, 256, "train")
    specs = shd.batch_specs(cfg, cell, MESH3)
    assert specs["tokens"] == P(("pod", "data"), None)


def test_long500k_kv_cache_sequence_sharded():
    """B=1 decode cannot batch-shard; the KV cache must shard its sequence
    dim over 'data' (SP) so a 512k cache fits."""
    cfg = get_arch("gemma3-4b")
    cell = ShapeCell("long_500k", 524288, 1, "decode")
    state_shape = jax.eval_shape(
        lambda: lm.init_decode_state(cfg, 1, cell.seq_len))
    specs = shd.decode_state_specs(cfg, cell, state_shape, MESH)
    found_sp = False

    def walk(path, leaf, spec):
        nonlocal found_sp
        # a KV leaf has the 512k sequence dim; it must carry 'data' (SP)
        for d, size in enumerate(leaf.shape):
            if size == cell.seq_len and d < len(spec) and spec[d] == "data":
                found_sp = True

    jax.tree_util.tree_map_with_path(
        walk, state_shape, specs, is_leaf=lambda x: hasattr(x, "shape"))
    assert found_sp


# ---------------------------------------------------------------------------
# multi-device integration (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_train_step_8dev():
    out = run_with_devices("""
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import tree_util as jtu
from repro.configs.base import ShapeCell, reduced
from repro.configs.registry import get_arch
from repro.data.pipeline import SyntheticLM
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import lm
from repro.optim.adamw import AdamW

mesh = make_host_mesh(model_axis=2)
cfg = reduced(get_arch("smollm-135m"), d_model=64, n_heads=4, n_kv_heads=2)
cell = ShapeCell("t", 32, 8, "train")
with jax.set_mesh(mesh):
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = shd.param_specs(cfg, jax.eval_shape(lambda: params), mesh)
    pshard = shd.to_shardings(pspecs, mesh)
    params = jax.device_put(params, pshard)
    opt = AdamW(lr=1e-3, total_steps=10, warmup_steps=1)
    opt_state = jax.jit(opt.init)(params)
    step = jax.jit(make_train_step(cfg, opt))
    batch = SyntheticLM(cfg, cell).batch(jnp.int32(0))
    p, o, m = step(params, opt_state, batch, jnp.int32(0))
    # must equal the unsharded single-device result
    params1 = jax.device_put(params, jtu.tree_map(
        lambda _: NamedSharding(mesh, P()), pspecs,
        is_leaf=lambda x: isinstance(x, P)))
    p1, o1, m1 = step(params1, opt_state, batch, jnp.int32(0))
    assert abs(float(m["loss"]) - float(m1["loss"])) < 1e-4, (
        float(m["loss"]), float(m1["loss"]))
    print("LOSS_OK", float(m["loss"]))
""")
    assert "LOSS_OK" in out


@pytest.mark.slow
def test_pipeline_parallel_8dev():
    out = run_with_devices("""
import numpy as np
from repro.dist.pipeline import pipeline_apply
mesh = jax.make_mesh((4,), ("pod",))
n_layers, d = 8, 16
W = jax.random.normal(jax.random.PRNGKey(0), (n_layers, d, d)) * 0.1
x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
layer_fn = lambda p, h: h + jnp.tanh(h @ p["w"])
out = pipeline_apply(layer_fn, {"w": W}, x, mesh=mesh, n_micro=4)
ref = x
for i in range(n_layers):
    ref = layer_fn({"w": W[i]}, ref)
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-6, err
print("PIPELINE_OK", err)
""")
    assert "PIPELINE_OK" in out


@pytest.mark.slow
def test_compressed_psum_8dev():
    out = run_with_devices("""
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.optim.compress import compressed_psum
mesh = jax.make_mesh((8,), ("pod",), axis_types=(AxisType.Auto,))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 128)) * 0.01
true_sum = g.sum(axis=0)
for scheme, tol in [("none", 1e-6), ("bf16", 2e-2), ("int8", 5e-2)]:
    fn = jax.shard_map(lambda gg: compressed_psum(gg, "pod", scheme),
                       mesh=mesh, in_specs=(P("pod"),), out_specs=P("pod"),
                       check_vma=False)
    out = fn(g)[0]
    rel = float(jnp.linalg.norm(out - true_sum) / jnp.linalg.norm(true_sum))
    assert rel < tol, (scheme, rel)
print("PSUM_OK")
""")
    assert "PSUM_OK" in out


@pytest.mark.slow
def test_small_mesh_dryrun_16dev():
    """End-to-end mini version of the production dry-run: lower + compile a
    sharded train step on a (4, 4) mesh for a small-but-real config."""
    out = run_with_devices("""
from repro.launch.dryrun import build_cell, collective_bytes
from repro.configs.base import ShapeCell
import repro.configs.base as base
import repro.launch.dryrun as dr
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model_axis=4)
fn, args, in_sh, cfg, cell = dr.build_cell("smollm-135m", "train_4k", mesh)
with jax.set_mesh(mesh):
    lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
cost = compiled.cost_analysis()
coll = collective_bytes(compiled.as_text())
assert coll["total"] > 0
assert float(cost.get("flops", 0)) > 0
print("DRYRUN_OK", coll["total"])
""", n_devices=16)
    assert "DRYRUN_OK" in out
