"""GSPMD sharding rules for every assigned architecture family.

The production layout on a v5e pod is a 2-D ``("data", "model")`` mesh
(multi-pod runs add a leading ``"pod"`` axis that behaves like extra data
parallelism for batches but keeps parameters pod-replicated, so the only
cross-pod traffic is the gradient all-reduce — see optim/compress.py).

Parameter rules (``param_specs``), per leaf role:

  embeddings / lm head   (V, D)       -> vocab on 'model', d_model on 'data'
                                         (matches the model-sharded vocab dim
                                         of the logits; see models/lm._logits)
  attention wq/wk/wv     (.., D, H, dh)-> heads on 'model' when H divides it
                                         (Megatron TP), else head_dim; d_model
                                         carries the FSDP 'data' shard
  attention wo           (.., H, dh, D)-> same, transposed
  dense FFN / channel-mix (.., D, F)   -> F on 'model' (column-parallel),
                          (.., F, D)   -> F on 'model' (row-parallel); the
                                         other dim carries 'data' (FSDP)
  MoE experts            (.., E, D, F) -> expert-parallel (E on 'model') when
                                         E divides the model axis (dbrx: 16
                                         experts on model=16), else
                                         TP-within-expert (F on 'model';
                                         mixtral: 8 experts on model=16)
  everything else        generic: largest divisible trailing dims get
                                         'data' then 'model'; small leaves
                                         (<= _REPLICATE_MAX elems) replicate

Every pin is divisibility-guarded: a dim that the mesh axis product does not
divide is silently dropped (never an invalid spec), and each mesh axis is
used at most once per leaf.  Stacked-scan leaves (``blocks/scan/...``) never
shard their leading unit dim — ``lax.scan`` slices it every step.

``constrain_batch`` / ``constrain_dims`` are the in-graph counterparts: they
apply ``lax.with_sharding_constraint`` under a mesh set with
``jax.set_mesh`` (Auto axes, see ``launch.mesh``) and are exact no-ops
outside one, so model code stays mesh-agnostic.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
from jax import tree_util as jtu
from jax.sharding import NamedSharding, PartitionSpec as P

# mesh axes that carry the global batch, outermost first
BATCH_AXES = ("pod", "data")
# leaves of at most this many elements replicate under the generic rule
# (norm scales, biases, decay params): sharding them saves nothing and costs
# collectives.  ``replicated_leaf`` is the one test of it
_REPLICATE_MAX = 65536


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def _current_mesh():
    """The ambient mesh set with ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _axis_size(mesh, name: str) -> int:
    if mesh is None or name not in mesh.axis_names:
        return 1
    return int(mesh.shape[name])


def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes the global batch is split over, in outer-to-inner order."""
    if mesh is None:
        return ()
    return tuple(a for a in BATCH_AXES if a in mesh.axis_names)


def _batch_spec_entry(mesh, batch: int):
    """The PartitionSpec entry for a batch dim: tuple for multi-pod meshes,
    plain axis name for single-pod, None when the batch doesn't divide."""
    ba = batch_axes(mesh)
    n = math.prod(_axis_size(mesh, a) for a in ba)
    if not ba or n <= 1 or batch % n != 0:
        return None
    return ba if len(ba) > 1 else ba[0]


def to_shardings(specs, mesh):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jtu.tree_map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# spec construction
# ---------------------------------------------------------------------------

def _spec_from_pins(shape: Sequence[int], pins: Mapping[int, Any], mesh) -> P:
    """Build a PartitionSpec from {dim: axis-or-axes} pins, dropping any pin
    whose axis product does not divide the dim (and any axis already used —
    GSPMD allows each mesh axis at most once per spec)."""
    out: list = [None] * len(shape)
    used: set = set()
    for d, ax in pins.items():
        if ax is None or not (0 <= d < len(shape)):
            continue
        axes = tuple(ax) if isinstance(ax, (tuple, list)) else (ax,)
        axes = tuple(a for a in axes
                     if a in getattr(mesh, "axis_names", ()) and a not in used)
        if not axes:
            continue
        n = math.prod(int(mesh.shape[a]) for a in axes)
        if n <= 1 or shape[d] % n != 0:
            continue
        used.update(axes)
        out[d] = axes if len(axes) > 1 else axes[0]
    return P(*out)


def _path_keys(path) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", p)) for p in path)


def _generic_pins(shp: Sequence[int], keys: Sequence[str], mesh) -> Dict[int, str]:
    """Fallback rule: 'data' on the largest divisible dim, 'model' on the
    next; never the stacked-scan unit dim."""
    start = 1 if "scan" in keys and len(shp) > 1 else 0
    dims = sorted(range(start, len(shp)), key=lambda d: -shp[d])
    pins: Dict[int, str] = {}
    for ax in ("data", "model"):
        n = _axis_size(mesh, ax)
        if n <= 1:
            continue
        for d in dims:
            if d not in pins and shp[d] % n == 0:
                pins[d] = ax
                break
    return pins


_COL_NAMES = ("w_gate", "w_up", "w_in", "w_in_gate", "w_in_rnn",
              "w_r", "w_k", "w_v", "w_g", "w_lora", "w_a", "w_i")
_ROW_NAMES = ("w_down", "w_out", "w_o")


def _role(keys: Sequence[str], nd: int) -> Optional[str]:
    """The role rule that claims a leaf, from its path and rank; None for
    the generic rule."""
    name = keys[-1] if keys else ""
    if "moe" in keys and name in ("w_gate", "w_up", "w_down") and nd >= 3:
        return "moe"
    if name in ("wq", "wk", "wv") and nd >= 3:
        return "qkv"
    if name == "wo" and nd >= 3:
        return "wo"
    if name == "table" and nd == 2:
        return "table"
    if name in _COL_NAMES and nd >= 2:
        return "col"
    if name in _ROW_NAMES and nd >= 2:
        return "row"
    return None


def replicated_leaf(path, shape: Sequence[int]) -> bool:
    """True for a leaf that ``param_specs`` replicates under every mesh:
    no role rule claims it and it has at most ``_REPLICATE_MAX`` elements.
    AdamW packs the moments of exactly these leaves (optim/adamw.py)."""
    return (_role(_path_keys(path), len(shape)) is None
            and math.prod(shape) <= _REPLICATE_MAX)


def param_specs(cfg, shapes, mesh):
    """Per-leaf PartitionSpec tree for ``lm.init_params(cfg, ...)`` shapes.

    ``shapes`` is the eval_shape pytree; the returned tree has the identical
    structure with a PartitionSpec at every array leaf.
    """
    del cfg  # rules key off leaf paths/shapes; kept for per-family overrides
    nm = _axis_size(mesh, "model")

    def rule(path, leaf):
        keys = _path_keys(path)
        shp = tuple(leaf.shape)
        nd = len(shp)
        role = _role(keys, nd)

        # ---- MoE expert banks: (.., E, D, F) / (.., E, F, D)
        if role == "moe":
            n_exp = shp[nd - 3]
            if nm > 1 and n_exp % nm == 0:
                # expert parallelism: one (or more) experts per model shard
                pins = {nd - 3: "model", nd - 2: "data"}
            elif keys[-1] == "w_down":      # TP within expert: F on 'model'
                pins = {nd - 2: "model", nd - 1: "data"}
            else:
                pins = {nd - 1: "model", nd - 2: "data"}
        # ---- attention projections
        elif role == "qkv":
            # (.., D, Hx, dh): heads on 'model' when divisible, else head_dim
            pins = {nd - 3: "data"}
            pins[nd - 2 if nm > 1 and shp[nd - 2] % nm == 0 else nd - 1] = \
                "model"
        elif role == "wo":
            # (.., H, dh, D)
            pins = {nd - 1: "data"}
            pins[nd - 3 if nm > 1 and shp[nd - 3] % nm == 0 else nd - 2] = \
                "model"
        # ---- embeddings / lm head / learned positions: (V, D)
        elif role == "table":
            pins = {0: "model", 1: "data"}
        # ---- dense 2-D projections (FFN, channel-mix, rwkv/rglru mixers)
        elif role == "col":
            pins = {nd - 1: "model", nd - 2: "data"}
        elif role == "row":
            pins = {nd - 2: "model", nd - 1: "data"}
        # ---- everything else
        elif replicated_leaf(path, shp):
            return P(*(None,) * nd)
        else:
            pins = _generic_pins(shp, keys, mesh)
        return _spec_from_pins(shp, pins, mesh)

    return jtu.tree_map_with_path(rule, shapes)


def opt_state_specs(pspecs, opt_shape):
    """Optimizer-state specs with the structure of ``opt_shape`` (the
    ``AdamWState`` of ``jax.eval_shape(opt.init, params_shape)``): the step
    and the packed moments replicate, as the params of the packed leaves
    (``replicated_leaf``) do; every other leaf's m and v take that param's
    spec, so its FSDP/TP shards apply to its moments."""
    from repro.optim.adamw import AdamWState
    own = opt_shape.layout.own(pspecs)
    return AdamWState(P(), P(), P(), own, own, opt_shape.layout)


def batch_specs(cfg, cell, mesh) -> Dict[str, P]:
    """Input-batch specs: the global batch dim is split over every batch
    axis present (multi-pod: ``("pod", "data")``); everything else stays
    unsharded (the token dims are consumed by batch-parallel ops)."""
    b = _batch_spec_entry(mesh, cell.global_batch)
    specs = {"tokens": P(b, None), "targets": P(b, None)}
    if cfg.frontend == "vision_stub":
        specs["patches"] = P(b, None, None)
    if cfg.family == "encdec":
        specs["frames"] = P(b, None, None)
    return specs


def decode_state_specs(cfg, cell, state_shape, mesh):
    """Decode-state (KV cache / recurrent state) specs.

    Batched decode shards the batch dim over the data axes.  B=1 long-context
    decode cannot — there the KV cache sequence dim is sharded over 'data'
    instead (sequence parallelism), which is what makes a 512k cache fit.
    KV head (or head_dim) carries 'model' when divisible, mirroring the
    attention TP of param_specs.
    """
    nm = _axis_size(mesh, "model")
    batch_ok = _batch_spec_entry(mesh, cell.global_batch) is not None

    def rule(path, leaf):
        keys = _path_keys(path)
        shp = tuple(leaf.shape)
        nd = len(shp)
        bdim = 1 if "scan" in keys else 0
        pins: Dict[int, Any] = {}
        if batch_ok:
            pins[bdim] = batch_axes(mesh)
        else:
            # sequence parallelism over the max_seq dim (KV caches only)
            for d in range(nd):
                if d != bdim and shp[d] == cell.seq_len:
                    pins[d] = "data"
                    break
        if keys[-1] in ("k", "v", "xk", "xv") and nd >= 2:
            # (.., B, S, Hkv, dh): model on kv heads, else head_dim
            pins[nd - 2 if nm > 1 and shp[nd - 2] % nm == 0 else nd - 1] = \
                "model"
        return _spec_from_pins(shp, pins, mesh)

    return jtu.tree_map_with_path(rule, state_shape)


# ---------------------------------------------------------------------------
# in-graph constraints (no-ops outside a mesh)
# ---------------------------------------------------------------------------

def constrain_dims(x, pins: Mapping[int, Any]):
    """``lax.with_sharding_constraint`` pinning {dim: mesh-axis(es)} under the
    ambient mesh; drops non-divisible pins; identity outside a mesh."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = _spec_from_pins(x.shape, pins, mesh)
    if all(s is None for s in spec):
        return x  # a trivial constraint would force full replication
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_batch(x, extra: Optional[Mapping[int, Any]] = None):
    """Keep dim 0 split over the batch axes (plus optional extra dim pins:
    e.g. the model-sharded vocab dim of the logits).  No-op outside a mesh."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    pins: Dict[int, Any] = {}
    ba = batch_axes(mesh)
    if ba:
        pins[0] = ba
    if extra:
        pins.update(extra)
    return constrain_dims(x, pins)
