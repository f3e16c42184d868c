"""Inference engines for ``repro.serve``: batched ODE evaluation under a
memory budget, and wave-based continuous batching for the LM decode path.

``ODEEngine`` is the paper workload as a service: CNF log-density
(``kind="density"``), score ``∇ₓ log p(x)`` (``"score"`` — the reverse
pass, i.e. the adjoint the paper is about), and ODE-classifier logits
(``"classify"``) over a caller-supplied vector field.  Batches come from
a ``RequestQueue``, are padded to a ``BucketSpec`` bucket (bounded jit
cache: one compiled program per (kind, bucket)), and every solve runs
through ``odeint(adjoint="pnode", offload="spill"|"disk")`` with a
caller-owned store whose ``lane_keys`` tie each checkpoint slot to the
request occupying that lane — slot key ``(request_id, step_index)``.
Because lane keys are consulted at callback *execution* time, the same
compiled bucket program serves every batch composition without retracing,
padding lanes store nothing, and ``store.free_request(rid)`` drops a
departing request's slots without touching its batch-mates.  Batched
offloaded solves are bitwise-identical to the unbatched per-request loop
(tests/test_serve.py asserts this across spill, disk, and the RAM/disk
split).

Memory budgets go through ``repro.mem.plan_odeint(batch=bucket)``: the
planner prices the *batched* working set (state and f-activation bytes
scale with the lane count, shared ``theta`` does not) and solves the
RAM/disk ``snaps_in_ram`` split the engine's stores then honor.

``adaptive=True`` selects the per-request loop path instead: adaptive
(dopri5) solves have data-dependent, per-lane-divergent step sequences,
so their staging-ring offload cannot be lane-keyed soundly (a batched
accept predicate under ``lax.cond`` would flush every lane on every
accept) — each request gets its own single-lane solve and store.  Same
queue, same tickets, same fault sites; throughput comes from the shared
compiled single-lane program rather than vmap.

``LMEngine`` is the token path: wave-based continuous batching honoring
the decode step's *scalar* position argument (all lanes of a wave share
``pos``), with the next wave's prefill interleaved between decode slices
of the active wave so admission never stalls the decode stream.

Fault sites (``repro.ft.inject``): ``serve.request`` (admission — see
``queue.py``) and ``serve.decode`` — an injected NaN poisons exactly one
lane's result, which resolves THAT ticket with an error while its
batch-mates' results stay bitwise-correct.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adjoint import odeint
from repro.core.adaptive import odeint_adaptive
from repro.core.cnf import change_of_variables, exact_trace_vf
from repro.mem.offload import make_store
from repro.mem.planner import plan_odeint
from repro.serve.queue import BucketSpec, RequestQueue, Ticket

__all__ = ["ODEEngine", "LMEngine"]


class ODEEngine:
    """Continuous-batching ODE inference over one vector field.

    Parameters
    ----------
    f : vector field ``f(u, theta, t)`` on ``(dim,)`` states.
    theta : its parameters (shared across every request).
    dim : state dimension; request payloads are ``(dim,)`` float arrays.
    dt, n_steps, t0, method : the solve grid (fixed-step path).
    offload : "spill" | "disk" | None — checkpoint tier for the reverse
        pass.  Overridden by the planner when a budget is given.
    mem_budget / ram_budget / disk_budget : consult ``plan_odeint`` with
        ``batch=max bucket`` (the worst-case working set) — the plan's
        policy/offload/snaps_in_ram configure the engine; ``.plan`` keeps
        the full report.
    head : optional ``head(u_final) -> logits`` readout for
        ``kind="classify"`` (default: identity — logits are the final
        state).
    adaptive : per-request adaptive (dopri5) path, see module docstring.
    """

    KINDS = ("density", "score", "classify")

    def __init__(self, f: Callable, theta: Any, *, dim: int, dt: float,
                 n_steps: int, t0: float = 0.0, method: str = "rk4",
                 offload: Optional[str] = "spill",
                 offload_segment: Optional[int] = None,
                 snaps_in_ram: Optional[int] = None,
                 mem_budget: Optional[int] = None,
                 ram_budget: Optional[int] = None,
                 disk_budget: Optional[int] = None,
                 buckets: Optional[BucketSpec] = None,
                 head: Optional[Callable] = None,
                 adaptive: bool = False, rtol: float = 1e-6,
                 atol: float = 1e-6, max_steps: int = 512,
                 spool_dir: Optional[str] = None,
                 queue: Optional[RequestQueue] = None,
                 fault_plan=None, registry=None, obs=None,
                 max_payload_bytes: int = 1 << 20, aging: float = 1.0):
        self.f = f
        self.theta = theta
        self.dim = int(dim)
        self.dt = float(dt)
        self.n_steps = int(n_steps)
        self.t0 = float(t0)
        self.method = method
        self.offload = offload
        self.offload_segment = offload_segment
        self.snaps_in_ram = snaps_in_ram
        self.buckets = buckets or BucketSpec()
        self.head = head if head is not None else (lambda u: u)
        self.adaptive = bool(adaptive)
        self.rtol, self.atol, self.max_steps = rtol, atol, int(max_steps)
        self.spool_dir = spool_dir
        self.fault_plan = fault_plan
        self.registry = registry
        self.obs = obs
        self._aug = exact_trace_vf(f, self.dim)
        self.plan = None
        if mem_budget is not None or ram_budget is not None:
            proto = (jnp.zeros((self.dim,), jnp.float32),
                     jnp.zeros((), jnp.float32))
            self.plan = plan_odeint(
                self._aug, proto, theta, dt=self.dt, n_steps=self.n_steps,
                t0=self.t0, method=method, mem_budget=mem_budget,
                ram_budget=ram_budget, disk_budget=disk_budget,
                verify="model", batch=self.buckets.max_size)
            # the plan sizes the BATCHED working set; honor its tier and
            # RAM/disk split (offload=None => the policy fits on device)
            self.offload = self.plan.offload
            if self.plan.snaps_in_ram is not None:
                self.snaps_in_ram = self.plan.snaps_in_ram
        if self.offload not in (None, "spill", "disk"):
            raise ValueError(
                f"ODEEngine serves the lane-keyed spill/disk tiers (or "
                f"no offload); got offload={self.offload!r}")
        self.queue = queue if queue is not None else RequestQueue(
            kinds=self.KINDS, dim=self.dim,
            max_payload_bytes=max_payload_bytes, aging=aging,
            fault_plan=fault_plan, registry=registry, obs=obs)
        self._stores: Dict[int, Any] = {}
        self._fns: Dict[Tuple[str, int], Callable] = {}

    # -- stores / compiled programs -----------------------------------------
    def _store(self, bucket: int):
        """One caller-owned store per bucket (the compiled bucket program
        captures it; sharing across kinds is safe — ``step`` is
        sequential).  Per-bucket disk subdirs keep one store's stale-file
        sweep away from its siblings' segment files."""
        if self.offload is None:
            return None
        if bucket not in self._stores:
            sub = None
            if self.spool_dir is not None:
                import os
                sub = os.path.join(self.spool_dir, f"bucket{bucket}")
                os.makedirs(sub, exist_ok=True)
            st = make_store(self.offload, fault_plan=self.fault_plan,
                            snaps_in_ram=self.snaps_in_ram, disk_dir=sub)
            if self.obs is not None:
                st.bind_obs(self.obs)
            st.lane_keys = (None,) * bucket
            self._stores[bucket] = st
        return self._stores[bucket]

    def _solver_kw(self, store) -> dict:
        kw = dict(dt=self.dt, n_steps=self.n_steps, t0=self.t0,
                  method=self.method, adjoint="pnode")
        if store is not None:
            kw.update(offload=self.offload,
                      offload_segment=self.offload_segment,
                      snaps_in_ram=self.snaps_in_ram, offload_store=store)
        return kw

    def _logp_one(self, theta, x, store):
        kw = self._solver_kw(store)
        z, dlogdet = odeint(self._aug, (x, jnp.zeros((), x.dtype)), theta,
                            **kw)
        return change_of_variables(z, dlogdet)

    def _fn(self, kind: str, bucket: int) -> Callable:
        """Compiled (kind, bucket) program — at most
        ``len(KINDS) * len(buckets.sizes)`` ever exist (the bounded
        compile cache the README documents)."""
        key = (kind, bucket)
        if key in self._fns:
            return self._fns[key]
        store = self._store(bucket)

        def density(theta, xb):
            return jax.vmap(lambda x: self._logp_one(theta, x, store))(xb)

        def score(theta, xb):
            g = jax.grad(lambda x: self._logp_one(theta, x, store))
            return jax.vmap(g)(xb)

        def classify(theta, xb):
            def one(x):
                uT = odeint(self.f, x, theta, **self._solver_kw(store))
                return self.head(uT)
            return jax.vmap(one)(xb)

        fn = {"density": density, "score": score,
              "classify": classify}[kind]
        self._fns[key] = jax.jit(fn)
        return self._fns[key]

    # -- adaptive (per-request) path ----------------------------------------
    def _adaptive_kw(self) -> dict:
        kw = dict(t0=self.t0, t1=self.t0 + self.dt * self.n_steps,
                  rtol=self.rtol, atol=self.atol, max_steps=self.max_steps)
        if self.offload is not None:
            kw.update(offload=self.offload,
                      offload_segment=self.offload_segment)
            if self.offload == "spill":
                kw.update(snaps_in_ram=self.snaps_in_ram)
        return kw

    def _adaptive_fn(self, kind: str) -> Callable:
        key = (f"adaptive.{kind}", 1)
        if key in self._fns:
            return self._fns[key]
        kw = self._adaptive_kw()

        def logp_one(theta, x):
            (z, dlogdet), _ = odeint_adaptive(
                self._aug, (x, jnp.zeros((), x.dtype)), theta, **kw)
            return change_of_variables(z, dlogdet)

        def density(theta, x):
            return logp_one(theta, x)

        def score(theta, x):
            return jax.grad(lambda xx: logp_one(theta, xx))(x)

        def classify(theta, x):
            uT, _ = odeint_adaptive(self.f, x, theta, **kw)
            return self.head(uT)

        fn = {"density": density, "score": score,
              "classify": classify}[kind]
        self._fns[key] = jax.jit(fn)
        return self._fns[key]

    # -- serving -------------------------------------------------------------
    def submit(self, kind: str, x, *, priority: float = 0.0,
               rid: Optional[str] = None) -> Ticket:
        return self.queue.submit(kind, x, priority=priority, rid=rid)

    def warmup(self, kinds=None, buckets=None) -> int:
        """Pre-compile (kind, bucket) programs with all-padding lane keys
        (stores nothing); returns the number compiled."""
        n = 0
        for kind in (kinds or self.KINDS):
            if self.adaptive:
                fn = self._adaptive_fn(kind)
                jax.block_until_ready(
                    fn(self.theta, jnp.zeros((self.dim,), jnp.float32)))
                n += 1
                continue
            for b in (buckets or self.buckets.sizes):
                store = self._store(b)
                if store is not None:
                    store.lane_keys = (None,) * b
                fn = self._fn(kind, b)
                jax.block_until_ready(
                    fn(self.theta, jnp.zeros((b, self.dim), jnp.float32)))
                n += 1
        return n

    def _resolve(self, batch, rows: List[np.ndarray], tick: int) -> None:
        for (req, ticket), row in zip(batch, rows):
            if not np.all(np.isfinite(row)):
                if self.registry is not None:
                    self.registry.inc("serve.errors")
                ticket.set_error(RuntimeError(
                    f"request {req.rid}: non-finite result "
                    f"(poisoned decode?)"), tick)
            else:
                if self.registry is not None:
                    self.registry.inc("serve.completed")
                ticket.set_result(row, tick)

    def step(self) -> int:
        """One scheduling quantum: claim a same-kind batch, pad it to a
        bucket, run the compiled program with the batch's lane keys, tick
        the ``serve.decode`` fault site, resolve tickets (a poisoned lane
        errors alone), free every request's slots.  Returns the number of
        requests served (0 = queue idle)."""
        batch = self.queue.next_batch(self.buckets.max_size)
        if not batch:
            return 0
        kind = batch[0][0].kind
        if self.adaptive:
            return self._step_adaptive(kind, batch)
        bucket = self.buckets.bucket_for(len(batch))
        xb = np.zeros((bucket, self.dim), np.float32)
        lanes: List[Optional[str]] = [None] * bucket
        for i, (req, _) in enumerate(batch):
            xb[i] = req.payload
            lanes[i] = req.rid
        store = self._store(bucket)
        stats0 = dict(store.stats) if store is not None else {}
        if store is not None:
            store.lane_keys = tuple(lanes)
        t_start = time.time()
        out = np.asarray(jax.block_until_ready(
            self._fn(kind, bucket)(self.theta, jnp.asarray(xb))))
        wall = time.time() - t_start
        out = out.copy()  # poisoning below must not alias a jax buffer
        if self.fault_plan is not None:
            spec = self.fault_plan.tick("serve.decode")
            if spec is not None and spec.kind == "nan":
                out[0] = np.nan  # first real lane: a request-level fault
        tick = self.queue.tick
        self._resolve(batch, [out[i] for i in range(len(batch))], tick)
        cbs = 0
        if store is not None:
            for req, _ in batch:
                store.free_request(req.rid)
            store.lane_keys = (None,) * bucket
            delta = {k: store.stats.get(k, 0) - stats0.get(k, 0)
                     for k in store.stats}
            cbs = (delta.get("write_cb", 0) + delta.get("read_cb", 0)
                   + delta.get("dispatch_cb", 0)
                   + delta.get("prefetch_hit_cb", 0))
        occ = len(batch) / bucket
        if self.registry is not None:
            self.registry.observe("serve.batch_occupancy", occ)
            self.registry.observe("serve.callbacks_per_request",
                                  cbs / len(batch))
            self.registry.observe("serve.batch_wall_s", wall)
        if self.obs is not None:
            self.obs.record("serve.batch", _runtime=True, req_kind=kind,
                            bucket=bucket, lanes=len(batch),
                            occupancy=occ, callbacks=cbs, wall_s=wall)
        return len(batch)

    def _step_adaptive(self, kind: str, batch) -> int:
        """Per-request loop: each request is its own single-lane adaptive
        solve (own store, built inside ``odeint_adaptive``) — trivially
        bitwise vs the unbatched reference, at batch occupancy 1."""
        fn = self._adaptive_fn(kind)
        rows = []
        t_start = time.time()
        for req, _ in batch:
            out = np.asarray(jax.block_until_ready(
                fn(self.theta, jnp.asarray(req.payload, jnp.float32))))
            out = np.atleast_1d(out).copy()
            if self.fault_plan is not None:
                spec = self.fault_plan.tick("serve.decode")
                if spec is not None and spec.kind == "nan":
                    out[...] = np.nan
            rows.append(out)
        wall = time.time() - t_start
        tick = self.queue.tick
        self._resolve(batch, rows, tick)
        if self.registry is not None:
            self.registry.observe("serve.batch_occupancy", 1.0)
            self.registry.observe("serve.batch_wall_s", wall)
        if self.obs is not None:
            self.obs.record("serve.batch", _runtime=True, req_kind=kind,
                            bucket=1, lanes=len(batch), occupancy=1.0,
                            adaptive=True, wall_s=wall)
        return len(batch)

    def run(self, max_steps: int = 10_000) -> int:
        """Drain the queue; returns requests served."""
        served = 0
        for _ in range(max_steps):
            n = self.step()
            if n == 0 and self.queue.depth() == 0:
                break
            served += n
        return served

    def slot_census(self) -> Dict[str, int]:
        """Summed live slots across every bucket store (0 everywhere when
        no request is in flight — departures freed their slots)."""
        total = {"ram": 0, "disk": 0, "disk_files": 0}
        for st in self._stores.values():
            for k, v in st.slot_census().items():
                total[k] = total.get(k, 0) + v
        return total


class _Wave:
    """One cohort of lanes decoding in lockstep (shared scalar ``pos``)."""

    def __init__(self, batch, state, tok, pos0: int, max_gen: int,
                 lanes: int):
        self.batch = batch              # [(Request, Ticket)] real lanes
        self.state = state
        self.tok = tok                  # (lanes, 1) int32 — last sampled
        self.pos = 0                    # decode steps taken so far
        self.pos0 = int(pos0)
        self.max_gen = int(max_gen)
        self.lanes = int(lanes)
        self.emitted: List[np.ndarray] = []   # per-step (lanes,) tokens
        self.errored: set = set()       # lane indices poisoned mid-decode

    @property
    def done(self) -> bool:
        return len(self.emitted) >= self.max_gen


class LMEngine:
    """Wave-based continuous batching for the LM prefill/decode path.

    The decode step takes a *scalar* position (``lm.decode_step``'s KV /
    recurrent state contract), so lanes cannot be at different sequence
    offsets inside one batch: requests are grouped into *waves* that
    prefill together and decode in lockstep.  Interleaving happens at the
    scheduling level — between decode slices of the active wave the
    engine prefills the next wave (``_staged``), so when the active wave
    retires the next one starts decoding immediately instead of stalling
    on prefill + compile.

    ``call_log`` records every device call (op, wall seconds, tokens
    emitted, compile-or-not) — the accounting ``launch/serve.py`` uses to
    split warm-up from steady state.
    """

    def __init__(self, cfg, *, lanes: int, prompt_len: int, max_gen: int,
                 decode_slice: int = 4, temperature: float = 0.0,
                 seed: int = 0, mesh=None, shard: bool = False,
                 params=None, fault_plan=None, registry=None, obs=None,
                 aging: float = 1.0):
        from repro.configs.base import ShapeCell
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_decode_step, make_prefill_step
        from repro.models import lm as lm_mod

        self.cfg = cfg
        self.lanes = int(lanes)
        self.prompt_len = int(prompt_len)
        self.max_gen = int(max_gen)
        self.decode_slice = max(1, int(decode_slice))
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.mesh = mesh if mesh is not None else make_host_mesh()
        self.fault_plan = fault_plan
        self.registry = registry
        self.obs = obs
        self._lm = lm_mod
        self.max_seq = self.prompt_len + self.max_gen
        self.queue = RequestQueue(
            kinds=("lm",), dim=self.prompt_len,
            max_payload_bytes=max(1 << 20, 8 * self.prompt_len),
            aging=aging, fault_plan=fault_plan, registry=registry, obs=obs)
        self.call_log: List[Dict[str, Any]] = []
        self._active: Optional[_Wave] = None
        self._staged: Optional[_Wave] = None
        self._decode_calls = 0
        self._wave_seq = 0
        self.pos0 = self.prompt_len + (
            cfg.n_patches if cfg.frontend == "vision_stub" else 0)

        with jax.set_mesh(self.mesh):
            if params is None:
                params = jax.jit(lambda k: lm_mod.init_params(cfg, k))(
                    jax.random.PRNGKey(self.seed))
            self.params = params
            prefill = make_prefill_step(cfg, max_seq=self.max_seq)
            decode = make_decode_step(cfg)
            if shard:
                # multi-replica serve: lanes sharded over the mesh's data
                # axes, decode state per repro.dist decode-state specs
                from jax.sharding import NamedSharding, PartitionSpec as P
                from repro.dist import sharding as shd
                cell = ShapeCell("serve", self.max_seq, self.lanes,
                                 "decode")
                pshape = jax.eval_shape(
                    lambda: lm_mod.init_params(cfg, jax.random.PRNGKey(0)))
                pshard = shd.to_shardings(
                    shd.param_specs(cfg, pshape, self.mesh), self.mesh)
                sshape = jax.eval_shape(
                    lambda: lm_mod.init_decode_state(cfg, self.lanes,
                                                     self.max_seq))
                sshard = shd.to_shardings(
                    shd.decode_state_specs(cfg, cell, sshape, self.mesh),
                    self.mesh)
                ba = shd.batch_axes(self.mesh)
                nd = 1
                for a in ba:
                    nd *= self.mesh.shape[a]
                bspec = ba if ba and self.lanes % max(1, nd) == 0 else None
                tshard = NamedSharding(self.mesh, P(bspec, None))
                scalar = NamedSharding(self.mesh, P())
                self._prefill_fn = jax.jit(prefill)
                # out state pinned to the same specs so the donated
                # decode->decode handoff never sees a sharding mismatch
                self._decode_fn = jax.jit(
                    decode, donate_argnums=(1,),
                    in_shardings=(pshard, sshard, tshard, scalar),
                    out_shardings=(tshard, sshard))
                self.params = jax.device_put(self.params, pshard)
                self._state_shard, self._tok_shard = sshard, tshard
            else:
                self._prefill_fn = jax.jit(prefill)
                self._decode_fn = jax.jit(decode, donate_argnums=(1,))
                self._state_shard = self._tok_shard = None

    # -- client API ----------------------------------------------------------
    def submit(self, prompt, *, gen: Optional[int] = None,
               priority: float = 0.0, rid: Optional[str] = None,
               extras: Optional[Dict[str, Any]] = None) -> Ticket:
        """Admit one prompt (``(prompt_len,)`` int tokens).  ``gen`` caps
        this request's emitted tokens (≤ engine ``max_gen``); ``extras``
        carries per-request frontend arrays (vision patches, enc-dec
        frames) stacked into the wave's prefill batch."""
        gen = self.max_gen if gen is None else min(int(gen), self.max_gen)
        meta = {"gen": gen}
        if extras:
            meta["extras"] = {k: np.asarray(v) for k, v in extras.items()}
        return self.queue.submit("lm", np.asarray(prompt, np.int32),
                                 priority=priority, rid=rid, meta=meta)

    # -- internals -----------------------------------------------------------
    def _sample(self, key, logits):
        if self.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.temperature, axis=-1).astype(jnp.int32)

    def _prefill_next(self) -> Optional[_Wave]:
        batch = self.queue.next_batch(self.lanes, kind="lm")
        if not batch:
            return None
        self._wave_seq += 1
        toks = np.zeros((self.lanes, self.prompt_len), np.int32)
        for i, (req, _) in enumerate(batch):
            toks[i] = req.payload
        prompt: Dict[str, Any] = {"tokens": jnp.asarray(toks)}
        extras = batch[0][0].meta.get("extras") or {}
        for k, proto in extras.items():
            stack = np.zeros((self.lanes,) + proto.shape, proto.dtype)
            for i, (req, _) in enumerate(batch):
                stack[i] = req.meta.get("extras", {}).get(
                    k, np.zeros_like(proto))
            prompt[k] = jnp.asarray(stack)
        compile_ = not self.call_log  # first prefill pays the compile
        t_start = time.time()
        with jax.set_mesh(self.mesh):
            state, logits = self._prefill_fn(self.params, prompt)
            jax.block_until_ready(logits)
        wall = time.time() - t_start
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed + 1),
                                 self._wave_seq)
        tok = self._sample(key, logits)[:, None]
        if self._state_shard is not None:
            # prefill output is committed wherever GSPMD left it; move the
            # wave state/token onto the decode-state specs before the
            # donated decode loop (explicit in_shardings won't reshard
            # committed args)
            state = jax.device_put(state, self._state_shard)
            tok = jax.device_put(tok, self._tok_shard)
        max_gen = max(r.meta["gen"] for r, _ in batch)
        wave = _Wave(batch, state, tok, self.pos0, max_gen, self.lanes)
        # the prefill's sampled token is token #1 of every lane — it
        # COUNTS toward throughput (the old driver dropped it)
        wave.emitted.append(np.asarray(tok[:, 0]))
        self.call_log.append({"op": "prefill", "wall_s": wall,
                              "tokens": len(batch), "compile": compile_,
                              "lanes": len(batch)})
        if self.obs is not None:
            self.obs.record("serve.prefill", _runtime=True,
                            lanes=len(batch), wall_s=wall)
        if self.registry is not None:
            self.registry.observe("serve.batch_occupancy",
                                  len(batch) / self.lanes)
        return wave

    def _decode_slice(self, wave: _Wave) -> None:
        k = min(self.decode_slice, wave.max_gen - len(wave.emitted))
        if k <= 0:
            return
        compile_ = self._decode_calls == 0
        armed = self.fault_plan is not None
        t_start = time.time()
        with jax.set_mesh(self.mesh):
            for _ in range(k):
                i = len(wave.emitted) - 1  # decode steps taken so far
                logits, wave.state = self._decode_fn(
                    self.params, wave.state, wave.tok,
                    jnp.int32(wave.pos0 + i))
                if armed:
                    spec = self.fault_plan.tick("serve.decode")
                    if spec is not None and spec.kind == "nan":
                        # poison exactly one lane's logits: a request-level
                        # fault, not a batch-level one
                        logits = logits.at[0].set(jnp.nan)
                    bad = np.asarray(jnp.any(~jnp.isfinite(logits), axis=-1))
                    wave.errored.update(int(j) for j in np.nonzero(bad)[0])
                key = jax.random.fold_in(
                    jax.random.PRNGKey(self.seed + 1),
                    (self._wave_seq << 16) + len(wave.emitted))
                wave.tok = self._sample(
                    key, jnp.nan_to_num(logits))[:, None]
                if self._tok_shard is not None:
                    wave.tok = jax.device_put(wave.tok, self._tok_shard)
                wave.emitted.append(np.asarray(wave.tok[:, 0]))
            jax.block_until_ready(wave.tok)
        wall = time.time() - t_start
        self._decode_calls += 1
        self.call_log.append({"op": "decode", "wall_s": wall,
                              "tokens": k * len(wave.batch),
                              "steps": k, "compile": compile_,
                              "lanes": len(wave.batch)})

    def _retire(self, wave: _Wave) -> None:
        tick = self.queue.tick
        grid = np.stack(wave.emitted, axis=1)  # (lanes, emitted)
        for i, (req, ticket) in enumerate(wave.batch):
            if i in wave.errored:
                if self.registry is not None:
                    self.registry.inc("serve.errors")
                ticket.set_error(RuntimeError(
                    f"request {req.rid}: poisoned decode (serve.decode)"),
                    tick)
                continue
            if self.registry is not None:
                self.registry.inc("serve.completed")
            ticket.set_result(grid[i, :req.meta["gen"]].copy(), tick)
        if self.obs is not None:
            self.obs.record("serve.retire", _runtime=True,
                            lanes=len(wave.batch),
                            tokens=len(wave.emitted) * len(wave.batch),
                            errored=len(wave.errored))

    def step(self) -> bool:
        """One scheduling quantum.  Activates a staged/new wave, decodes
        one slice, and interleaves the NEXT wave's prefill between slices
        of the active one.  Returns False when fully idle."""
        if self._active is None:
            self._active = self._staged or self._prefill_next()
            self._staged = None
            if self._active is None:
                return False
            return True
        self._decode_slice(self._active)
        if self._active.done:
            self._retire(self._active)
            self._active = None
            return True
        if self._staged is None and self.queue.depth() > 0:
            # prefill interleaved between decode slices: admission never
            # stalls the decode stream
            self._staged = self._prefill_next()
        return True

    def run(self, max_quanta: int = 100_000) -> None:
        """Drive until queue + waves drain."""
        for _ in range(max_quanta):
            busy = self.step()
            if not busy and self.queue.depth() == 0 \
                    and self._active is None and self._staged is None:
                return
        raise RuntimeError("LMEngine.run did not drain "
                           f"within {max_quanta} quanta")
