"""Checkpoint stores: where adjoint checkpoints live between fwd and bwd.

The checkpointed sweeps in ``core/sweeps.py`` write their checkpoints
through one of these stores instead of returning them directly as ``custom_vjp`` residuals.  Four tiers:

  device   checkpoints stay traced values and travel through the residual
           pytree — exactly the seed behavior (XLA keeps them in device
           memory for the whole fwd->bwd window).
  host     checkpoints are moved to the backend's pinned-host memory space
           with ``jax.device_put(x, jax.memory.Space.Host)`` at put time and
           brought back at get time; the residual pytree carries
           host-resident arrays, so device-live memory between the sweeps
           is O(working set).  Sharded arrays keep their layout: a
           memory-kind transfer preserves the NamedSharding, so each device
           spills its own shard.  A backend without a pinned_host space
           cannot hold this tier: ``HostStore`` raises there instead of
           keeping the checkpoints on the device.  Inside jit, JAX lowers
           the transfer only for TPU/GPU (``annotate_device_placement``);
           on XLA:CPU it is the identity, so there the tier compiles to
           the ``device`` program.
  spill    checkpoints leave the XLA program entirely through a
           token-threaded ``jax.pure_callback`` into a host-side numpy dict.
           The residual is one f32 scalar (the ordering token), so the
           reverse pass's device-live set is O(ncheck) / O(1) regardless of
           ``n_steps``.  Ordering: every write returns a fresh token and
           every read consumes the latest one, so writes are
           data-dependencies of reads and XLA cannot reorder or elide
           them; slot reads return a token too, ordering subsequent
           frees/overwrites after the reads that precede them.
           (``io_callback(ordered=True)`` would be the natural primitive,
           but its effects are silently dropped inside ``custom_vjp`` rules
           on jax 0.4.37 — verified empirically — hence the token chain.)
  disk     the spill machinery with its slot payloads routed to
           file-backed segment files (``repro_spill_*.npz`` under a
           temp/caller directory) instead of the host RAM dict.  Same
           callbacks, same token contract, same CRC-integrity and
           retry-backoff behavior — only WHERE the host side of the
           callback puts the bytes changes, so every bitwise-gradient
           contract that holds for ``spill`` holds for ``disk`` unchanged.

Multi-tier split (``snaps_in_ram``): a spill store built with
``snaps_in_ram=K`` keeps at most K checkpoint slots resident in the RAM
dict and routes overflow batches to disk files — dolfin-adjoint's
multistage ``snaps_in_ram``/``snaps_on_disk`` shape (SNIPPETS.md snippet
2).  Routing is per write batch (a segment lands wholly in one tier, so a
prefetch usually touches one medium) and per slot on the slot-addressed
revolve path; freeing RAM slots makes room again, so a revolve schedule's
hot window stays in RAM while cold snapshots sink to disk.
``snaps_in_ram=None`` (default) is the historical all-RAM store;
``snaps_in_ram=0`` (what ``make_store("disk")`` configures) is all-disk.
Disk files hold one write batch each (one ``np.savez`` extent, no pickle),
with a slot->file index, a one-file read cache sized for the
segment-aligned access pattern, refcounted deletion, a stale-file sweep on
``set_disk_dir`` (dead runs' ``repro_spill_*.npz`` are removed), and a
``weakref.finalize`` that deletes this store's files (and its own tempdir)
at GC/exit.

Two addressing modes, matching the two checkpoint write paths:

  * slot puts/gets (``put``/``get``/``free``) take a *Python int* slot —
    the trace-time-unrolled revolve schedule addresses checkpoints by step
    index known at trace time;
  * indexed writes (``write_at``) take a *traced* index and thread the
    token explicitly — reads on the scanned paths go through the
    segment-batched ``prefetch``.  (The adaptive forward sweep used to
    ``write_at`` once per attempted step; it now batches accepted steps
    through a device-side staging ring and flushes with ``write_batch``
    once per segment — see ``core/adaptive.py``.)

Segment-batched I/O (``write_batch``/``prefetch``): one callback per
checkpoint *segment* instead of per step.  ``write_batch(token, base, tree)``
stores ``seg`` consecutive slots from leaves stacked on axis 0;
``prefetch(token, base, seg)`` returns slots ``[base, base+seg)`` stacked.
The scanned pnode/adaptive/implicit reverse sweeps use these to cut host
round-trips from O(n_steps) to O(n_segments); token threading is
unchanged, so frees still cannot reorder ahead of reads.

Async overlap (``prefetch_issue``): ``prefetch`` alone is synchronous on
XLA:CPU (``pure_callback`` blocks), so batching wins the callback *count*
but not overlap.  ``prefetch_issue(token, base, seg)`` is the overlap
half: a token-only callback that SUBMITS the host-side gather of
``[base, base+seg)`` to the store's single-worker background executor and
returns immediately; the matching ``prefetch``/``prefetch_checked`` at the
same base consumes the staged rows (``prefetch_hit_cb`` counts the hits)
instead of re-reading storage.  The reverse sweeps issue segment k-1's
gather right after waiting on segment k, so disk/dict I/O overlaps the
adjoint compute of the current segment.  Fault injection, integrity
verification, and retry-backoff stay in the synchronous wait callback (the
background task is a raw gather), so chaos schedules remain deterministic.
Ordering: the issue, the wait, and any later free all ride the one token
chain, and the wait blocks on the background future before returning — so
a free ordered after the wait cannot overtake the read.  Do not order a
free of the same slots BETWEEN an issue and its wait (no caller does).

Payload cap: XLA:CPU copies callback operands/results on the same intra-op
thread pool the callback itself occupies, and once a single buffer is
large enough for that copy to be parallelized (~100 KiB measured on jax
0.4.37) the nested parallel-for deadlocks the pool — the callback never
returns and the program hangs.  ``write_batch``/``prefetch`` therefore
split any segment whose largest per-leaf payload (batch axes included)
exceeds ``_CB_PAYLOAD_CAP`` into multiple token-chained callbacks of
slot-aligned chunks.  ``spill_stats()`` counts every chunk callback, so
the BENCH gates price the real host round-trips.  A single slot bigger
than the cap cannot be split further (warned; the slot-addressed
``put``/``write_at`` paths have the same exposure).

Payload ownership: on jax 0.9 every in-jit ``pure_callback`` reaches
the store through ``pure_callback_impl``, which first ``device_put``s the
operands to the CPU device: an asynchronous copy of the numpy buffer
jaxlib filled for the callback, or, where that buffer is 64-byte aligned,
an alias that holds it (that buffer owns its bytes on XLA:CPU and on a TPU
v5e host alike).  So a write callback receives CPU ``jax.Array``s that
JAX owns and nothing else writes to, and the store keeps those arrays as
they are: holding one keeps its buffer alive, and each slot is a view of
the arrays its write kept, at the slot's index.  The views are made when
a slot is first read, so the write callback
returns without waiting for jax's own (asynchronous) copy of the operands
to land, and copies no payload byte.  The read callbacks hand the stored
bytes back as they are: one slot as a view, slots that sit at even steps
in one kept array as one strided view of it (what a read of a chunk a
write stored gives), anything else through one ``np.stack``.  XLA copies
a callback's results into its own buffers, so nothing aliases the store.
The paths that still copy, each because a view would be wrong or a stack
is the only way to assemble the rows:

  * an operand that is not a ``jax.Array`` (a numpy array, e.g. from a
    caller that invokes a callback body directly, or a jax that passes
    raw buffers): its producer may reuse or write the bytes;
  * a lane-keyed chunk with padding lanes (``None`` keys): a view of a
    real lane would pin the padding lanes' bytes, which no slot accounts
    for, so the real lanes' slots are copied out;
  * a ``corrupt`` write fault: ``FaultPlan.corrupt_arrays`` returns fresh
    corrupted arrays (the clean operand is never written);
  * a read whose rows do not sit at even steps in one buffer (rows from
    disk files, or slots written by different callbacks): one ``np.stack``;
  * a read with a missing slot, a failed checked read, or a lane-keyed
    read with padding lanes: the zero-filled stack the caller masks.

``host_copy_bytes`` counts the payload bytes these paths copy or
zero-fill (the integrity checksum's staging and disk I/O are not
counted), so it reads 0 on an all-RAM sweep of plain slots.  Because a
slot views all that its write kept, the RAM gauge counts each write's
arrays once, for as long as any slot views them: freeing some of a
chunk's slots releases nothing until the last one goes.

Counters: every store keeps its own host-side callback counters
(``store.stats``, keyed by an auto-assigned ``store_id``) and mirrors each
increment into a process-wide aggregate — ``spill_stats()`` returns the
aggregate (the historical API the BENCH_3 gates and per-segment
callback-count tests read), ``per_store_spill_stats()`` the per-store
view.  All counter mutation holds one module lock: XLA executes callbacks
on its own thread pool, so a chunked/vmapped program's callbacks can run
concurrently with each other and with a benchmark's
``reset_spill_stats()`` on the main thread — unlocked dict updates would
lose increments or tear the reset.  Counters count actual EXECUTIONS, not
traces.  ``read_cb``/``write_cb`` count data-carrying round-trips only;
``dispatch_cb`` counts the token-only async-issue callbacks separately so
the BENCH_3 callbacks-per-reverse-pass gates keep their historical
meaning.  ``disk_write_bytes``/``disk_read_bytes`` break the byte traffic
down by medium, ``host_copy_bytes`` counts the store's own payload copies
(see above), and ``ram_bytes_peak`` is a high-water gauge of the RAM
dict (max-merged into the aggregate; zeroed by ``reset_spill_stats``) —
the number the BENCH_6 RAM-budget gate checks.  Attaching a
``repro.obs.FlightRecorder`` via ``bind_obs`` makes every callback
additionally record a ``spill.write``/``spill.read``/``spill.free``/
``spill.dispatch`` trace event carrying the store id, slot base, slot
count, payload bytes, the medium (``medium="ram"|"disk"``) and, on
writes and reads, the bytes the store copied (``host_copy_bytes``) —
recorded purely host-side inside the callbacks that already run, so the
traced program is unchanged and grads stay bitwise identical with obs on.

A checkpoint slot holds one step's state, S bytes, for the explicit RK
and the implicit theta-method sweeps alike (the paper's explicit slot also
holds the step's N_s stage derivatives; the explicit reverse step
rebuilds those from their own linearisations, ``core.integrators``).

Table-2 mapping (see ``repro.mem.model``): the store only changes WHERE
the checkpoint slots live, never how many f-evaluations the policy
performs — spill and disk grads are bitwise-identical to device
grads (tests/test_mem.py, tests/test_longhaul.py).

vmap: the *slot-addressed* mode is not supported under ``vmap`` (the
callback sees one logical index for the whole batch, so per-example
checkpoints would alias — ``core.sweeps.resolve_offload`` rejects it
up front).  The *segment-batched* mode IS (``vmap_method="broadcast_all"``):
one callback serves the entire batch, each slot stores the full batch
block with batch axes leading, so element b's checkpoints occupy index b
of the block — the per-batch-element layout the vmapped segmented sweep
relies on (``core.sweeps``, explicit and implicit alike).  Stores are
per-``odeint``-call objects unless a caller passes its own
(``odeint(offload_store=...)``), so concurrent solves never share keys
(a caller-owned ``disk_dir`` likewise belongs to one live store at a
time — the stale sweep on init assumes any file it finds is from a dead
run).

Per-request lane keys (PR 10, the serving engine's contract): setting
``store.lane_keys = (rid_0, ..., rid_{B-1})`` — one entry per leading
mapped batch lane, ``None`` marking a padding lane — switches the
segment-batched callbacks from whole-batch blocks to per-lane rows keyed
``(rid_b, base + i)``.  Each in-flight request's checkpoint segments are
then independently written, prefetched, and freed: padding lanes store
NOTHING (a half-full bucket costs half the checkpoint bytes), a
departing request's slots are dropped host-side with
``free_request(rid)`` (``slot_census()`` returns to empty once every
lane departed), and ``request_slots(rid)`` counts one request's live
slots.  ``lane_keys`` is consulted at callback EXECUTION time, never at
trace time, so one compiled bucket program serves every batch
composition — the jit cache stays bounded by the bucket set.  Values
pass through the exact same bytes as the unkeyed layout (row ``b`` of
the batch block), so keyed batched solves stay bitwise-identical to the
equivalent unbatched per-request loop.  Only a single mapped axis is
supported (the serving batch); ``free_request`` runs between executions
(host-side, not token-ordered) — never while a solve that still needs
those slots is in flight.

Resilience (PR 8; all dormant-by-default, the plain paths above are
byte-identical when unused):

  * ``integrity=True`` records a crc32 over every slot's CLEAN payload at
    write time; ``prefetch_checked`` re-verifies on read and returns an
    ``ok`` flag alongside the data (False on a missing slot, a checksum
    mismatch, or exhausted read retries), so callers with recompute
    freedom — the scanned implicit adjoint — can ``lax.cond`` into
    re-integrating the segment from its boundary state instead of
    consuming garbage.  Corruption is modeled *at rest*: an injected
    ``spill.write``/``corrupt`` fault flips stored bytes after
    checksumming, which is exactly what the read-side verify catches —
    on the disk tier the flipped bytes are what lands in the segment
    file, so on-disk corruption takes the identical recompute path.
  * reads retry with exponential backoff (host-side ``time.sleep``; never
    in traced code) up to ``max_retries`` times when a ``FaultPlan``
    flakes the attempt — transient faults cost ``retry_cb`` ticks and
    succeed; persistent ones surface as ``ok=False`` (checked) or a
    ``RuntimeError`` (unchecked paths have no recompute fallback).
  * ``effective_tier(tier, fault_plan)`` walks the degradation ladder
    spill -> disk -> host -> device past tiers the plan marks down
    (``FaultSpec("tier.spill", 0, "down")``), recording ``store.degrade``
    obs events; scanned sweeps skip the slot-addressed host tier, so for
    them a downed disk tier degrades straight to device (disk itself IS
    scanned-capable — it's the same callbacks).
"""
from __future__ import annotations

import glob
import itertools
import os
import shutil
import tempfile
import threading
import time
import weakref
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import tree_util as jtu

from repro.obs.profile import host_annotation

PyTree = Any

TIERS = ("device", "host", "spill", "disk")

_TOKEN_SDS = jax.ShapeDtypeStruct((), jnp.float32)

#: per-callback payload cap in bytes, applied to each operand/result leaf
#: with mapped batch axes counted.  Above ~100 KiB the XLA:CPU callback
#: buffer copy is parallelized on the pool the callback blocks, and the
#: program deadlocks (see module docstring); 96 KiB keeps headroom.
_CB_PAYLOAD_CAP = 96 * 1024

#: filename prefix for disk-tier segment files; ``set_disk_dir`` sweeps
#: stale matches (files left by a dead run) before reusing a directory.
_DISK_PREFIX = "repro_spill_"


def is_batch_tracer(x) -> bool:
    """True for a vmap tracer: the only JAX tracer carrying ``batch_dim``
    (jax no longer exports its class)."""
    return isinstance(x, jax.core.Tracer) and hasattr(x, "batch_dim")


def batch_scale(tree: PyTree) -> int:
    """Product of mapped-axis sizes riding the leaves of ``tree`` — the
    factor by which vmap multiplies every callback payload.

    Must be called where the mapped axes are still visible as
    ``BatchTracer``s (``core.sweeps.resolve_offload``, called by the entry
    points): ``custom_vjp`` forwards are
    retraced at *logical* shapes, so by the time ``write_batch`` runs the
    batch axes cannot be recovered from its arguments."""
    def scale(x) -> int:
        s, y, depth = 1, x, 0
        while isinstance(y, jax.core.Tracer) and depth < 8:
            if is_batch_tracer(y):
                bd = y.batch_dim
                if isinstance(bd, int):
                    s *= int(np.shape(y.val)[bd])
                y = y.val
            else:
                nxt = getattr(y, "primal", None)
                if nxt is None:
                    nxt = getattr(y, "val", None)
                if nxt is None or nxt is y:
                    break
                y = nxt
            depth += 1
        return s

    return max((scale(x) for x in jtu.tree_leaves(tree)), default=1)


def _tree_nbytes(tree: PyTree) -> int:
    """Logical payload bytes of a pytree (works on traced values)."""
    return sum(int(np.prod(jnp.shape(x), dtype=np.int64))
               * np.dtype(jnp.result_type(x)).itemsize
               for x in jtu.tree_leaves(tree))


def _chunk_slots(seg: int, per_slot_bytes: int) -> int:
    """Slots per callback so no payload leaf exceeds ``_CB_PAYLOAD_CAP``."""
    if per_slot_bytes <= 0:
        return seg
    m = int(_CB_PAYLOAD_CAP // per_slot_bytes)
    if m < 1:
        import warnings
        warnings.warn(
            f"spill store: a single checkpoint slot is {per_slot_bytes} "
            f"bytes, above the {_CB_PAYLOAD_CAP}-byte per-callback payload "
            "cap; XLA:CPU may deadlock copying it (see "
            "repro.mem.offload docstring)", stacklevel=3)
        return 1
    return min(m, seg)

#: counter keys every SpillStore tracks (per store and in the aggregate):
#: ``*_cb`` counts data-carrying host round-trips, ``*_slots`` checkpoint
#: slots moved (slots/cb = achieved batching factor), ``*_bytes`` payload
#: traffic; ``dispatch_cb`` counts token-only async prefetch issues and
#: ``prefetch_hit_cb`` the waits that consumed a background gather;
#: ``disk_*_bytes`` is the slice of the byte traffic that hit segment
#: files; ``ram_bytes_peak`` is a high-water gauge (max-merged, not
#: summed) of the RAM dict; ``retry_cb`` counts read attempts repeated
#: after an injected flake and ``integrity_fail`` slots that failed their
#: checksum/presence check; ``host_copy_bytes`` counts the payload bytes
#: the store itself copies or zero-fills on the host (module docstring).
_STAT_KEYS = ("write_cb", "read_cb", "free_cb",
              "write_slots", "read_slots", "write_bytes", "read_bytes",
              "dispatch_cb", "prefetch_hit_cb",
              "disk_write_bytes", "disk_read_bytes", "ram_bytes_peak",
              "retry_cb", "integrity_fail", "host_copy_bytes")

#: guards ALL counter mutation and the reset: callbacks execute on XLA's
#: thread pool, concurrently with each other (chunked/vmapped programs)
#: and with a benchmark's ``reset_spill_stats()`` on the main thread.
_STATS_LOCK = threading.RLock()

#: process-wide aggregate (the historical ``spill_stats()`` view) —
#: updated in lockstep with the owning store's per-store dict, and kept
#: separate so traffic survives the (per-odeint-call) store objects.
_AGG: Dict[str, int] = {k: 0 for k in _STAT_KEYS}

#: live stores by id, weakly: stores are per-odeint-call objects, so dead
#: ones drop out of ``per_store_spill_stats()`` while their traffic stays
#: in the aggregate.
_STORES: "weakref.WeakValueDictionary[str, SpillStore]" = \
    weakref.WeakValueDictionary()
_STORE_IDS = itertools.count()


def reset_spill_stats() -> None:
    """Zero the aggregate and every live store's counters atomically (a
    callback running mid-reset sees either all-old or all-new)."""
    with _STATS_LOCK:
        for k in _STAT_KEYS:
            _AGG[k] = 0
        for st in list(_STORES.values()):
            for k in _STAT_KEYS:
                st.stats[k] = 0


def spill_stats() -> Dict[str, int]:
    """Copy of the AGGREGATE spill-store callback counters (every store's
    traffic summed; see ``per_store_spill_stats`` for the breakdown):
    ``*_cb`` counts host round-trips, ``*_slots`` counts checkpoint slots
    moved (so slots/cb is the achieved batching factor), ``*_bytes`` the
    payload traffic."""
    with _STATS_LOCK:
        return dict(_AGG)


def per_store_spill_stats() -> Dict[str, Dict[str, int]]:
    """Counters keyed by ``store_id`` for every live ``SpillStore`` that
    has executed at least one callback since its creation or the last
    reset (all-zero stores are omitted to keep the view readable)."""
    with _STATS_LOCK:
        return {sid: dict(st.stats) for sid, st in sorted(_STORES.items())
                if any(st.stats.values())}


def default_segment(n_steps: int) -> int:
    """Default checkpoint-segment length: ceil(sqrt(n_steps)), the classic
    bandwidth/footprint balance — O(sqrt n) host callbacks per sweep while
    the device-side staging buffer stays O(sqrt n) state vectors (sublinear,
    so spilling still removes the O(n) term from device-live memory)."""
    if n_steps <= 1:
        return 1
    r = int(np.sqrt(n_steps))
    return int(r if r * r >= n_steps else r + 1)


def host_memory_kind() -> Optional[str]:
    """``"pinned_host"`` when the default device can address pinned host
    memory (what ``jax.memory.Space.Host`` maps to), else None."""
    dev = jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    if "pinned_host" in kinds and dev.default_memory().kind != "pinned_host":
        return "pinned_host"
    return None


#: degradation ladder: where a tier falls when a fault plan marks it down
_LADDER = {"spill": "disk", "disk": "host", "host": "device"}


def _crc_leaves(arrs) -> int:
    """One crc32 over the concatenated bytes of a slot's leaves."""
    c = 0
    for a in arrs:
        c = zlib.crc32(np.ascontiguousarray(a).tobytes(), c)
    return c


class _Kept:
    """The arrays one write callback keeps; slots view them at an index
    (``_leaves``).  The numpy views are made on first use, so a write that
    keeps jax's own operands returns without waiting for jax's copy of them
    to the CPU device to land."""

    __slots__ = ("arrays", "nbytes", "_views")

    def __init__(self, arrays):
        self.arrays = list(arrays)
        self.nbytes = sum(int(a.nbytes) for a in self.arrays)
        self._views = None

    def views(self) -> List[np.ndarray]:
        if self._views is None:
            self._views = [np.asarray(a) for a in self.arrays]
        return self._views


def _keep(operands) -> Tuple[_Kept, int]:
    """Keep a write callback's operands, and say how many bytes that
    copied: a ``jax.Array`` as it is (JAX owns its buffer, and holding the
    array keeps it alive), any other operand (a numpy array, which its
    producer may reuse or write to) as a copy."""
    arrays = [a if isinstance(a, jax.Array) else np.array(a)
              for a in operands]
    return _Kept(arrays), sum(int(b.nbytes) for a, b in zip(operands, arrays)
                              if a is not b)


def _leaves(entry) -> List[np.ndarray]:
    """A stored slot's leaves: views, at its index, of the arrays kept."""
    kept, ix = entry
    return [v[ix] for v in kept.views()]


def _root(a):
    """The object that owns the bytes ``a`` views (``a`` itself when it
    owns them)."""
    while isinstance(a, np.ndarray) and a.base is not None:
        a = a.base
    return a


def _addr(a: np.ndarray) -> int:
    return a.__array_interface__["data"][0]


def _strided_view(rows, grid: Tuple[int, ...], axis: int):
    """One read-only view equal to ``np.stack(rows, axis)`` (reshaped so
    the grid's axes replace the stacked one), when the rows — given in C
    order over ``grid`` — sit at even steps in one buffer, as the slots a
    write stored from one chunk do; else None.  Every element of the view
    is an element of some row, so it reads only live memory."""
    r0 = rows[0]
    root, p0 = _root(r0), _addr(r0)
    units = [int(np.prod(grid[j + 1:], dtype=np.int64))
             for j in range(len(grid))]
    steps = [(_addr(rows[u]) - p0) if n > 1 else 0
             for n, u in zip(grid, units)]
    for idx, r in zip(np.ndindex(*grid), rows):
        if (r.shape != r0.shape or r.strides != r0.strides
                or r.dtype != r0.dtype or _root(r) is not root
                or _addr(r) != p0 + sum(i * d for i, d in zip(idx, steps))):
            return None
    return np.lib.stride_tricks.as_strided(
        r0, r0.shape[:axis] + tuple(grid) + r0.shape[axis:],
        r0.strides[:axis] + tuple(steps) + r0.strides[axis:],
        writeable=False)


def _slot_salt(slot) -> int:
    """Deterministic int salt for a slot key: ints pass through, the
    lane-keyed tuples (request_id, step) hash via crc32 of their repr —
    stable across processes (unlike ``hash``), so injected corruption
    stays replayable."""
    if isinstance(slot, (int, np.integer)):
        return int(slot)
    return zlib.crc32(repr(slot).encode("utf-8"))


def _cleanup_disk(paths: List[str], root: Optional[str], owned: bool) -> None:
    """weakref.finalize target: delete this store's segment files and, if
    the store created its own tempdir, the directory itself.  Module-level
    (no bound self) so the finalizer does not keep the store alive."""
    for p in paths:
        try:
            os.unlink(p)
        except OSError:
            pass
    if owned and root:
        shutil.rmtree(root, ignore_errors=True)


def _shutdown_exec(ex) -> None:
    """weakref.finalize target for the prefetch executor."""
    ex.shutdown(wait=False)


def effective_tier(tier: Optional[str], fault_plan=None, *,
                   scanned: bool = False, obs=None) -> Optional[str]:
    """Walk the degradation ladder (spill -> disk -> host -> device) past
    tiers a ``FaultPlan`` marks unavailable (``FaultSpec("tier.<t>", 0,
    "down")``).  Returns the first available tier; each hop is recorded as
    a ``store.degrade`` obs event when a recorder is given.
    ``scanned=True`` says the caller is a scanned segment-batched sweep,
    which cannot use the slot-addressed host tier — a downed disk tier
    then degrades straight to device (disk itself is scanned-capable, so
    spill -> disk holds for scanned sweeps too)."""
    if fault_plan is None or tier in (None, "device"):
        return tier
    cur = tier
    while cur not in (None, "device") and fault_plan.tier_disabled(cur):
        nxt = "device" if (scanned and cur == "disk") else _LADDER[cur]
        if obs is not None:
            obs.record("store.degrade", requested=tier, from_tier=cur,
                       to_tier=nxt, scanned=bool(scanned))
        cur = nxt
    return cur


def make_store(tier: Optional[str], *, fault_plan=None,
               integrity: bool = False, max_retries: int = 3,
               retry_backoff_s: float = 1e-3,
               snaps_in_ram: Optional[int] = None,
               disk_dir: Optional[str] = None) -> "CheckpointStore":
    """Build a store for ``tier``.  The resilience knobs apply to the
    spill/disk tiers only (the others have no host round-trips to
    protect): ``fault_plan`` arms the injection hooks inside the
    callbacks, ``integrity`` turns on per-slot crc32 checksums (required
    by ``prefetch_checked``), ``max_retries``/``retry_backoff_s`` bound
    the read retry loop.  ``snaps_in_ram`` caps the RAM-resident slot
    count of a ``spill`` store (overflow sinks to disk files; the
    dolfin-adjoint multistage split — ``make_store("disk")`` is the
    ``snaps_in_ram=0`` corner) and ``disk_dir`` pins the segment files to
    a caller-owned directory (stale files from dead runs are swept;
    default is a self-cleaning tempdir).  ``store.requested_tier`` always
    records what the caller asked for, even after a ladder degrade
    upstream."""
    if tier in (None, "device"):
        st: CheckpointStore = DeviceStore()
    elif tier == "host":
        st = HostStore()
    elif tier in ("spill", "disk"):
        sp = DiskStore() if tier == "disk" else SpillStore()
        sp.fault_plan = fault_plan
        sp.integrity = bool(integrity)
        sp.max_retries = int(max_retries)
        sp.retry_backoff_s = float(retry_backoff_s)
        if tier == "spill" and snaps_in_ram is not None:
            sp.snaps_in_ram = int(snaps_in_ram)
        if disk_dir is not None:
            sp.set_disk_dir(disk_dir)
        st = sp
    else:
        raise ValueError(f"unknown offload tier {tier!r}; one of {TIERS}")
    st.requested_tier = tier
    return st


class CheckpointStore:
    """Common interface; concrete tiers override the transfer points.

    Forward sweep:   put(slot, tree)* -> pack() returned as residuals.
    Reverse sweep:   unpack(res, slots); then get/put/free in any order the
    schedule demands (bwd puts come from revolve "advance" actions).
    Scanned sweeps:  token = init_token(); token = write_at(token, i, tree)
    or token = write_batch(token, base, stacked); token, stacked =
    prefetch(token, base, seg) — token must ride the scan carry and cross
    fwd->bwd through the residuals.
    """

    tier = "device"

    def __init__(self):
        self._vals: Dict[int, PyTree] = {}
        self._order: List[int] = []
        self.effective_tier = self.tier
        self.requested_tier = self.tier
        self.store_id = f"{self.tier}-{next(_STORE_IDS)}"
        self._obs = None

    def bind_obs(self, recorder) -> None:
        """Attach a ``repro.obs.FlightRecorder``.  Device/host tiers
        record trace-time ``store.put``/``store.get``/``store.free``
        events (the schedule — once per compilation); the spill tier
        additionally records runtime ``spill.*`` events from inside its
        host callbacks (once per execution)."""
        self._obs = recorder

    def _note(self, kind: str, slot, tree: PyTree = None) -> None:
        if self._obs is None:
            return
        self._obs.record(kind, store=self.store_id,
                         tier=self.effective_tier, slot=slot,
                         bytes=_tree_nbytes(tree) if tree is not None else 0)

    # -- slot-addressed (trace-time revolve schedule) ----------------------
    def put(self, slot: int, tree: PyTree) -> None:
        self._note("store.put", slot, tree)
        if slot not in self._vals:
            self._order.append(slot)
        self._vals[slot] = self._to_store(tree)

    def get(self, slot: int) -> PyTree:
        self._note("store.get", slot, self._vals[slot])
        return self._from_store(self._vals[slot])

    def free(self, slot: int) -> None:
        self._note("store.free", slot)
        self._vals.pop(slot, None)

    def pack(self) -> PyTree:
        """Residual pytree carrying the forward sweep's checkpoints (in put
        order — the slot keys themselves are trace-time ints the reverse
        rule recomputes and passes back to ``unpack``)."""
        return tuple(self._vals[s] for s in self._order)

    def unpack(self, res: PyTree, slots) -> None:
        self._vals = dict(zip(slots, res))
        self._order = list(slots)

    # -- index-addressed (scanned writes with a traced index) --------------
    def init_token(self):
        return jnp.zeros((), jnp.float32)

    def write_at(self, token, idx, tree: PyTree, keep=None):
        raise NotImplementedError(
            f"offload tier {self.tier!r} does not support scanned "
            "(traced-index) checkpoint writes; use 'spill' or 'disk'")

    # -- segment-batched (one callback per checkpoint segment) -------------
    def write_batch(self, token, base, tree: PyTree):
        raise NotImplementedError(
            f"offload tier {self.tier!r} does not support segment-batched "
            "checkpoint writes; use 'spill' or 'disk'")

    def prefetch(self, token, base, seg: int):
        raise NotImplementedError(
            f"offload tier {self.tier!r} does not support segment "
            "prefetch; use 'spill' or 'disk'")

    def prefetch_issue(self, token, base, seg: int):
        """Async-dispatch hook; a no-op on tiers without host I/O."""
        return token

    # -- transfer points ----------------------------------------------------
    def _to_store(self, tree: PyTree) -> PyTree:
        return tree

    def _from_store(self, tree: PyTree) -> PyTree:
        return tree


class DeviceStore(CheckpointStore):
    tier = "device"


class HostStore(CheckpointStore):
    """Pinned-host residuals via memory-kind transfer."""

    tier = "host"

    def __init__(self):
        super().__init__()
        if host_memory_kind() is None:
            raise RuntimeError(
                "offload='host' needs a pinned_host memory space, which "
                f"{jax.devices()[0].device_kind!r} does not expose; use "
                "offload='spill' or 'disk'")

    def _to_store(self, tree: PyTree) -> PyTree:
        return jax.device_put(tree, jax.memory.Space.Host)

    def _from_store(self, tree: PyTree) -> PyTree:
        return jax.device_put(tree, jax.memory.Space.Device)


class SpillStore(CheckpointStore):
    """Host-side spill through token-threaded pure_callback, with slot
    payloads split between a RAM dict and disk segment files.

    The store object itself is a static (nondiff) argument of the
    ``custom_vjp`` that uses it, so the same instance — and the same host
    state — is visible to both the fwd and bwd rules.  Leaf shape/dtype
    metadata is recorded at put-trace time (object attributes persist from
    the fwd trace to the bwd trace) so reads know their result shapes.

    ``snaps_in_ram`` governs the RAM/disk routing (see module docstring);
    all host-side slot state (``_host``, ``_disk`` index, file-slot
    refcounts, the read cache) is guarded by ``_io_lock`` because the
    background prefetch executor gathers concurrently with XLA's callback
    threads.
    """

    tier = "spill"

    def __init__(self):
        super().__init__()
        #: slot -> (_Kept, index): the slot's leaves are views of the
        #: kept arrays at that index (``_leaves``)
        self._host: Dict[Any, Tuple[_Kept, tuple]] = {}
        self._meta: Dict[Any, Tuple[Any, Tuple[jax.ShapeDtypeStruct, ...]]] = {}
        self._tok = None
        self.effective_tier = self.tier
        #: per-store callback counters (see module docstring); mutation
        #: holds _STATS_LOCK and mirrors into the _AGG view
        self.stats: Dict[str, int] = {k: 0 for k in _STAT_KEYS}
        _STORES[self.store_id] = self
        #: vmap payload multiplier for the chunking decision — set by the
        #: odeint entry point via ``batch_scale(...)`` (mapped axes are
        #: invisible by the time write_batch/prefetch are traced; see
        #: ``batch_scale``).
        self.payload_scale = 1
        #: per-request lane keys (serving; see module docstring): a tuple
        #: with one request id per leading mapped batch lane (None =
        #: padding lane, stores nothing).  Consulted at callback
        #: EXECUTION time — mutate between executions to re-key the same
        #: compiled program for a new batch composition.
        self.lane_keys: Optional[Tuple[Any, ...]] = None
        #: resilience knobs (see ``make_store``); all dormant by default —
        #: with fault_plan=None and integrity=False the callbacks execute
        #: the exact pre-PR-8 byte sequence
        self.fault_plan = None
        self.integrity = False
        self.max_retries = 3
        self.retry_backoff_s = 1e-3
        #: per-slot crc32 over the CLEAN payload, recorded at write time
        #: when ``integrity`` is on (host-side dict like ``_host``)
        self._sums: Dict[int, int] = {}
        #: RAM/disk split: at most ``snaps_in_ram`` slots in ``_host``
        #: (None = unlimited — the historical all-RAM store)
        self.snaps_in_ram: Optional[int] = None
        self._ram_bytes = 0
        #: id(_Kept) -> [kept, slots viewing it]: a slot pins all the
        #: arrays its write kept, so RAM bytes count each _Kept once
        #: while any slot views it
        self._pins: Dict[int, list] = {}
        self._disk_dir: Optional[str] = None
        self._disk_dir_owned = False
        self._disk: Dict[int, str] = {}            # slot -> segment file
        self._file_slots: Dict[str, set] = {}      # file -> live slots
        self._created: List[str] = []              # files we own (finalizer)
        self._read_cache: Tuple[Optional[str], Optional[dict]] = (None, None)
        self._file_seq = itertools.count()
        self.swept_files = 0
        #: serializes host-side slot-state access between XLA callback
        #: threads and the background prefetch executor
        self._io_lock = threading.RLock()
        self._exec = None
        self._inflight: Dict[int, Any] = {}        # chunk base -> Future

    # -- disk backend (host-side; callers hold no lock, these take it) ------
    def set_disk_dir(self, path: str) -> None:
        """Pin disk-tier segment files to a caller-owned directory.  Any
        stale ``repro_spill_*.npz`` left by a dead run is swept (counted
        in ``self.swept_files``); this store's own files are still removed
        at GC, but the directory itself is left alone."""
        os.makedirs(path, exist_ok=True)
        swept = 0
        for p in glob.glob(os.path.join(path, _DISK_PREFIX + "*.npz")):
            try:
                os.unlink(p)
                swept += 1
            except OSError:  # pragma: no cover - races with external rm
                pass
        self.swept_files = swept
        self._disk_dir = path
        self._disk_dir_owned = False
        weakref.finalize(self, _cleanup_disk, self._created, path, False)

    def _disk_root(self) -> str:
        if self._disk_dir is None:
            self._disk_dir = tempfile.mkdtemp(prefix="repro-spill-")
            self._disk_dir_owned = True
            weakref.finalize(self, _cleanup_disk, self._created,
                             self._disk_dir, True)
        return self._disk_dir

    def _pin(self, kept: _Kept, n: int) -> None:
        # under _io_lock; n = +1 when a slot takes a view of ``kept``, -1
        # when it lets it go
        ent = self._pins.setdefault(id(kept), [kept, 0])
        if ent[1] == 0:
            self._ram_bytes += kept.nbytes
        ent[1] += n
        if ent[1] == 0:
            del self._pins[id(kept)]
            self._ram_bytes -= kept.nbytes

    def _host_insert(self, slot, entry) -> None:
        # under _io_lock
        old = self._host.get(slot)
        self._host[slot] = entry
        self._pin(entry[0], 1)
        if old is not None:
            self._pin(old[0], -1)
        with _STATS_LOCK:
            if self._ram_bytes > self.stats["ram_bytes_peak"]:
                self.stats["ram_bytes_peak"] = self._ram_bytes
            if self._ram_bytes > _AGG["ram_bytes_peak"]:
                _AGG["ram_bytes_peak"] = self._ram_bytes

    def _drop_slot(self, slot) -> None:
        """Remove every copy of ``slot`` (RAM and disk); deletes a segment
        file once its last live slot is dropped."""
        with self._io_lock:
            old = self._host.pop(slot, None)
            if old is not None:
                self._pin(old[0], -1)
            path = self._disk.pop(slot, None)
            if path is not None:
                live = self._file_slots.get(path)
                if live is not None:
                    live.discard(slot)
                    if not live:
                        self._file_slots.pop(path, None)
                        if self._read_cache[0] == path:
                            self._read_cache = (None, None)
                        try:
                            os.unlink(path)
                        except OSError:  # pragma: no cover
                            pass

    def _ram_has_room(self, slots) -> bool:
        # under _io_lock
        if self.snaps_in_ram is None:
            return True
        projected = len(self._host) + sum(1 for s in slots
                                          if s not in self._host)
        return projected <= self.snaps_in_ram

    def _disk_write_rows(self, rows: Dict[Any, tuple]) -> int:
        # under _io_lock; one savez extent per write batch, no pickle
        path = os.path.join(
            self._disk_root(),
            f"{_DISK_PREFIX}{self.store_id}_{next(self._file_seq)}.npz")
        payload = {f"s{slot}_l{k}": a
                   for slot, entry in rows.items()
                   for k, a in enumerate(_leaves(entry))}
        np.savez(path, **payload)
        self._created.append(path)
        self._file_slots[path] = set(rows)
        for slot in rows:
            # a rewrite supersedes any prior copy in either medium
            self._drop_slot(slot)
            self._disk[slot] = path
            self._file_slots[path].add(slot)
        return sum(a.nbytes for a in payload.values())

    def _store_rows(self, rows: Dict[Any, tuple]) -> Tuple[str, int]:
        """Route a batch of slots to RAM or disk per ``snaps_in_ram``.
        Returns ``(medium, disk_bytes)`` for counters/obs."""
        if not rows:
            return "ram", 0
        with self._io_lock:
            if self._ram_has_room(rows):
                for slot, entry in rows.items():
                    if slot in self._disk:
                        self._drop_slot(slot)
                    self._host_insert(slot, entry)
                return "ram", 0
            dbytes = self._disk_write_rows(rows)
        with _STATS_LOCK:
            self.stats["disk_write_bytes"] += dbytes
            _AGG["disk_write_bytes"] += dbytes
        return "disk", dbytes

    def _disk_read_slot(self, slot):
        # under _io_lock; one-file cache matches the segment-aligned
        # access pattern (a prefetch chunk was written as one file)
        path = self._disk.get(slot)
        if path is None:
            return None
        cpath, cdata = self._read_cache
        if cpath != path:
            with np.load(path) as z:
                cdata = {k: z[k] for k in z.files}
            self._read_cache = (path, cdata)
        leaves, k = [], 0
        while f"s{slot}_l{k}" in cdata:
            leaves.append(cdata[f"s{slot}_l{k}"])
            k += 1
        return leaves or None

    def _slot_read_any(self, slot):
        """One slot's leaves from whichever medium holds it (None if
        missing).  Second element reports disk bytes moved."""
        with self._io_lock:
            entry = self._host.get(slot)
            if entry is not None:
                return _leaves(entry), 0
            leaves = self._disk_read_slot(slot)
            if leaves is None:
                return None, 0
            return leaves, sum(a.nbytes for a in leaves)

    def _gather_rows(self, base: int, seg: int):
        """Host-side bulk read of ``seg`` consecutive slots (missing ->
        None rows).  Runs on the background executor (via
        ``prefetch_issue``) or synchronously inside the wait callback —
        raw I/O only, no fault ticks, so chaos stays deterministic."""
        rows, dbytes = [], 0
        with self._io_lock:
            for i in range(seg):
                leaves, db = self._slot_read_any(base + i)
                rows.append(leaves)
                dbytes += db
        return rows, dbytes

    @staticmethod
    def _check_lanes(bnd: int, shape, keys) -> None:
        """lane_keys requires exactly ONE mapped axis whose size matches
        the key tuple — anything else is a serving-engine wiring bug."""
        if bnd != 1:
            raise ValueError(
                f"lane_keys requires exactly one mapped batch axis, got "
                f"{bnd} (nest the request batch as the single vmapped "
                "axis)")
        if shape[0] != len(keys):
            raise ValueError(
                f"lane_keys has {len(keys)} entries but the mapped batch "
                f"axis has {shape[0]} lanes")

    def _gather_rows_keyed(self, base: int, seg: int, keys):
        """Keyed counterpart of ``_gather_rows``: per-lane rows
        ``[(keys[b], base+i) for i in range(seg)]`` (None rows for
        missing slots and padding lanes)."""
        rows, dbytes = [], 0
        with self._io_lock:
            for rk in keys:
                lane = []
                for i in range(seg):
                    if rk is None:
                        lane.append(None)
                        continue
                    leaves, db = self._slot_read_any((rk, base + i))
                    lane.append(leaves)
                    dbytes += db
                rows.append(lane)
        return rows, dbytes

    def slot_census(self) -> Dict[str, int]:
        """Live slot counts by medium (tests/benchmarks introspection)."""
        with self._io_lock:
            return {"ram": len(self._host), "disk": len(self._disk),
                    "disk_files": len(self._file_slots)}

    def request_slots(self, request_id) -> int:
        """Live lane-keyed slots held for one request (both media)."""
        with self._io_lock:
            return sum(1 for k in set(self._host) | set(self._disk)
                       if isinstance(k, tuple) and k[0] == request_id)

    def free_request(self, request_id) -> int:
        """Drop every lane-keyed checkpoint slot of a departed request
        (both media; segment files are deleted once their last live slot
        goes).  Host-side and NOT token-ordered: the serving engine calls
        it between executions, never while a solve that still needs the
        slots is in flight.  Returns the number of slots dropped."""
        with self._io_lock:
            victims = [k for k in set(self._host) | set(self._disk)
                       if isinstance(k, tuple) and k[0] == request_id]
        for k in victims:
            self._drop_slot(k)
            self._sums.pop(k, None)
        if victims:
            self._tally_counter("free_cb")
        if self._obs is not None:
            self._obs.record("spill.free_request", _runtime=True,
                             store=self.store_id, request=request_id,
                             slots=len(victims))
        return len(victims)

    def _ensure_exec(self):
        if self._exec is None:
            from concurrent.futures import ThreadPoolExecutor
            self._exec = ThreadPoolExecutor(
                max_workers=1,
                thread_name_prefix=f"spill-prefetch-{self.store_id}")
            weakref.finalize(self, _shutdown_exec, self._exec)
        return self._exec

    # -- resilience helpers (host-side, called from the callbacks) -----------
    def _tally_counter(self, key: str, n: int = 1) -> None:
        with _STATS_LOCK:
            self.stats[key] += n
            _AGG[key] += n

    def _seal(self, spec, slot, entry):
        """Checksum one slot's clean payload (with ``integrity``), then
        apply a ticked ``spill.write`` fault to it: ``drop`` loses it in
        transit (None, nothing stored), ``corrupt`` returns fresh,
        deterministically flipped copies — the corruption-at-rest model
        the read-side verify detects (on the disk tier the flipped bytes
        land in the segment file).  Returns ``(entry, bytes copied)``."""
        if self.integrity:
            self._sums[slot] = _crc_leaves(_leaves(entry))
        if spec is None:
            return entry, 0
        if spec.kind == "drop":
            self._drop_slot(slot)
            return None, 0
        if spec.kind == "corrupt":
            bad = _Kept(self.fault_plan.corrupt_arrays(
                _leaves(entry), salt=_slot_salt(slot)))
            return (bad, (...,)), bad.nbytes
        return entry, 0

    def _read_attempt_ok(self, base: int) -> bool:
        """One logical read, retried with exponential backoff while the
        fault plan flakes it.  Every attempt ticks ``spill.read`` (so a
        spec's ``count`` window spans retries: transient faults are
        escaped by retrying, persistent ones exhaust the budget).
        Returns False only when ``max_retries`` retries all flaked."""
        if self.fault_plan is None:
            return True
        for attempt in range(self.max_retries + 1):
            spec = self.fault_plan.tick("spill.read")
            if spec is None or spec.kind != "flake":
                return True
            if attempt == self.max_retries:
                return False
            self._tally_counter("retry_cb")
            if self._obs is not None:
                self._obs.record("spill.retry", _runtime=True,
                                 store=self.store_id, base=base,
                                 attempt=attempt + 1)
            time.sleep(self.retry_backoff_s * (2 ** attempt))
        return False

    def _leaves_intact(self, slot: int, leaves) -> bool:
        """Present and (when integrity is on) matching the write-time
        checksum.  A slot written before integrity was enabled has no
        recorded sum and passes (nothing to verify against)."""
        if leaves is None:
            return False
        if not self.integrity:
            return True
        want = self._sums.get(slot)
        return want is None or _crc_leaves(leaves) == want

    def _slot_intact(self, slot: int) -> bool:
        leaves, _ = self._slot_read_any(slot)
        return self._leaves_intact(slot, leaves)

    # -- counting + obs (host-side, called from the callbacks) --------------
    def _tally(self, direction: str, *, slots: int, nbytes: int, base,
               medium: str = "ram", disk_bytes: int = 0, copied: int = 0):
        """Bump this store's counters and the aggregate in lockstep (under
        the module lock — see module docstring), then record an obs event
        if a recorder is bound.  Runs on XLA's callback thread."""
        with _STATS_LOCK:
            if direction == "free":
                self.stats["free_cb"] += 1
                _AGG["free_cb"] += 1
            else:
                keys = [(f"{direction}_cb", 1),
                        (f"{direction}_slots", slots),
                        (f"{direction}_bytes", nbytes),
                        ("host_copy_bytes", copied)]
                if direction == "read" and disk_bytes:
                    keys.append(("disk_read_bytes", disk_bytes))
                for key, n in keys:
                    self.stats[key] += n
                    _AGG[key] += n
        if self._obs is not None:
            extra = {} if direction == "free" else {"host_copy_bytes": copied}
            self._obs.record(f"spill.{direction}", _runtime=True,
                             store=self.store_id, base=base,
                             slots=slots, bytes=nbytes, medium=medium,
                             **extra)

    # -- host-side callbacks (never traced) ---------------------------------
    def _write_slot(self, spec, slot: int, leaves) -> None:
        """Store one slot from the callback's operands (kept as they are
        where JAX owns them — module docstring) and count the write."""
        kept, copied = _keep(leaves)
        entry, c = self._seal(spec, slot, (kept, (...,)))
        medium = "ram"
        if entry is not None:
            medium, _ = self._store_rows({slot: entry})
        self._tally("write", slots=1, nbytes=kept.nbytes, base=slot,
                    medium=medium, copied=copied + c)

    def _cb_write(self, token, slot, *leaves):
        with host_annotation("spill/write"):
            spec = (self.fault_plan.tick("spill.write")
                    if self.fault_plan is not None else None)
            self._write_slot(spec, int(slot), leaves)
        return np.float32(0)

    def _cb_write_if(self, token, slot, keep, *leaves):
        with host_annotation("spill/write"):
            spec = (self.fault_plan.tick("spill.write")
                    if self.fault_plan is not None else None)
            if bool(keep):
                self._write_slot(spec, int(slot), leaves)
            else:  # masked out: the round-trip still happened
                self._tally("write", slots=0, nbytes=0, base=int(slot))
        return np.float32(0)

    def _cb_read(self):
        def read(token, slot):
            with host_annotation("spill/read"):
                if not self._read_attempt_ok(int(slot)):
                    # the slot-addressed schedule has no recompute
                    # fallback; a persistent read failure is fatal here
                    raise RuntimeError(
                        f"spill store: read of slot {int(slot)} still "
                        f"failing after {self.max_retries} retries")
                leaves, dbytes = self._slot_read_any(int(slot))
                if leaves is None:
                    # a schedule bug or a reordered free — fail loudly
                    # rather than silently contributing zero gradients
                    raise KeyError(f"spill store: slot {int(slot)} read "
                                   "before it was written (or after free)")
                if not self._leaves_intact(int(slot), leaves):
                    self._tally_counter("integrity_fail")
                    raise RuntimeError(
                        f"spill store: slot {int(slot)} failed its "
                        "integrity check (checksum mismatch) and the "
                        "slot-addressed path has no recompute fallback")
                arrs = tuple(np.asarray(x) for x in leaves)
                self._tally("read", slots=1,
                            nbytes=sum(a.nbytes for a in arrs),
                            base=int(slot),
                            medium="disk" if dbytes else "ram",
                            disk_bytes=dbytes)
                return (np.float32(0),) + arrs
        return read

    def _cb_free(self, token, slot):
        with host_annotation("spill/free"):
            self._drop_slot(int(slot))
            self._tally("free", slots=1, nbytes=0, base=int(slot))
        return np.float32(0)

    def _cb_write_batch(self, token, base, *stacked):
        """ONE host round-trip storing seg consecutive slots (leaves arrive
        stacked on the segment axis).

        Batch-aware: under ``vmap`` (``vmap_method="broadcast_all"``) every
        argument arrives broadcast to the full batch shape — the token's
        ndim IS the number of mapped axes (its logical shape is scalar), so
        the segment axis sits at ``np.ndim(token)`` and each slot stores
        the whole batch block ``arr[..., i, :]``.  One callback serves the
        entire batch and batch elements never alias: element b's
        checkpoints live at index b of its slot's block (the
        per-batch-element key scheme).

        With ``lane_keys`` set the batch block is instead split into
        per-lane rows keyed ``(lane_keys[b], base + i)`` — same bytes,
        request-addressable slots (padding lanes store nothing)."""
        with host_annotation("spill/write_batch"):
            spec = (self.fault_plan.tick("spill.write")
                    if self.fault_plan is not None else None)
            bnd = np.ndim(token)
            seg = int(np.shape(stacked[0])[bnd])
            base = int(np.ravel(base)[0])  # broadcast copies are identical
            kept, copied = _keep(stacked)
            keys = self.lane_keys
            if keys is not None:
                self._check_lanes(bnd, np.shape(stacked[0]), keys)
                slots = [((rk, base + i), (b, i, ...))
                         for b, rk in enumerate(keys) if rk is not None
                         for i in range(seg)]
            else:
                sl = (slice(None),) * bnd
                slots = [(base + i, sl + (i, ...)) for i in range(seg)]
            # a view of a real lane would pin the padding lanes' bytes,
            # which no slot accounts for: copy each slot out instead
            padded = keys is not None and any(rk is None for rk in keys)
            rows: Dict[Any, tuple] = {}
            for key, ix in slots:
                entry = (kept, ix)
                if padded:
                    own, c = _keep(_leaves(entry))
                    entry = (own, (...,))
                    copied += c
                entry, c = self._seal(spec, key, entry)
                copied += c
                if entry is not None:
                    rows[key] = entry
            medium, _ = self._store_rows(rows)
            self._tally("write", slots=seg, nbytes=kept.nbytes, base=base,
                        medium=medium, copied=copied)
        return np.zeros(np.shape(token), np.float32)

    def _cb_dispatch(self, seg, m):
        """Token-only callback: SUBMIT the gather of ``[base, base+seg)``
        (in the same slot-aligned chunks the wait will use) to the
        background executor and return.  Raw I/O only — faults, integrity,
        and retries stay in the synchronous wait callback."""
        def dispatch(token, base):
            with host_annotation("spill/dispatch"):
                base = int(np.ravel(base)[0])
                ex = self._ensure_exec()
                keys = self.lane_keys  # snapshot: stable per execution
                for o in range(0, seg, m):
                    b = base + o
                    if keys is not None:
                        self._inflight[b] = ex.submit(
                            self._gather_rows_keyed, b, min(m, seg - o),
                            keys)
                    else:
                        self._inflight[b] = ex.submit(
                            self._gather_rows, b, min(m, seg - o))
                self._tally_counter("dispatch_cb")
                if self._obs is not None:
                    self._obs.record("spill.dispatch", _runtime=True,
                                     store=self.store_id, base=base,
                                     slots=seg)
            return np.zeros(np.shape(token), np.float32)
        return dispatch

    def _cb_prefetch(self, seg, checked=False):
        def fetch(token, base):
            with host_annotation("spill/prefetch"):
                _, sds = self._meta["idx"]
                bshape = np.shape(token)  # mapped axes (see _cb_write_batch)
                bnd = len(bshape)
                base = int(np.ravel(base)[0])
                sl = (slice(None),) * bnd
                ok = True
                if not self._read_attempt_ok(base):
                    if not checked:
                        raise RuntimeError(
                            f"spill store: prefetch at base {base} still "
                            f"failing after {self.max_retries} retries and "
                            "this path has no recompute fallback")
                    ok = False  # checked caller recomputes the segment
                # consume a background gather staged by prefetch_issue, if
                # one is in flight for this chunk; fall back to reading
                # storage synchronously (also on background I/O errors —
                # the sync path then surfaces them deterministically)
                keys = self.lane_keys
                if keys is not None:
                    self._check_lanes(len(bshape), bshape, keys)
                rows, dbytes, hit = None, 0, False
                fut = self._inflight.pop(base, None)
                if fut is not None:
                    try:
                        rows, dbytes = fut.result()
                        hit = True
                    except Exception:  # pragma: no cover - backend I/O race
                        rows = None
                if rows is None:
                    rows, dbytes = (
                        self._gather_rows_keyed(base, seg, keys)
                        if keys is not None
                        else self._gather_rows(base, seg))
                if hit:
                    self._tally_counter("prefetch_hit_cb")
                # the rows in C order over the grid that replaces the
                # slot axis: (lane, slot) when keyed, (slot,) otherwise
                if keys is not None:
                    grid, axis = (len(keys), seg), 0
                    flat = [r for lane in rows for r in lane]
                else:
                    grid, axis = (seg,), bnd
                    flat = rows
                out, copied = [], 0
                for k, s in enumerate(sds):
                    shape = bshape + (seg,) + tuple(s.shape)
                    leaf = [None if r is None else r[k] for r in flat]
                    if ok and all(r is not None for r in leaf):
                        # every row in RAM or read: hand the stored bytes
                        # back, as one view when they sit in one chunk
                        stack = _strided_view(leaf, grid, axis)
                        if stack is None:
                            stack = np.stack(leaf, axis).reshape(shape)
                            copied += stack.nbytes
                    else:  # missing slots and padding lanes read zeros
                        stack = np.zeros(shape, s.dtype)
                        copied += stack.nbytes
                        if ok:
                            for idx, r in zip(np.ndindex(*grid), leaf):
                                if r is not None:
                                    stack[sl[:axis] + idx] = r
                    out.append(stack)
                if checked and ok:
                    if keys is not None:
                        for b, rk in enumerate(keys):
                            if rk is None:  # padding: legitimately absent
                                continue
                            for i in range(seg):
                                if self._leaves_intact((rk, base + i),
                                                       rows[b][i]):
                                    continue
                                ok = False
                                self._tally_counter("integrity_fail")
                                if self._obs is not None:
                                    self._obs.record(
                                        "spill.integrity", _runtime=True,
                                        store=self.store_id,
                                        slot=[rk, base + i], base=base)
                    else:
                        for i in range(seg):
                            if not self._leaves_intact(base + i, rows[i]):
                                ok = False
                                self._tally_counter("integrity_fail")
                                if self._obs is not None:
                                    self._obs.record(
                                        "spill.integrity", _runtime=True,
                                        store=self.store_id, slot=base + i,
                                        base=base)
                self._tally("read", slots=seg,
                            nbytes=sum(a.nbytes for a in out), base=base,
                            medium=("disk" if dbytes else "ram") if ok
                            else "ram",
                            disk_bytes=dbytes, copied=copied)
                res = (np.zeros(bshape, np.float32),)
                if checked:
                    res = res + (np.full(bshape, ok, bool),)
                return res + tuple(out)
        return fetch

    # -- metadata ------------------------------------------------------------
    def _record(self, key, tree: PyTree):
        leaves, treedef = jtu.tree_flatten(tree)
        sds = tuple(jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))
                    for x in leaves)
        self._meta[key] = (treedef, sds)
        return leaves

    def _per_slot_chunk(self, sds, seg: int) -> int:
        per_slot = max((int(np.prod(s.shape, dtype=np.int64))
                        * np.dtype(s.dtype).itemsize)
                       for s in sds) * self.payload_scale if sds else 0
        return _chunk_slots(seg, per_slot)

    # -- slot-addressed ------------------------------------------------------
    def put(self, slot: int, tree: PyTree) -> None:
        if self._tok is None:
            self._tok = self.init_token()
        leaves = self._record("slot", tree)
        self._tok = jax.pure_callback(
            self._cb_write, _TOKEN_SDS, self._tok, np.int32(slot), *leaves)

    def get(self, slot: int) -> PyTree:
        # reads also return a fresh token that subsequent free/put calls
        # consume: without that anti-dependency edge the scheduler could
        # legally run a free (or an overwriting put) before the read
        treedef, sds = self._meta["slot"]
        out = jax.pure_callback(
            self._cb_read(), (_TOKEN_SDS,) + sds,
            self._tok, np.int32(slot))
        self._tok = out[0]
        return jtu.tree_unflatten(treedef, out[1:])

    def free(self, slot: int) -> None:
        self._tok = jax.pure_callback(
            self._cb_free, _TOKEN_SDS, self._tok, np.int32(slot))

    def pack(self) -> PyTree:
        return self._tok

    def unpack(self, res: PyTree, slots) -> None:
        self._tok = res

    # -- index-addressed -----------------------------------------------------
    def write_at(self, token, idx, tree: PyTree, keep=None):
        leaves = self._record("idx", tree)
        if keep is None:
            return jax.pure_callback(
                self._cb_write, _TOKEN_SDS, token, idx, *leaves)
        return jax.pure_callback(
            self._cb_write_if, _TOKEN_SDS, token, idx, keep, *leaves)

    # -- segment-batched -----------------------------------------------------
    def write_batch(self, token, base, tree: PyTree):
        """Store slots ``[base, base+seg)`` in one callback per
        payload-capped chunk (one total in the common case).  ``tree``
        leaves carry the segment on axis 0 (``seg`` = the static leading
        dim, as stacked by a per-segment inner scan); ``base`` may be
        traced.  Returns a fresh ordering token."""
        leaves, treedef = jtu.tree_flatten(tree)
        # record PER-SLOT metadata (axis 0 stripped) under the same "idx"
        # key the adaptive write_at path records, so prefetch interoperates
        # with either write path
        sds = tuple(jax.ShapeDtypeStruct(tuple(jnp.shape(x)[1:]),
                                         jnp.result_type(x))
                    for x in leaves)
        self._meta["idx"] = (treedef, sds)
        seg = int(jnp.shape(leaves[0])[0]) if leaves else 1
        m = self._per_slot_chunk(sds, seg)
        tok = token
        for o in range(0, seg, m):
            chunk = [x[o:o + m] for x in leaves]
            tok = jax.pure_callback(self._cb_write_batch, _TOKEN_SDS, tok,
                                    base + o, *chunk,
                                    vmap_method="broadcast_all")
        return tok

    def prefetch_issue(self, token, base, seg: int):
        """Dispatch the host-side gather of slots ``[base, base+seg)``
        onto the store's background executor: ONE token-only callback that
        returns as soon as the work is queued, so the read of the next
        segment overlaps this segment's compute.  The matching
        ``prefetch``/``prefetch_checked`` at the same base consumes the
        staged rows.  Ordering rides the usual token chain — issue before
        wait, frees after the wait (the wait blocks on the background
        future, so a post-wait free cannot overtake the read)."""
        if "idx" not in self._meta:
            return token  # nothing written yet; the wait will read cold
        _, sds = self._meta["idx"]
        m = self._per_slot_chunk(sds, seg)
        return jax.pure_callback(self._cb_dispatch(seg, m), _TOKEN_SDS,
                                 token, base, vmap_method="broadcast_all")

    def prefetch(self, token, base, seg: int):
        """Fetch slots ``[base, base+seg)`` stacked on axis 0 in one
        callback per payload-capped chunk — one total in the common case
        (missing slots read as zeros — the reverse sweeps cond-skip or
        mask them).  Returns ``(token, tree)``; the fresh token orders any
        later frees/overwrites after this read.  When a ``prefetch_issue``
        for the same base is in flight its staged rows are consumed
        instead of re-reading storage (``prefetch_hit_cb``) — the
        double-buffered path; without an issue this is a synchronous
        read."""
        treedef, sds = self._meta["idx"]
        m = self._per_slot_chunk(sds, seg)
        tok, pieces = token, []
        for o in range(0, seg, m):
            mm = min(m, seg - o)
            out_sds = (_TOKEN_SDS,) + tuple(
                jax.ShapeDtypeStruct((mm,) + tuple(s.shape), s.dtype)
                for s in sds)
            out = jax.pure_callback(self._cb_prefetch(mm), out_sds, tok,
                                    base + o, vmap_method="broadcast_all")
            tok = out[0]
            pieces.append(out[1:])
        if len(pieces) == 1:
            stacked = pieces[0]
        else:
            stacked = [jnp.concatenate(ps, axis=0) for ps in zip(*pieces)]
        return tok, jtu.tree_unflatten(treedef, stacked)

    def prefetch_checked(self, token, base, seg: int):
        """``prefetch`` plus an integrity verdict: returns ``(token, ok,
        tree)`` where ``ok`` (a traced bool) is True only if every slot in
        ``[base, base+seg)`` was present, passed its crc32 (recorded at
        write time; requires the store built with ``integrity=True``), and
        the host read did not exhaust its retry budget.  On ``ok=False``
        the returned tree is whatever could be read (zeros on total
        failure) — callers must ``lax.cond`` on ``ok`` into a recompute
        fallback rather than consume it.  Chunked exactly like
        ``prefetch``; the chunk verdicts AND together."""
        treedef, sds = self._meta["idx"]
        m = self._per_slot_chunk(sds, seg)
        ok_sds = jax.ShapeDtypeStruct((), jnp.bool_)
        tok, ok, pieces = token, None, []
        for o in range(0, seg, m):
            mm = min(m, seg - o)
            out_sds = (_TOKEN_SDS, ok_sds) + tuple(
                jax.ShapeDtypeStruct((mm,) + tuple(s.shape), s.dtype)
                for s in sds)
            out = jax.pure_callback(self._cb_prefetch(mm, checked=True),
                                    out_sds, tok, base + o,
                                    vmap_method="broadcast_all")
            tok = out[0]
            ok = out[1] if ok is None else jnp.logical_and(ok, out[1])
            pieces.append(out[2:])
        if len(pieces) == 1:
            stacked = pieces[0]
        else:
            stacked = [jnp.concatenate(ps, axis=0) for ps in zip(*pieces)]
        return tok, ok, jtu.tree_unflatten(treedef, stacked)


class DiskStore(SpillStore):
    """All-disk spill: the ``snaps_in_ram=0`` corner of ``SpillStore`` as
    its own tier, so planners/validators can name it.  Same callbacks,
    token contract, integrity/retry behavior — slot payloads live in
    ``repro_spill_*.npz`` segment files instead of the RAM dict."""

    tier = "disk"

    def __init__(self):
        super().__init__()
        self.snaps_in_ram = 0
        self.effective_tier = "disk"
