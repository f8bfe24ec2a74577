"""Forecast-as-a-service: batched multi-domain serving over the fused stencil.

The ROADMAP's "heavy traffic from millions of users" is not one huge
advection domain — it is MANY small ones: a `StencilRequest` is an
``(initial u/v/w fields, AdvectParams, n_steps)`` forecast job, and the
`StencilServingEngine` packs up to `batch_size` of them into ONE padded
mega-launch of the fused kernel (`kernels.advection.advect_fused_batched`
— the batch rides an outer grid dimension, slots streaming back to back
through the shared VMEM rings). This is the paper's §IV kernel-pool/DMA
overlap applied at the product layer: chunked arrival of new forecast
jobs overlaps the resident jobs' compute, with the slot lifecycle shared
with the LLM engine via `serving.slots.SlotManager`.

Contracts (gated by BENCH_serving.json / BENCH_faults.json and
tests/test_stencil_serving.py / tests/test_faults.py):

  * Packing is exact, not approximate: a request SMALLER than the padded
    slot shape is embedded at the origin with per-slot interior masks
    freezing everything outside its own extent and boundary ring, so the
    mega-launch's cropped outputs are BITWISE-equal to per-domain
    sequential `advect_fused` runs on the unpadded fields.
  * Compiled executables are cached keyed on
    ``(shape, T, dtype, n_blocks, exchange, mesh)`` with hit/miss
    counters and a bounded LRU (`max_entries`) — one trace per
    configuration, every later mega-step a hit.
  * The slot batch lives on the device across mega-steps. A prime uploads
    only the job's own fields and writes its slot in place; after a
    mega-step only each live slot's cropped state comes back
    (`StencilRequest.states`, one cropped (u, v, w) per fused step).
  * Faults are injected from a deterministic `serving.faults.FaultPlan`
    at mega-step boundaries (the old `lose_device_at` hook is a
    deprecated one-fault alias) and recovery is LAYERED:
      - the mega-step runs the in-graph finite-guard pass
        (`advect_fused_batched(..., guard=True)` — one extra read pass
        over the advanced fields, priced by
        `roofline.guard_bytes_model`), so a poisoned slot is detected
        the step it goes non-finite; the guard is a SEPARATE pallas
        pass over the fused kernel's outputs, so every slot's fields —
        healthy or poisoned — stay bitwise-equal to an unguarded run;
      - periodic snapshots of the in-flight state in host memory (the
        states already streamed back, by reference; through
        `training/checkpoint`'s atomic-write machinery when
        `snapshot_dir` is set) let ANY fault roll back and replay,
        resume bitwise-equal to an uninterrupted run; a fault that
        re-fires at the same (uid, step) site after a rollback is
        persistent by definition and the slot is QUARANTINED with an
        error status instead of rolled back forever;
      - a stalled exchange is retried with bounded backoff, then walks
        the `DegradationLadder` (`remote_dma` -> `collective` — a new
        cache key, one recorded re-trace) and finally resorts to the
        implicit last rung: reshard down to fewer slots;
      - every action lands in `health()` counters (faults, retries,
        quarantines, rollbacks, degradations, reshards), surfaced by
        `launch/serve.py` and gated by `benchmarks/fault_sweep.py`.
  * A device loss re-shards the engine: live slots are re-packed into a
    smaller batch (a new cache key — the recorded miss), overflow jobs
    resume from their in-flight state when slots free up, and the
    completed outputs stay bitwise-equal to an uninterrupted run.
  * Per-tenant pricing: `AdvectionDomain(batch=...)` scales the
    flops/bytes/wire accounting and `roofline.serving_throughput_model`
    turns it into domains/s.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.analysis import vmem as AV
from repro.kernels.advection import advection as K
from repro.kernels.advection.ref import AdvectParams
from repro.serving.faults import (DEFAULT_LADDER, DegradationLadder,
                                  ExchangeStalled, Fault, FaultInjector,
                                  FaultPlan, RecoveryExhausted,
                                  retry_with_backoff)
from repro.serving.slots import SlotManager
from repro.stencil.advection import AdvectionDomain
from repro.training import checkpoint as CKPT

# host spans on the profiler's clock (`jax.profiler.TraceAnnotation`):
# free when no trace runs, nested on the calling thread, keyword
# arguments recorded as the event's stats
_span = jax.profiler.TraceAnnotation


def _bucket(n: int, cap: int) -> int:
    """The power of two at or above `n`, at most `cap`. Slot writes and
    crops move a job's fields padded to these extents, so a mix of any
    extents shares a few programs: with X and Y powers of two, at most
    ``(log2 X + 1) * (log2 Y + 1)`` of each per batch shape."""
    return min(1 << max(n - 1, 0).bit_length(), cap)


@functools.partial(jax.jit, donate_argnums=0)
def _write_slot(batch, slot, fields):
    """Write slot `slot` of the device batch in place: `fields` at the
    origin, zero everywhere else. One compile per batch and field shape
    (the field shapes are `_bucket` extents)."""
    out = []
    for f, x in zip(batch, fields):
        slab = jnp.zeros(f.shape[1:], f.dtype)
        slab = slab.at[:x.shape[0], :x.shape[1]].set(x)
        out.append(lax.dynamic_update_index_in_dim(f, slab, slot, 0))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=2)
def _crop_slot(batch, slot, extent):
    """Slot `slot`'s ``(Xr, Yr, Z)`` corner of the device batch, as new
    arrays. One compile per extent (a `_bucket` extent)."""
    Xr, Yr = extent
    return tuple(lax.dynamic_slice(f, (slot, 0, 0, 0),
                                   (1, Xr, Yr, f.shape[3]))[0]
                 for f in batch)


@dataclasses.dataclass
class StencilRequest:
    """One forecast job: initial fields + coefficients + a step budget.

    `n_steps` counts FUSED steps (each advances `domain.fuse_T` Euler
    substeps); 0 means the job is complete at prime time and returns its
    initial fields. `params=None` uses the engine domain's coefficients;
    a per-tenant `AdvectParams` (same Z) rides the slot's batched leaves.
    `status` walks pending -> running -> done, or -> quarantined (with
    `error` set and `out=None`) when the finite guard traps the slot.

    Each `states` entry (and `out`, its last) is a tuple of arrays copied
    from the device for this job and this step alone; they may come back
    read-only, and the engine's snapshot keeps references to them, so
    they are not to be written in place.
    """
    uid: int
    u: np.ndarray                        # (Xr, Yr, Z) initial fields
    v: np.ndarray
    w: np.ndarray
    n_steps: int = 1
    params: Optional[AdvectParams] = None
    out: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
    states: Optional[List[Tuple[np.ndarray, ...]]] = None
    status: str = "pending"
    error: Optional[str] = None


@dataclasses.dataclass
class _InFlight:
    """A live job's cropped state, as the host holds it, detached for
    re-sharding."""
    req: StencilRequest
    budget: int
    fields: Tuple[np.ndarray, np.ndarray, np.ndarray]
    params: Tuple[np.ndarray, ...]
    extent: Tuple[int, int]


@dataclasses.dataclass
class _Snapshot:
    """Everything a rollback needs to replay from this boundary: each
    live slot's cropped fields (references to host states the engine
    already holds, never copies), copies of the masks and coefficients
    (`arrays`), the slot assignments, the queue, and the length of every
    reachable request's streamed-state list (so replayed steps do not
    double-append). `disk_step` is set when the padded batch was also
    written through `training/checkpoint.save` (`arrays` then holds it
    too) — the rollback then restores it from DISK, exercising the same
    atomic-write machinery the training tier trusts."""
    steps_run: int
    B: int
    fields: Dict[int, Tuple[np.ndarray, ...]]
    arrays: Dict[str, np.ndarray]
    extents: List[Tuple[int, int]]
    live: List[Tuple[int, int, int]]     # (slot, uid, budget)
    reqs: Dict[int, StencilRequest]
    states_len: Dict[int, int]
    queue: List[Any]
    done_uids: set
    disk_step: Optional[int]


class ExecutableCache:
    """Compiled-executable cache: hit/miss/eviction counters + bounded LRU.

    Keys are the full recompilation surface of a mega-step —
    ``(shape, T, dtype, n_blocks, exchange, mesh)`` — so a re-shard (new
    batch in `shape`) or an engine/mesh change records a miss and traces
    once, while every steady-state mega-step is a hit on the same
    executable. `max_entries` bounds the cache under shape-diverse
    traffic: insertion past the bound evicts the least-recently-used
    entry (a later return to that key re-traces — a counted miss, never
    an error). `evict(key)` drops one entry explicitly — the
    `cache_evict` fault kind's hook."""

    def __init__(self, max_entries: Optional[int] = None):
        if max_entries is not None and max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self._fns: "OrderedDict[Any, Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key, build):
        fn = self._fns.get(key)
        if fn is None:
            self.misses += 1
            fn = self._fns[key] = build()
            if (self.max_entries is not None
                    and len(self._fns) > self.max_entries):
                self._fns.popitem(last=False)
                self.evictions += 1
        else:
            self.hits += 1
            self._fns.move_to_end(key)
        return fn

    def evict(self, key) -> bool:
        """Drop `key` if cached; True when something was evicted."""
        if key in self._fns:
            del self._fns[key]
            self.evictions += 1
            return True
        return False

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._fns), "evictions": self.evictions}


class StencilServingEngine:
    """Continuous-batching forecast server over the batched fused kernel.

    `domain` fixes the padded slot shape ``(X, Y, Z)``, the fusion depth
    `fuse_T`, dt, tiling, and the cache-key mesh/exchange/n_blocks
    configuration (the serving mega-step itself runs the single-shard
    batched kernel; a distributed mega-step would slot into `_build_step`
    under the same key discipline). Requests whose extent is smaller than
    the slot are padded and mask-frozen; Z must match exactly (the z axis
    has no interior mask — it is the vectorised lane dimension).

    The padded batch `u`, `v`, `w` are device arrays ``(B, X, Y, Z)`` that
    live across mega-steps; each mega-step donates them to the cached step
    and keeps its outputs. Only two things cross the link with fields in
    them: a prime uploads the job's own ``(Xr, Yr, Z)`` fields and writes
    its slot in place, and after a mega-step each live slot's cropped
    state is sliced on the device and downloaded into arrays of its own,
    which become the job's next `states` entry. Both move the fields
    padded to the extent's `_bucket` (the next power of two, at most the
    slot), so traffic of mixed extents compiles a few slot programs, not
    two per extent. The masks and per-slot coefficients stay host numpy
    and go up each mega-step (a few KiB). `health()` counts the bytes
    moved each way (`bytes_to_device`, `bytes_to_host`).

    Fault tolerance knobs: `fault_plan` (a `FaultPlan`, or a spec string
    for `FaultPlan.parse`) schedules deterministic faults at mega-step
    boundaries; `snapshot_every=k` rolls a recovery point every k
    mega-steps (default 1; None disables rollback and a tripped guard
    quarantines immediately). The recovery point stays in host memory:
    it holds references to each live slot's last downloaded state (or
    its job's initial fields) and copies of the masks and coefficients,
    so a snapshot copies no field. `snapshot_dir` additionally assembles
    the padded batch and round-trips each snapshot through
    `training/checkpoint`'s atomic on-disk format;
    `max_retries`/`backoff_s` bound the exchange-stall retry loop;
    `cache_max_entries` bounds the executable cache (LRU).
    """

    def __init__(self, domain: AdvectionDomain, *, batch_size: int = 4,
                 fault_plan: Union[FaultPlan, str, None] = None,
                 snapshot_every: Optional[int] = 1,
                 snapshot_dir: Union[str, Path, None] = None,
                 max_retries: int = 3, backoff_s: float = 0.0,
                 sleeper=time.sleep,
                 cache_max_entries: Optional[int] = None):
        if domain.variant != "fused":
            raise ValueError("the serving tier packs the fused (v4) kernel; "
                             f"got variant={domain.variant!r}")
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        if snapshot_every is not None and snapshot_every < 1:
            raise ValueError(f"snapshot_every must be >= 1 or None, got "
                             f"{snapshot_every}")
        self.domain = domain
        self.B = batch_size
        self.cache = ExecutableCache(max_entries=cache_max_entries)
        self.steps_run = 0
        # physical mega-step executions: unlike `steps_run` (the LOGICAL
        # step index, rewound by a rollback so replay is bitwise), this
        # counter is never restored — faulted-minus-clean is the recovery
        # overhead BENCH_faults.json bounds at exactly one replayed
        # snapshot interval per rollback
        self.megasteps_executed = 0
        # field, mask and coefficient bytes moved across the link
        self.bytes_to_device = 0
        self.bytes_to_host = 0
        if isinstance(fault_plan, str):
            fault_plan = FaultPlan.parse(fault_plan)
        self._injector = FaultInjector(fault_plan)
        self._ladder = self._make_ladder()
        self._snapshot_every = snapshot_every
        self._snapshot_dir = Path(snapshot_dir) if snapshot_dir else None
        self._snap: Optional[_Snapshot] = None
        self._suspects: set = set()
        self._quarantined: set = set()
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self._sleeper = sleeper
        self._last_ok: Optional[np.ndarray] = None
        self._alloc(batch_size)

    def _make_ladder(self) -> DegradationLadder:
        start = self.domain.exchange
        rungs = (DEFAULT_LADDER if start in DEFAULT_LADDER
                 else (start,) + tuple(DEFAULT_LADDER))
        return DegradationLadder(rungs, start=start)

    # -- storage -----------------------------------------------------------
    def _alloc(self, batch_size: int) -> None:
        d = self.domain
        dt = np.dtype(d.dtype)
        # static VMEM budget BEFORE any allocation or compile: the
        # batched slot rings must fit VMEM_PER_CORE (the analysis
        # layer's generalisation of roofline.serving_max_batch — same
        # bound, but the error names the buffer and its sizing)
        AV.serving_ring_plan(d.Y, d.Z, batch=batch_size, T=d.fuse_T,
                             itemsize=dt.itemsize, y_tile=d.y_tile,
                             context="serving engine slot rings").check()
        self.B = batch_size
        self.slots = SlotManager(batch_size)
        # drop the old batch before the new one is made
        self.u = self.v = self.w = None
        shape = (batch_size, d.X, d.Y, d.Z)
        self.u = jnp.zeros(shape, dt)
        self.v = jnp.zeros(shape, dt)
        self.w = jnp.zeros(shape, dt)
        self.xm = np.zeros((batch_size, d.X), np.float32)
        self.ym = np.zeros((batch_size, d.Y), np.float32)
        base = [np.asarray(leaf) for leaf in d.params]
        self._p = [np.stack([leaf] * batch_size) for leaf in base]
        self._extent: List[Tuple[int, int]] = [(0, 0)] * batch_size
        # each live slot's state as downloaded after the last mega-step
        self._fresh: Dict[int, Tuple[np.ndarray, ...]] = {}

    def _step_key(self):
        d = self.domain
        return ((self.B, d.X, d.Y, d.Z), d.fuse_T, d.dtype, d.n_blocks,
                d.exchange, (d.mesh_nx, d.mesh_ny))

    def _build_step(self):
        d = self.domain

        def step(u, v, w, p, xm, ym):
            return K.advect_fused_batched(u, v, w, p, T=d.fuse_T, dt=d.dt,
                                          interpret=d.interpret,
                                          y_tile=d.y_tile, tiling=d.tiling,
                                          x_interior_mask=xm,
                                          y_interior_mask=ym, guard=True)

        # the batch is donated: the step writes its outputs over it, so
        # the device never holds two batches beside the step's own
        # working set
        return jax.jit(step, donate_argnums=(0, 1, 2))

    # -- slot lifecycle ----------------------------------------------------
    def _up_shape(self, extent: Tuple[int, int]) -> Tuple[int, int, int]:
        """The shape a slot write of `extent` uploads and a crop of it
        downloads: the extent's `_bucket`."""
        d = self.domain
        return (_bucket(extent[0], d.X), _bucket(extent[1], d.Y), d.Z)

    def _move_bytes(self, extent: Tuple[int, int]) -> int:
        """u, v and w of `extent`'s bucket, in bytes."""
        return 3 * int(np.prod(self._up_shape(extent))) * np.dtype(
            self.domain.dtype).itemsize

    def _put(self, slot: int, fields) -> None:
        """Upload `fields` (``(Xr, Yr, Z)`` each) and write them into
        `slot` of the device batch, zero outside them; no fields zero
        the slot. Fields short of their bucket are padded on the host
        first."""
        d = self.domain
        if fields is None:
            up = (np.zeros((0, 0, d.Z), d.dtype),) * 3
        else:
            Xr, Yr = np.shape(fields[0])[:2]
            shape = self._up_shape((Xr, Yr))
            up = []
            for f in fields:
                if np.shape(f) != shape:
                    a = np.zeros(shape, d.dtype)
                    a[:Xr, :Yr] = f
                    f = a
                up.append(f)
        dev = tuple(jnp.asarray(f, d.dtype) for f in up)
        self.bytes_to_device += sum(a.nbytes for a in dev)
        self.u, self.v, self.w = jax.block_until_ready(
            _write_slot((self.u, self.v, self.w), slot, dev))

    def _pack(self, slot: int, fields, params, extent: Tuple[int, int]
              ) -> None:
        d = self.domain
        Xr, Yr = extent
        self._put(slot, fields)
        # freeze everything outside the request's own interior: its
        # boundary ring behaves exactly like the unpadded kernel's
        # structural walls, so padding is bitwise-invisible
        self.xm[slot] = 0.0
        self.xm[slot, 1:Xr - 1] = 1.0
        self.ym[slot] = 0.0
        self.ym[slot, 1:Yr - 1] = 1.0
        leaves = (list(params) if params is not None
                  else [np.asarray(leaf) for leaf in d.params])
        for dst, leaf in zip(self._p, leaves):
            dst[slot] = np.asarray(leaf, dst.dtype)
        self._extent[slot] = extent

    def _prime(self, slot: int, req: StencilRequest) -> bool:
        """Pack `req` into `slot`; True when complete at prime time
        (``n_steps == 0`` — the job's output is its initial state and it
        never occupies the slot)."""
        d = self.domain
        # what the prime moves: the upload, or the copy into `out` of a
        # job complete at prime time
        shp = np.shape(req.u)
        nbytes = (0 if len(shp) != 3
                  else self._move_bytes(shp[:2]) if req.n_steps
                  else 3 * np.size(req.u) * np.dtype(d.dtype).itemsize)
        with _span("engine.prime", uid=req.uid, bytes=nbytes):
            if req.n_steps < 0:
                raise ValueError(f"n_steps must be >= 0, got {req.n_steps} "
                                 f"(request {req.uid})")
            shp = np.asarray(req.u).shape
            if (np.asarray(req.v).shape != shp
                    or np.asarray(req.w).shape != shp):
                raise ValueError(f"request {req.uid} field shapes differ")
            if len(shp) != 3:
                raise ValueError(f"request {req.uid} fields must be "
                                 f"(X, Y, Z), got shape {shp}")
            Xr, Yr, Zr = shp
            if Zr != d.Z:
                raise ValueError(
                    f"request {req.uid} has Z={Zr} but the engine slot is "
                    f"Z={d.Z}: z is the lane dimension and cannot be padded")
            if Xr > d.X or Yr > d.Y:
                raise ValueError(
                    f"request {req.uid} extent ({Xr}, {Yr}) exceeds the "
                    f"padded slot shape ({d.X}, {d.Y}); domains must fit "
                    "the slot")
            if Xr < 3 or Yr < 3:
                raise ValueError(
                    f"request {req.uid} extent ({Xr}, {Yr}) has no interior "
                    "cell; the stencil needs >= 3 points per decomposed axis")
            if req.params is not None and np.asarray(
                    req.params.tzc1).shape != (d.Z,):
                raise ValueError(f"request {req.uid} params are not for "
                                 f"Z={d.Z}")
            req.states = []
            if req.n_steps == 0:
                req.out = tuple(np.array(f, np.dtype(d.dtype))
                                for f in (req.u, req.v, req.w))
                req.status = "done"
                return True
            self._pack(slot, (req.u, req.v, req.w), req.params, (Xr, Yr))
            self.slots.occupy(slot, req, req.n_steps)
            req.status = "running"
            return False

    def _resume(self, slot: int, flight: _InFlight) -> None:
        """Re-pack a job displaced by a re-shard, from its in-flight state."""
        nbytes = self._move_bytes(flight.extent)
        with _span("engine.prime", uid=flight.req.uid, bytes=nbytes):
            self._pack(slot, flight.fields, flight.params, flight.extent)
            self.slots.occupy(slot, flight.req, flight.budget)

    def _clear(self, slot: int) -> None:
        # an idle slot keeps stepping in the mega-launch; all-zero masks
        # freeze it completely so it costs nothing semantically
        self.xm[slot] = 0.0
        self.ym[slot] = 0.0
        self._extent[slot] = (0, 0)

    def _download(self, slots: List[int]) -> Dict[int, Tuple[np.ndarray, ...]]:
        """Each of `slots`' cropped ``(u, v, w)``: its bucket sliced on the
        device and copied into host arrays of its own, of which the job
        keeps its extent."""
        crops = {s: _crop_slot((self.u, self.v, self.w), s,
                               self._up_shape(self._extent[s])[:2])
                 for s in slots}
        for c in crops.values():          # every copy in flight at once
            for a in c:
                a.copy_to_host_async()
        out = {}
        for s, c in crops.items():
            Xr, Yr = self._extent[s]
            got = [np.asarray(a) for a in c]
            self.bytes_to_host += sum(a.nbytes for a in got)
            out[s] = tuple(a[:Xr, :Yr] for a in got)
        return out

    # -- the mega-step -----------------------------------------------------
    def _host_arrays(self) -> Tuple[np.ndarray, ...]:
        """What the host keeps of the batch: masks and coefficients."""
        return (self.xm, self.ym, *self._p)

    def _mega_step(self) -> None:
        misses = self.cache.misses
        fn = self.cache.get(self._step_key(), self._build_step)
        host = self._host_arrays()
        nbytes = sum(a.nbytes for a in host)
        # each phase waits for its own work, so its time does not fall
        # into the next span; the kernel could not start before its
        # inputs arrived, nor the copies before its outputs, so the waits
        # add no work
        with _span("engine.upload", bytes=nbytes):
            xm, ym, *p = jax.block_until_ready(
                [jnp.asarray(a) for a in host])
            self.bytes_to_device += nbytes
        with _span("engine.device", cache_miss=int(self.cache.misses
                                                   > misses)):
            self.u, self.v, self.w, gf = jax.block_until_ready(
                fn(self.u, self.v, self.w, AdvectParams(*p), xm, ym))
        live = self.slots.live_slots()
        crops = sum(self._move_bytes(self._extent[s]) for s in live)
        with _span("engine.download", bytes=gf.nbytes + crops):
            gf.copy_to_host_async()
            self._fresh = self._download(live)
            # a slot is healthy iff every x-slice flag word of its
            # guard pass is 1.0 — the post-kernel isfinite pass
            flags = np.asarray(gf)
            self.bytes_to_host += flags.nbytes
            self._last_ok = flags.min(axis=1) > 0.0
        self.steps_run += 1
        self.megasteps_executed += 1

    def _guarded_mega_step(self, queue: List[Any]) -> None:
        """One mega-step under the retry / degradation discipline: armed
        exchange stalls hang the attempt, the bounded backoff loop
        absorbs transient ones, a persistent stall degrades the ladder
        (new exchange -> new cache key -> one recorded re-trace), and a
        fully exhausted ladder takes the implicit last rung — reshard
        down to fewer slots (the lost transport's devices are gone)."""
        inj, lad = self._injector, self._ladder

        def attempt():
            inj.poll_stall(lad.current)
            self._mega_step()

        with _span("engine.megastep", step=self.steps_run):
            while True:
                try:
                    retry_with_backoff(
                        attempt, max_retries=self.max_retries,
                        backoff_s=self.backoff_s, sleeper=self._sleeper,
                        on_retry=lambda k, e: inj.record("retries"))
                    return
                except ExchangeStalled as e:
                    try:
                        rung = lad.degrade(str(e))
                        inj.record("degradations")
                        inj.note(f"step {self.steps_run}: "
                                 f"{lad.transitions[-1]}")
                        self.domain = dataclasses.replace(self.domain,
                                                          exchange=rung)
                    except RecoveryExhausted:
                        n = max(self.B // 2, 1)
                        inj.record("reshards")
                        inj.note(f"step {self.steps_run}: ladder exhausted "
                                 f"-> reshard to {n} slots")
                        inj.clear_stalls()
                        queue[:0] = self.reshard(n)

    # -- fault injection ---------------------------------------------------
    def _apply_faults(self, queue: List[Any]) -> None:
        """Apply the plan's faults due at this mega-step boundary."""
        inj = self._injector
        for idx, f in inj.due(self.steps_run):
            if f.kind == "device_loss":
                n = f.reshard_to if f.reshard_to is not None \
                    else max(self.B // 2, 1)
                inj.mark_fired(idx)
                inj.record("device_losses")
                inj.record("reshards")
                inj.note(f"step {self.steps_run}: device loss -> "
                         f"reshard to {n} slots")
                # displaced jobs resume ahead of queued fresh work
                queue[:0] = self.reshard(n)
            elif f.kind in ("nan_poison", "halo_corruption"):
                if f.slot >= self.B or not self.slots.is_live(f.slot):
                    inj.skip(idx, f"slot {f.slot} not live at step "
                                  f"{self.steps_run}")
                    continue
                arr = getattr(self, f.field)
                Xr, Yr = self._extent[f.slot]
                if f.kind == "nan_poison":
                    # one interior cell: the stencil spreads it, the
                    # guard flags the whole slot this same step
                    arr = arr.at[f.slot, 1, 1, 0].set(f.value())
                else:
                    # a corrupted halo band: the mask freezes the
                    # boundary ring, so the poison SITS there (caught by
                    # the guard) but cannot re-enter the interior —
                    # one-shot, rollback + replay is clean
                    arr = arr.at[f.slot, :min(f.depth, Xr), :Yr, :].set(
                        f.value())
                setattr(self, f.field, arr)
                inj.mark_fired(idx)
                inj.note(f"step {self.steps_run}: {f.kind} slot {f.slot} "
                         f"field {f.field} ({f.mode})")
            elif f.kind == "exchange_stall":
                inj.arm_stall(idx, f)
                inj.mark_fired(idx)
                inj.note(f"step {self.steps_run}: exchange stall armed on "
                         f"rung {f.rung!r} ({f.stalls} attempts)")
            elif f.kind == "cache_evict":
                if self.cache.evict(self._step_key()):
                    inj.record("cache_evictions")
                    inj.note(f"step {self.steps_run}: evicted current "
                             f"executable (re-trace on next launch)")
                else:
                    inj.note(f"step {self.steps_run}: cache_evict found "
                             f"no entry for the current key")
                inj.mark_fired(idx)

    # -- snapshots / rollback ----------------------------------------------
    def _reachable(self, queue: List[Any]) -> Dict[int, StencilRequest]:
        out: Dict[int, StencilRequest] = {}
        for s in self.slots.live_slots():
            r = self.slots.request(s)
            out[r.uid] = r
        for item in queue:
            r = item.req if isinstance(item, _InFlight) else item
            out[r.uid] = r
        return out

    def _held(self, slot: int) -> Tuple[np.ndarray, ...]:
        """What the device holds in live `slot` at a mega-step boundary:
        its job's last downloaded state, or its initial fields before
        its first step."""
        req = self.slots.request(slot)
        return req.states[-1] if req.states else (req.u, req.v, req.w)

    def _take_snapshot(self, queue: List[Any], done: Dict[int, Any]) -> None:
        d = self.domain
        live = self.slots.live_slots()
        host = self._host_arrays()
        nbytes = sum(a.nbytes for a in host)
        if self._snapshot_dir is not None:
            nbytes += 3 * self.B * d.X * d.Y * d.Z * np.dtype(d.dtype).itemsize
        with _span("engine.snapshot", bytes=nbytes):
            arrays = {"xm": self.xm.copy(), "ym": self.ym.copy()}
            for i, leaf in enumerate(self._p):
                arrays[f"p{i}"] = leaf.copy()
            fields = {s: self._held(s) for s in live}
            reqs = self._reachable(queue)
            disk_step = None
            if self._snapshot_dir is not None:
                shape = (self.B, d.X, d.Y, d.Z)
                for k in range(3):
                    a = np.zeros(shape, d.dtype)
                    for s, f in fields.items():
                        Xr, Yr = self._extent[s]
                        a[s, :Xr, :Yr] = f[k]
                    arrays["uvw"[k]] = a
                CKPT.save(self._snapshot_dir, arrays, self.steps_run)
                disk_step = self.steps_run
            self._snap = _Snapshot(
                steps_run=self.steps_run, B=self.B, fields=fields,
                arrays=arrays, extents=list(self._extent),
                live=[(s, self.slots.request(s).uid, self.slots.budget(s))
                      for s in live],
                reqs=reqs,
                states_len={uid: (len(r.states) if r.states is not None
                                  else -1)
                            for uid, r in reqs.items()},
                queue=list(queue), done_uids=set(done), disk_step=disk_step)
        self._injector.record("snapshots")

    def _rollback(self, queue: List[Any], done: Dict[int, Any],
                  reason: str) -> None:
        """Restore the last snapshot and replay from it. Quarantined
        jobs stay quarantined (their slot comes back empty); everything
        else — the device batch, slot assignments, budgets, streamed
        states, the queue, the step counter — returns to the boundary, so
        the replay is bitwise-indistinguishable from a run that never
        faulted."""
        snap = self._snap
        assert snap is not None
        arrays, fields = snap.arrays, snap.fields
        if self._snapshot_dir is not None and snap.disk_step is not None:
            # restore through the checkpoint machinery: the atomic
            # on-disk copy is the recovery point, not host memory
            arrays, _ = CKPT.restore(self._snapshot_dir, snap.arrays,
                                     step=snap.disk_step)
            fields = {s: tuple(arrays[k][s, :snap.extents[s][0],
                                         :snap.extents[s][1]]
                               for k in "uvw")
                      for s, _, _ in snap.live}
        self._alloc(snap.B)
        self.xm[:] = arrays["xm"]
        self.ym[:] = arrays["ym"]
        for i in range(len(self._p)):
            self._p[i][:] = arrays[f"p{i}"]
        self._extent = list(snap.extents)
        for slot, uid, budget in snap.live:
            if uid in self._quarantined:
                self._clear(slot)
                continue
            self._put(slot, fields[slot])
            self.slots.occupy(slot, snap.reqs[uid], budget)
        for uid, req in snap.reqs.items():
            if uid in self._quarantined:
                continue
            n = snap.states_len[uid]
            if n < 0:
                req.states = None
            else:
                del req.states[n:]
            req.out = None
            req.status = "running" if any(u == uid for _, u, _ in snap.live) \
                else "pending"
        for uid in list(done):
            if uid not in snap.done_uids and uid not in self._quarantined:
                del done[uid]
        queue[:] = list(snap.queue)
        self.steps_run = snap.steps_run
        self._injector.record("rollbacks")
        self._injector.note(f"rollback to step {snap.steps_run}: {reason}")

    def _quarantine(self, slot: int, reason: str) -> StencilRequest:
        """Isolate a poisoned slot: error out its job, zero its data (so
        the frozen non-finite cells stop tripping the guard), and free
        the slot for healthy work."""
        req = self.slots.request(slot)
        req.status = "quarantined"
        req.error = reason
        req.out = None
        self._quarantined.add(req.uid)
        self.slots.release(slot)
        self._clear(slot)
        self._put(slot, None)
        self._injector.record("quarantines")
        self._injector.note(f"quarantined uid {req.uid} (slot {slot}): "
                            f"{reason}")
        return req

    # -- fault tolerance ---------------------------------------------------
    def reshard(self, new_batch_size: int) -> List[_InFlight]:
        """Re-shard the engine onto `new_batch_size` slots (a simulated
        device loss took the rest, or devices returned — resharding UP
        works the same way): live jobs are detached with their in-flight
        state as the host holds it (a lost device cannot be read back),
        the batch is re-allocated (a NEW cache key — the next mega-step
        records a miss and re-traces), and as many jobs as fit are
        re-packed immediately.
        Jobs that no longer fit are returned for the caller (`run`) to
        resume — state intact, budget intact — when slots free up. Slot
        independence makes the re-pack bitwise-invisible to every job's
        output."""
        if new_batch_size < 1:
            raise ValueError(f"new_batch_size must be >= 1, got "
                             f"{new_batch_size}")
        live = self.slots.live_slots()
        flights = [
            _InFlight(req=self.slots.request(s), budget=self.slots.budget(s),
                      fields=self._held(s),
                      params=tuple(leaf[s].copy() for leaf in self._p),
                      extent=self._extent[s])
            for s in live]
        self._alloc(new_batch_size)
        for slot, flight in enumerate(flights[:new_batch_size]):
            self._resume(slot, flight)
        return flights[new_batch_size:]

    # -- driver ------------------------------------------------------------
    def run(self, requests: List[StencilRequest], *,
            lose_device_at: Optional[int] = None,
            reshard_to: Optional[int] = None,
            fault_plan: Union[FaultPlan, str, None] = None
            ) -> Dict[int, StencilRequest]:
        """Serve `requests` to completion; returns {uid: completed request}
        (each with `out` = final cropped fields and `states` = the
        streamed per-step snapshots; a quarantined request comes back
        with ``status == "quarantined"``, `error` set, and ``out=None``).

        `fault_plan` (a `FaultPlan` or spec string) replaces the
        engine's injector for this run. `lose_device_at=k` is the
        DEPRECATED one-fault alias: it builds a plan with a single
        device-loss fault after the k-th mega-step re-sharding onto
        `reshard_to` slots (default: half, at least 1)."""
        if lose_device_at is not None:
            if fault_plan is not None:
                raise ValueError("pass either fault_plan or the deprecated "
                                 "lose_device_at, not both")
            if lose_device_at < 1:
                raise ValueError(f"lose_device_at must be >= 1, got "
                                 f"{lose_device_at}")
            n = reshard_to if reshard_to is not None else max(self.B // 2, 1)
            fault_plan = FaultPlan((Fault(
                "device_loss", at_step=self.steps_run + lose_device_at,
                reshard_to=n),))
        if fault_plan is not None:
            if isinstance(fault_plan, str):
                fault_plan = FaultPlan.parse(fault_plan)
            self._injector = FaultInjector(fault_plan)
        with _span("engine.run", requests=len(requests)):
            queue: List[Any] = list(requests)
            done: Dict[int, StencilRequest] = {}
            while queue or self.slots.any_live():
                if (self._snapshot_every is not None
                        and self.steps_run % self._snapshot_every == 0):
                    self._take_snapshot(queue, done)
                for s in self.slots.idle_slots():
                    if not queue:
                        break
                    item = queue.pop(0)
                    if isinstance(item, _InFlight):
                        self._resume(s, item)
                    elif self._prime(s, item):
                        done[item.uid] = item
                self._apply_faults(queue)
                if not self.slots.any_live():
                    continue
                step_idx = self.steps_run
                self._guarded_mega_step(queue)
                bad = [b for b in self.slots.live_slots()
                       if not self._last_ok[b]]
                if bad:
                    fresh = [b for b in bad
                             if (self.slots.request(b).uid, step_idx)
                             not in self._suspects]
                    if fresh and self._snap is not None:
                        # first sighting at this (uid, step) site: assume a
                        # transient, roll back and replay. A fault that
                        # re-fires on the replay is persistent — the replay
                        # lands here again with the site already suspect and
                        # falls through to quarantine.
                        for b in bad:
                            self._suspects.add(
                                (self.slots.request(b).uid, step_idx))
                        self._rollback(queue, done,
                                       reason=f"non-finite guard at step "
                                              f"{step_idx}, slots {bad}")
                        continue
                    for b in bad:
                        req = self._quarantine(
                            b, f"non-finite field detected at step {step_idx}")
                        done[req.uid] = req
                for s in self.slots.live_slots():
                    req = self.slots.request(s)
                    # the state came down in `engine.download`; this is
                    # the job's bookkeeping and moves no bytes
                    with _span("engine.crop", uid=req.uid, bytes=0):
                        state = self._fresh.pop(s)
                        req.states.append(state)
                        if self.slots.tick(s):
                            req.out = state
                            req.status = "done"
                            done[req.uid] = req
                            self.slots.release(s)
                            self._clear(s)
            return done

    # -- accounting --------------------------------------------------------
    def cache_stats(self) -> Dict[str, int]:
        return self.cache.stats()

    def health(self) -> Dict[str, Any]:
        """The fault/recovery counters surface: everything the injector
        recorded (faults seen, retries, quarantines, rollbacks,
        degradations, reshards, snapshots) plus the live exchange rung,
        the quarantined uids, the executable-cache stats, and the running
        totals of field, mask and coefficient bytes moved to and from the
        device. Printed by `launch/serve.py` and gated by
        `benchmarks/fault_sweep.py`."""
        h = self._injector.health()
        h["exchange"] = self._ladder.current
        h["quarantined_uids"] = sorted(self._quarantined)
        h["cache"] = self.cache_stats()
        h["bytes_to_device"] = self.bytes_to_device
        h["bytes_to_host"] = self.bytes_to_host
        return h

    def guard_bytes_per_step(self) -> int:
        """Extra HBM bytes the finite-guard pass adds to one mega-launch
        (`roofline.guard_bytes_model` at the current batch size)."""
        return dataclasses.replace(self.domain,
                                   batch=self.B).guard_bytes_per_step()

    def modelled_throughput(self) -> float:
        """Domains/s of this engine's mega-launch per
        `roofline.serving_throughput_model`, at the current batch size."""
        return dataclasses.replace(self.domain,
                                   batch=self.B).serving_throughput()
