"""The distributed stencil driver: the 2D-decomposed depth-T halo exchange,
with two interchangeable exchange engines and optional compute overlap.

One driver serves every operator. `_operator` turns `spec=None` (PW
advection of (u, v, w) through the hand-written `advect_fused`) or a
`StencilSpec` (its fields through `stencil_fused`) into a field count, a
wall width, a halo depth and a per-shard update; the local block, the
shard_map wrapper and the run core below move that tuple of fields and
never ask which operator it is.

Each shard of the (nx, ny) mesh owns an (X/nx, Y/ny, Z) slab
(`make_distributed_step(axis="y", x_axis="x")`; an axis of size 1 exchanges
nothing). ONE depth-T exchange serves T Euler substeps: each substep
contaminates one more halo row/plane, so depth-T halos are exactly consumed
after T substeps — the collective is amortised over T exactly like the HBM
pass the v4 fused kernel amortises. The exchange is two-phase, X-THEN-Y:
phase 1 trades depth-T x-planes of the raw shard along the x ring; phase 2
trades depth-T y-rows of the x-EXTENDED slab along the y ring. The corner
contract lives entirely in that ordering — a y-neighbour's x-extended rows
already contain its x-halo columns, so the four (T, T, Z) corner blocks
ride phase 2 and no diagonal (8-neighbour) communication is ever issued.
Reordering the phases (or exchanging y on the unextended slab) silently
zeroes the corners; the scaling2d benchmark's counted-vs-modelled wire-byte
gate and the corner regression test pin the contract. The wrapped ring is
periodic: halo data that wraps past the global edge is wrong by
construction and is frozen by the global-interior masks every engine
shares.

`exchange=` selects the transport for those bands (both engines move
byte-identical bands through byte-identical phases, so
`roofline.halo_wire_bytes_model` prices either):

  * ``"collective"`` — `lax.ppermute`, scheduled by XLA. Multi-hop: when T
    exceeds a shard's local extent, hop k is a distance-k ppermute fetching
    the k-away neighbour's share directly, so ceil(T/local) permutes per
    side move exactly T rows total. With `overlap=True` the interior pass
    has no data dependence on the permutes, so XLA *may* hide the exchange
    behind it — an opportunity, not a guarantee
    (`roofline.XLA_OVERLAP_DISCOUNT`).
  * ``"remote_dma"`` — the paper-faithful §IV endgame: the bands move by
    `pltpu.make_async_remote_copy` issued from INSIDE a Pallas kernel
    (`kernels.advection.advection.halo_band_exchange_dma`) into
    double-buffered recv slabs (slot = substep-block k % 2, so block k+1's
    bands land while block k computes). The kernel owns its issue/wait
    schedule instead of trusting XLA. Multi-hop like the collective
    engine: one `make_async_remote_copy` per `_band_schedule` hop, each
    landing at its recv-slab offset, so T beyond the local extent moves
    without an engine fallback. Compiled mode requires a TPU backend
    (Mosaic semaphores have no CPU lowering); in interpret mode the
    engine runs a schedule-faithful emulation — the same per-hop band
    messages and recv-slab assembly offsets (`_band_schedule`),
    transported by ppermute — which `tests/test_distributed_remote_dma.py`
    and `tests/test_pipeline_driver.py` hold BITWISE-equal to the
    collective engine.

The slot parity is exploited by the pipelined multi-block driver
`make_distributed_run(n_blocks=K)`: ONE jitted program runs K
substep-blocks (K*T substeps) with the block counter threaded as a TRACED
`lax.fori_loop` induction variable into the engine's recv-slot selection,
so the step body is traced exactly once for any K and alternating parity
gives block k+1's bands a vacant recv slot to land in while block k's
interior pass computes. `roofline.pipeline_efficiency_model` prices that
INTENDED steady-state schedule; the traced body today still orders
exchange before compute within each block, so realising the cross-block
landing needs the boundary-first async continuation the ROADMAP lists —
the gates here are trace-once and bitwise equivalence, not measured
overlap.

`local_kernel="fused"` runs the per-shard slab update through the v4
Pallas kernel instead of the jnp reference loop, composing the depth-T
exchange with the kernel's in-grid `(y_tile, x)` tiling: the shard's slab
streams through ONE kernel launch whose VMEM register is bounded by
`y_tile` while the wrapped (periodic) halo rows/planes are frozen via the
kernel's `(x_interior_mask, y_interior_mask)` — the same global-interior
masks the reference loop applies per substep. A `pallas_call` declares no
varying-across-mesh-axes (vma) type for its outputs, so any step using a
Pallas kernel per shard is built with ``check_vma=False``: outputs are
fully sharded along the mesh axes anyway so no information is lost, but
shard_map will no longer error if a future edit accidentally consumes an
unreduced value — the distributed equivalence tests are the guard.

`overlap=True` splits each shard's update into an interior pass (owned
slab only — no data dependence on any exchange, the §IV DMA/compute
overlap chip-to-chip) and a boundary pass on the halo'd slab; the T-deep
bands adjacent to a cut are then selected from the boundary pass,
everything else from the interior pass.
`roofline.overlap_efficiency_model` prices how much of the exchange each
engine hides behind that interior pass, and
`RooflineTerms.collective_exposed_s` is the wire time left on the critical
path — the quantity `benchmarks/overlap_sweep.py` sweeps.

Runs under `shard_map` over any mesh axes (smoke-tested on the host mesh;
`launch.mesh.make_stencil_mesh` builds the (nx, ny) production shape).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.advection import advection as K
from repro.kernels.advection.ref import (AdvectParams, pw_advect_ref,
                                         pw_step_ref)
from repro.stencil import spec as SP

EXCHANGES = ("collective", "remote_dma")

# The per-hop band schedule lives in the kernels layer (`_kernel_band_dma`
# issues one `make_async_remote_copy` per entry); re-exported here because
# the ppermute emulation, the wire pricing and the tests all address recv
# slabs through it.
_band_schedule = K._band_schedule


class HaloCorrupted(RuntimeError):
    """A verified exchange received a band whose checksum mismatched: the
    moved bytes are not the sent bytes, so the fields downstream of the
    exchange are untrustworthy. Raised host-side by `check_integrity`
    (and the resilient driver) from the per-shard mismatch flags a
    `verify_integrity=True` step/run returns; the recovery contract is
    roll back to the last checkpoint and replay."""


def check_integrity(flags) -> None:
    """Raise `HaloCorrupted` if any shard's verified exchange counted a
    band checksum mismatch. `flags` is the uint32 mismatch-count array a
    `verify_integrity=True` step or run returns as its last output (one
    entry per shard; a run accumulates over its blocks)."""
    bad = int(np.sum(np.asarray(flags), dtype=np.uint64))
    if bad:
        raise HaloCorrupted(
            f"{bad} halo band checksum mismatch(es) across shards; the "
            f"exchanged fields are not trustworthy — roll back to the "
            f"last checkpoint and replay")


def _corrupt_band(g, dim: int, rows: int, value: float):
    """Fault-injection hook: overwrite the leading `rows` planes/rows of a
    RECEIVED band with `value` — damage on the wire, after the sender's
    checksum was computed, so a verified exchange must detect it."""
    idx = [slice(None)] * g.ndim
    idx[dim] = slice(0, rows)
    return g.at[tuple(idx)].set(value)


def _exchange_halos(f, axis: str, depth: int = 1, dim: int = 1,
                    *, integrity_out=None, corrupt=None):
    """Fetch `depth` rows (dim=1) or planes (dim=0) per side from the ring
    of shards on mesh axis `axis`. Returns (hi_from_prev, lo_from_next):
    hi = the `depth` rows just below my slab (tails of my predecessors),
    lo = the `depth` rows just above it (heads of my successors).

    Multi-hop: when `depth` exceeds the local extent L, hop k (a
    distance-k ppermute with a static pair table) fetches the k-away
    neighbour's share directly: hop 1 moves min(L, depth) rows,
    hop k moves min(L, depth-(k-1)L), so ceil(depth/L) permutes per side
    carry exactly `depth` rows total — bytes-on-wire are hop-count
    independent. The ring is periodic; rows that wrap past the global
    domain carry wrong data by construction and MUST be frozen by the
    caller's global-interior mask.

    Integrity (`integrity_out` a list): every band message additionally
    carries its `kernels.advection.band_checksum` word through the SAME
    permutation, the receiver recomputes the word over the received band,
    and one uint32 mismatch indicator per band is appended to the list —
    4 extra wire bytes per band (`roofline.integrity_bytes_model`),
    fields bit-untouched. `corrupt=(rows, value)` is the fault hook:
    damage the hop-1 received hi band AFTER the send-side checksum, as
    wire corruption would.
    """
    L = f.shape[dim]
    n = jax.lax.axis_size(axis)
    hops = -(-depth // L)

    def part(g, lo, hi):
        idx = [slice(None)] * g.ndim
        idx[dim] = slice(lo, hi)
        return g[tuple(idx)]

    hi_parts, lo_parts = [], []
    for k in range(1, hops + 1):
        cnt = min(L, depth - (k - 1) * L)
        fwd = [(i, (i + k) % n) for i in range(n)]
        bwd = [(i, (i - k) % n) for i in range(n)]
        # tail of the k-away predecessor -> me; head of the k-away successor
        hi_band, lo_band = part(f, L - cnt, L), part(f, 0, cnt)
        hi_recv = jax.lax.ppermute(hi_band, axis, fwd)
        lo_recv = jax.lax.ppermute(lo_band, axis, bwd)
        if integrity_out is not None:
            hi_ck = jax.lax.ppermute(K.band_checksum(hi_band), axis, fwd)
            lo_ck = jax.lax.ppermute(K.band_checksum(lo_band), axis, bwd)
        if corrupt is not None and k == 1:
            hi_recv = _corrupt_band(hi_recv, dim, min(corrupt[0], cnt),
                                    corrupt[1])
        if integrity_out is not None:
            integrity_out.append(
                (K.band_checksum(hi_recv) != hi_ck).astype(jnp.uint32))
            integrity_out.append(
                (K.band_checksum(lo_recv) != lo_ck).astype(jnp.uint32))
        hi_parts.append(hi_recv)
        lo_parts.append(lo_recv)
    if hops == 1:
        return hi_parts[0], lo_parts[0]
    # hi: farthest predecessor first so global coordinates stay ascending
    return (jnp.concatenate(hi_parts[::-1], axis=dim),
            jnp.concatenate(lo_parts, axis=dim))


def _exchange_remote_dma_emulated(f, axis: str, depth: int,
                                  dim: int, *, integrity_out=None,
                                  corrupt=None):
    """Interpret-mode transport for the `remote_dma` engine: the DMA
    kernel's exact schedule — one contiguous band message per (side, hop),
    each landing at its `_band_schedule` recv-slab offset in a
    zero-initialised extended slab — with `lax.ppermute` standing in for
    `make_async_remote_copy` (Mosaic semaphores have no CPU path). Wire
    accounting is unchanged: one ppermute operand per band message, so
    `count_exchange_wire_bytes` prices this engine identically to the
    collective one. Returns the extended slab directly (the engine owns
    its assembly, unlike `_exchange_halos`' (hi, lo) contract); the tests
    gate it bitwise-equal against the collective concatenation.

    `integrity_out` / `corrupt` mean what they mean on `_exchange_halos`:
    one checksum word rides each band message, the receiver verifies it
    after the (optional) injected wire damage to the hop-1 hi band.
    """
    L = f.shape[dim]
    n = jax.lax.axis_size(axis)

    def band(g, lo, hi):
        idx = [slice(None)] * g.ndim
        idx[dim] = slice(lo, hi)
        return g[tuple(idx)]

    ext_shape = list(f.shape)
    ext_shape[dim] += 2 * depth
    ext = jnp.zeros(tuple(ext_shape), f.dtype)

    def place(acc, buf, off):
        idx = [slice(None)] * acc.ndim
        idx[dim] = slice(off, off + buf.shape[dim])
        return acc.at[tuple(idx)].set(buf)

    ext = place(ext, f, depth)   # owned block
    for k, cnt, hi_off, lo_off in _band_schedule(L, depth):
        fwd = [(i, (i + k) % n) for i in range(n)]
        bwd = [(i, (i - k) % n) for i in range(n)]
        hi_band, lo_band = band(f, L - cnt, L), band(f, 0, cnt)
        hi_recv = jax.lax.ppermute(hi_band, axis, fwd)
        lo_recv = jax.lax.ppermute(lo_band, axis, bwd)
        if integrity_out is not None:
            hi_ck = jax.lax.ppermute(K.band_checksum(hi_band), axis, fwd)
            lo_ck = jax.lax.ppermute(K.band_checksum(lo_band), axis, bwd)
        if corrupt is not None and k == 1:
            hi_recv = _corrupt_band(hi_recv, dim, min(corrupt[0], cnt),
                                    corrupt[1])
        if integrity_out is not None:
            integrity_out.append(
                (K.band_checksum(hi_recv) != hi_ck).astype(jnp.uint32))
            integrity_out.append(
                (K.band_checksum(lo_recv) != lo_ck).astype(jnp.uint32))
        ext = place(ext, hi_recv, hi_off)
        ext = place(ext, lo_recv, lo_off)
    return ext


def remote_dma_schedule_wire_bytes(Xl: int, Yl: int, Z: int, itemsize: int,
                                   *, nx: int = 1, ny: int = 1,
                                   T: int = 1, n_fields: int = 3) -> int:
    """Per-shard sent bytes of the remote-DMA engine's actual schedule:
    the summed `_band_schedule` message sizes over both sides of the
    two-phase x-then-y exchange (phase 2 operands are x-EXTENDED — the
    corner blocks). Computed from the messages the engine issues, NOT from
    `roofline.halo_wire_bytes_model`'s closed form;
    `tests/test_overlap_accounting.py` holds the two EXACTLY equal,
    pinning the DMA schedule to the priced model."""
    total = 0
    if nx > 1:
        total += sum(2 * cnt * Yl * Z
                     for _, cnt, _, _ in _band_schedule(Xl, T))
    x_ext = Xl + (2 * T if nx > 1 else 0)
    if ny > 1:
        total += sum(2 * cnt * x_ext * Z
                     for _, cnt, _, _ in _band_schedule(Yl, T))
    return total * n_fields * itemsize


def make_distributed_advect(mesh: Mesh, params: AdvectParams,
                            axis: str = "data"):
    """Returns jit(advect) over fields sharded (None, axis, None) in y.

    LEGACY rung: the original 1D depth-1 source-only exchange, kept as the
    minimal overlap exemplar. New work composes depth-T halos, the 2D
    x-then-y phases and the exchange engines via `make_distributed_step`.
    """

    def local(u, v, w):
        """Per-shard: exchange halos, compute interior meanwhile, patch edges."""
        # 1) launch halo exchange (6 edge planes, tiny vs the slab)
        halos = [_exchange_halos(f, axis) for f in (u, v, w)]
        # 2) interior compute — no dependence on `halos`, so XLA overlaps the
        #    collective-permutes with this stencil (the §IV overlap on ICI)
        interior = pw_advect_ref(u, v, w, params)
        # 3) boundary patch: rebuild the two edge y-bands with halo rows
        n = mesh.shape[axis]
        idx = jax.lax.axis_index(axis)

        def with_halo(f, h):
            prev_hi, next_lo = h
            return jnp.concatenate([prev_hi, f, next_lo], axis=1)

        uh, vh, wh = (with_halo(f, h) for f, h in zip((u, v, w), halos))
        full = pw_advect_ref(uh, vh, wh, params)
        band = [s[:, 1:-1, :] for s in full]   # drop halo rows back off
        # interior rows are identical; edge rows (y=0 / y=-1 of the slab) come
        # from the halo'd compute. For edge shards the global boundary stays 0.
        Y = u.shape[1]
        rows = jnp.arange(Y)
        is_edge_row = (rows < 1) | (rows >= Y - 1)
        gl = (idx == 0)
        gh = (idx == n - 1)
        glob_lo = gl & (rows < 1)
        glob_hi = gh & (rows >= Y - 1)
        keep_band = is_edge_row & ~(glob_lo | glob_hi)
        sel = keep_band[None, :, None]
        out = [jnp.where(sel, b, i) for b, i in zip(band, interior)]
        return tuple(out)

    spec = P(None, axis, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=(spec, spec, spec))
    return jax.jit(fn)


def resolve_modes(mesh: Mesh, interpret: Optional[bool],
                   local_kernel: Optional[str]) -> Tuple[bool, str]:
    """The Pallas mode and per-shard kernel a caller left open, derived
    from the mesh: on TPU devices the compiled fused kernel; anywhere else
    the Pallas interpreter and the jnp reference loop."""
    interpret = K.resolve_interpret(interpret, mesh=mesh)
    if local_kernel is None:
        local_kernel = "reference" if interpret else "fused"
    return interpret, local_kernel


class _Operator(NamedTuple):
    """What the driver needs to know of the operator it moves."""
    n_fields: int
    r: int              # wall width: the outermost r cells never change
    D: int              # halo depth one exchange serves (T substeps)
    substeps: Callable  # (fields, x_int, y_int, y_tile) -> fields
    compiled_dma: bool  # the compiled Mosaic DMA kernel can carry it
    checkpoints: bool   # `_run_state` can snapshot its fields
    vmem: dict          # extra keywords of its VMEM plan


def _operator(params: AdvectParams, spec, spec_params, T: int, dt: float,
              local_kernel: str, interpret: bool) -> _Operator:
    """The operator a driver moves, and the one place that branches on
    `spec`: `spec=None` is PW advection of (u, v, w) through the
    hand-written `advect_fused`; a `StencilSpec` moves its `n_fields`
    fields through `stencil_fused`, exchanging at depth `spec.halo(T)`.
    The reference loop (`local_kernel="reference"`) applies the same
    global-interior mask every substep."""
    if spec is None:
        n, r, D, rk2 = 3, 1, T, False

        def fused(fields, **kw):
            return K.advect_fused(*fields, params, **kw)

        def sources(fields):
            return pw_advect_ref(*fields, params)
        vmem = dict(context="distributed block")
    else:
        if not isinstance(spec, SP.StencilSpec):
            raise ValueError(f"spec must be a StencilSpec, "
                             f"got {type(spec)!r}")
        n, r, D = spec.n_fields, spec.radius, spec.halo(T)
        rk2 = spec.integrator == "rk2"

        def fused(fields, **kw):
            return K.stencil_fused(fields, spec_params, spec, **kw)

        def sources(fields):
            return SP.spec_sources(fields, spec_params, spec)
        vmem = dict(spec=spec, context="spec-driven distributed block")

    def euler(fields, srcs, m, h):
        return tuple(f + h * jnp.where(m, s, 0.0)
                     for f, s in zip(fields, srcs))

    def substeps(fields, x_int, y_int, tile):
        """T masked integrator steps on a (halo'd) slab; None mask =
        all-interior (the slab edge is then the true boundary, walled
        structurally)."""
        if local_kernel == "fused":
            return fused(
                fields, T=T, dt=dt, interpret=interpret, y_tile=tile,
                x_interior_mask=(None if x_int is None
                                 else x_int.astype(jnp.float32)),
                y_interior_mask=(None if y_int is None
                                 else y_int.astype(jnp.float32)))
        m = jnp.ones((), jnp.bool_)
        if x_int is not None:
            m = m & x_int[:, None, None]
        if y_int is not None:
            m = m & y_int[None, :, None]
        for _ in range(T):
            if rk2:
                half = euler(fields, sources(fields), m, 0.5 * dt)
                fields = euler(fields, sources(half), m, dt)
            else:
                fields = euler(fields, sources(fields), m, dt)
        return fields

    return _Operator(n, r, D, substeps, compiled_dma=spec is None,
                     checkpoints=spec is None, vmem=vmem)


def _check_config(op: _Operator, T: int, local_kernel: str, exchange: str,
                  interpret: bool, mesh: Mesh, verify_integrity: bool = False,
                  corrupt_halo=None) -> None:
    """Build-time validation shared by the step and run drivers.
    `corrupt_halo`'s field index is bounded by the operator's field
    count."""
    if not carries_checksums(exchange, interpret):
        if verify_integrity:
            raise RuntimeError(
                "verify_integrity=True rides checksum words on the "
                "ppermute transports (collective engine and the "
                "remote-DMA emulation); the compiled Mosaic DMA kernel "
                "carries no checksum channel yet. Use interpret=True or "
                "exchange='collective'.")
        if corrupt_halo is not None:
            raise RuntimeError(
                "corrupt_halo injects wire damage in the ppermute "
                "transports; the compiled Mosaic DMA kernel has no "
                "injection hook. Use interpret=True.")
    if corrupt_halo is not None:
        fi, depth, _ = corrupt_halo
        if not (0 <= int(fi) < op.n_fields):
            raise ValueError(f"corrupt_halo field index must be "
                             f"0..{op.n_fields - 1}, got {fi}")
        if int(depth) < 1:
            raise ValueError(f"corrupt_halo depth must be >= 1, "
                             f"got {depth}")
    if T < 1:
        raise ValueError(f"T must be >= 1, got {T}")
    if local_kernel not in ("reference", "fused"):
        raise ValueError(f"local_kernel must be 'reference' or 'fused', "
                         f"got {local_kernel!r}")
    if exchange not in EXCHANGES:
        raise ValueError(f"exchange must be one of {EXCHANGES}, "
                         f"got {exchange!r}")
    if exchange == "remote_dma" and not interpret:
        platform = mesh.devices.flat[0].platform
        if platform != "tpu":
            raise RuntimeError(
                f"exchange='remote_dma' in compiled mode issues "
                f"pltpu.make_async_remote_copy from inside a Pallas kernel "
                f"and needs TPU devices (Mosaic); this mesh holds "
                f"{platform!r} devices. Use exchange='collective', or "
                "interpret=True for the schedule-faithful emulation.")
        if not op.compiled_dma:
            raise RuntimeError(
                "spec-driven steps have no compiled Mosaic DMA kernel yet "
                "(the hand-written halo_band_exchange_dma is 3-field "
                "advection-specific); use exchange='collective', or "
                "interpret=True for the schedule-faithful emulation.")


def carries_checksums(exchange: str, interpret: bool) -> bool:
    """Whether `exchange` can ride checksum words on its band messages:
    both ppermute transports do (the collective engine, and the remote-DMA
    emulation in interpret mode); the compiled Mosaic DMA kernel has no
    checksum channel yet."""
    return exchange != "remote_dma" or interpret


def _flag_shape(x_axis: Optional[str]):
    """Per-shard shape of the integrity mismatch count (out_spec puts one
    entry per shard in the global array)."""
    return (1,) if x_axis is None else (1, 1)


def _build_local_block(mesh: Mesh, op: _Operator, *, axis: str,
                       x_axis: Optional[str], T: int, local_kernel: str,
                       y_tile: Optional[int], interpret: bool,
                       overlap: bool, exchange: str,
                       verify_integrity: bool = False,
                       corrupt_halo=None):
    """The per-shard substep-block body shared by `make_distributed_step`
    (one block, static `dma_block_index`) and `make_distributed_run`
    (K blocks, the block counter a traced `fori_loop` induction variable
    feeding the remote-DMA engine's recv-slot parity). Returns
    ``local_block(fields, block_index) -> fields``, a tuple of
    `op.n_fields` slabs, or with `verify_integrity` ``-> fields +
    (mismatch,)`` where `mismatch` is the shard's uint32 count of
    band-checksum mismatches this block (`_flag_shape`-shaped so
    `_wrap_shard_map` can lay one per shard). `corrupt_halo=(field_idx,
    rows, value)` injects wire damage into that field's hop-1 hi band on
    the LAST exchanged phase (y when y is decomposed, else x) — the
    detection path's fault hook.
    """
    n_y = mesh.shape[axis]
    n_x = mesh.shape[x_axis] if x_axis is not None else 1
    r, D = op.r, op.D

    def local_block(fields, block_index):
        Xl, Yl, Z = fields[0].shape
        X_g, Y_g = n_x * Xl, n_y * Yl
        dx = D if n_x > 1 else 0
        dy = D if n_y > 1 else 0
        if dy and D > Y_g - 2 * r:
            raise ValueError(
                f"halo depth {D} (T={T}) exceeds the decomposable global "
                f"Y extent ({Y_g} rows, interior {Y_g - 2 * r}); lower T")
        if dx and D > X_g - 2 * r:
            raise ValueError(
                f"halo depth {D} (T={T}) exceeds the decomposable global "
                f"X extent ({X_g} planes, interior {X_g - 2 * r}); lower T")
        if local_kernel == "fused":
            # static VMEM budget: the ring register summed against
            # VMEM_PER_CORE at trace time, so an over-budget config
            # fails BEFORE compile with the buffer named (the analysis
            # layer's vmem pass)
            from repro.analysis import vmem as _vmem
            _vmem.distributed_block_plan(
                (Xl, Yl, Z), T=T, itemsize=fields[0].dtype.itemsize,
                local_kernel=local_kernel, y_tile=y_tile, nx=n_x, ny=n_y,
                **op.vmem).check()
        iy = jax.lax.axis_index(axis)
        ix = jax.lax.axis_index(x_axis) if dx else None

        # ---- integrity / fault-injection plumbing: one mismatch word per
        # verified band collects in `integrity_out`; `corrupt_halo` damage
        # lands on the LAST exchanged phase so it survives into the slab.
        integrity_out = [] if verify_integrity else None
        corrupt_dim = None
        if corrupt_halo is not None and (dx or dy):
            corrupt_dim = 1 if dy else 0

        # ---- two-phase exchange: x first, then y on the x-extended slab
        # (phase 2's rows carry phase 1's corner columns — see module doc).
        # `_extend` is the engine dispatch; every engine returns the same
        # extended slab, so the corner contract is engine-independent.
        def _extend(fields, ax_name, n, dim, cid):
            def _corrupt_for(fi):
                if corrupt_dim != dim or fi != int(corrupt_halo[0]):
                    return None
                return (int(corrupt_halo[1]), corrupt_halo[2])

            if exchange == "remote_dma":
                if interpret:
                    return tuple(
                        _exchange_remote_dma_emulated(
                            f, ax_name, D, dim,
                            integrity_out=integrity_out,
                            corrupt=(_corrupt_for(fi)
                                     if corrupt_halo is not None else None))
                        for fi, f in enumerate(fields))
                bands = K.halo_band_exchange_dma(
                    *fields, axis=ax_name, mesh_axes=mesh.axis_names,
                    n=n, depth=D, dim=dim, block_index=block_index,
                    collective_id=cid)
                return tuple(jnp.concatenate([hi, f, lo], axis=dim)
                             for f, (hi, lo) in zip(fields, bands))
            hs = [_exchange_halos(f, ax_name, depth=D, dim=dim,
                                  integrity_out=integrity_out,
                                  corrupt=(_corrupt_for(fi)
                                           if corrupt_halo is not None
                                           else None))
                  for fi, f in enumerate(fields)]
            return tuple(jnp.concatenate([h[0], f, h[1]], axis=dim)
                         for f, h in zip(fields, hs))

        def _with_flag(out):
            if not verify_integrity:
                return out
            mismatch = jnp.zeros((), jnp.uint32)
            for m in (integrity_out or []):
                mismatch = mismatch + m.reshape(())
            return out + (mismatch.reshape(_flag_shape(x_axis)),)

        ext = fields
        if dx:
            with jax.named_scope("exchange_x"):
                ext = _extend(ext, x_axis, n_x, 0, 0)
        if dy:
            with jax.named_scope("exchange_y"):
                ext = _extend(ext, axis, n_y, 1, 1)

        # ---- global-interior masks over the slab coordinates: the wall
        # is `r` cells wide (a radius-r stencil cannot carry values past
        # r frozen cells)
        x_int = y_int = None
        if dx:
            gx = ix * Xl - dx + jnp.arange(Xl + 2 * dx)
            x_int = (gx >= r) & (gx <= X_g - 1 - r)
        if dy:
            gy = iy * Yl - dy + jnp.arange(Yl + 2 * dy)
            y_int = (gy >= r) & (gy <= Y_g - 1 - r)

        # ---- boundary pass (consumes the exchange), trimmed to owned rows
        with jax.named_scope("compute"):
            outs = op.substeps(ext, x_int, y_int, y_tile)
            out = tuple(f[dx:dx + Xl, dy:dy + Yl, :] for f in outs)
        if not (overlap and (dx or dy)):
            return _with_flag(out)

        # ---- interior pass: owned slab only, no exchange dependence.
        # Shard-cut edges act as walls contaminating < D cells inward; the
        # select below discards exactly those bands.
        ox_int = oy_int = None
        if dx:
            ogx = ix * Xl + jnp.arange(Xl)
            ox_int = (ogx >= r) & (ogx <= X_g - 1 - r)
        if dy:
            ogy = iy * Yl + jnp.arange(Yl)
            oy_int = (ogy >= r) & (ogy <= Y_g - 1 - r)
        with jax.named_scope("compute_interior"):
            inner = op.substeps(fields, ox_int, oy_int, y_tile)
        sx = jnp.arange(Xl)
        ok_x = jnp.ones((Xl,), jnp.bool_) if not dx else (
            ((ix == 0) | (sx >= D)) & ((ix == n_x - 1) | (sx < Xl - D)))
        sy = jnp.arange(Yl)
        ok_y = jnp.ones((Yl,), jnp.bool_) if not dy else (
            ((iy == 0) | (sy >= D)) & ((iy == n_y - 1) | (sy < Yl - D)))
        sel = (ok_x[:, None] & ok_y[None, :])[:, :, None]
        return _with_flag(tuple(jnp.where(sel, i, b)
                                for i, b in zip(inner, out)))

    return local_block


def _wrap_shard_map(local, mesh: Mesh, n_fields: int, axis: str,
                    x_axis: Optional[str], local_kernel: str, exchange: str,
                    interpret: bool, *, integrity: bool = False,
                    n_scalars: int = 0, donate: bool = False):
    """jit(shard_map(local)) over `n_fields` sharded slabs, with the repo's
    spec/check_vma conventions.

    `integrity` appends the per-shard mismatch flag to the out_specs
    (one `_flag_shape` entry per shard, laid out along the mesh axes);
    `n_scalars` appends replicated scalar inputs (the run core's traced
    block bounds). Compiled, the field arguments and results keep the
    kernels' row-major layout (`kernels.advection.field_format`), so no
    field is relaid out on entry or exit; a caller places its fields with
    `field_formats`. `donate` hands the field arguments' buffers to the
    program.
    """
    spec = (P(None, axis, None) if x_axis is None
            else P(x_axis, axis, None))
    flag_spec = P(axis) if x_axis is None else P(x_axis, axis)
    # check_vma=False whenever a Pallas kernel runs per shard (the fused
    # local kernel, or the compiled remote-DMA exchange) — rationale in the
    # module docstring, documented once there.
    uses_pallas = (local_kernel == "fused"
                   or (exchange == "remote_dma" and not interpret))
    out_specs = (spec,) * n_fields
    if integrity:
        out_specs = out_specs + (flag_spec,)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec,) * n_fields + (P(),) * n_scalars,
                       out_specs=out_specs,
                       check_vma=not uses_pallas)
    donate_argnums = tuple(range(n_fields)) if donate else ()
    if interpret:
        return jax.jit(fn, donate_argnums=donate_argnums)
    fmt = K.field_format(NamedSharding(mesh, spec))
    rep = NamedSharding(mesh, P())
    return jax.jit(
        fn, in_shardings=(fmt,) * n_fields + (rep,) * n_scalars,
        out_shardings=((fmt,) * n_fields
                       + tuple(NamedSharding(mesh, s)
                               for s in out_specs[n_fields:])),
        donate_argnums=donate_argnums)


def field_formats(mesh: Mesh, axis: str = "data",
                  x_axis: Optional[str] = None):
    """Where a compiled step or run on `mesh` takes its fields: the
    sharding of the decomposition in the kernels' row-major layout.
    `jax.device_put(field, field_formats(mesh, ...))` places a host array
    there directly."""
    spec = (P(None, axis, None) if x_axis is None
            else P(x_axis, axis, None))
    return K.field_format(NamedSharding(mesh, spec))


def make_distributed_step(mesh: Mesh, params: AdvectParams, *,
                          axis: str = "data", x_axis: Optional[str] = None,
                          T: int = 1, dt: float = 1.0,
                          local_kernel: Optional[str] = None,
                          y_tile: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          overlap: bool = False,
                          exchange: str = "collective",
                          dma_block_index: int = 0,
                          verify_integrity: bool = False,
                          corrupt_halo=None,
                          spec=None, spec_params=None):
    """Returns jit(step): T Euler substeps per ONE depth-T halo exchange.

    `spec=` (a `stencil.spec.StencilSpec`, with `spec_params=` whatever its
    `pack_params` consumes) generalises the step beyond PW advection: the
    returned jit takes `spec.n_fields` slabs and the ONE exchange runs at
    depth `spec.halo(T) = radius * stages * T` — deeper stencils and the
    RK2 integrator simply exchange deeper, through the same driver and
    the same two-phase engines (`params` is ignored; pass the spec's
    params via `spec_params`). Only the operator differs (`_operator`);
    the compiled Mosaic DMA kernel carries (u, v, w) alone, so a spec
    build refuses it.

    `axis` is the mesh axis decomposing y. With `x_axis` the step runs on a
    2D (x, y) device mesh — each shard owns an (X/nx, Y/ny, Z) slab and the
    exchange is the two-phase x-then-y ordering described in the module
    docstring (corners ride phase 2; no diagonal sends). An axis of size 1
    exchanges nothing along that direction.

    Every exchange engine's wrapped ring is periodic, so shards at the
    global edges receive wrapped (wrong) halo data — but every substep
    masks the source to zero outside the *global* interior, and a depth-1
    stencil cannot carry values past an unchanging row: the global-boundary
    row is a wall, the wrapped rows never contaminate the trimmed result.
    The same mask argument lifts the old T <= local-extent restriction on
    BOTH engines: multi-hop `_exchange_halos` / `halo_band_exchange_dma`
    fetch arbitrarily deep halos, so the only hard bound left is
    T <= global extent - 2 along each decomposed axis (beyond that no
    interior cell exists whose depth-T cone the ring can serve).

    `exchange` selects the band transport (module docstring): "collective"
    is XLA-scheduled ppermute; "remote_dma" issues the bands from inside a
    Pallas kernel via `pltpu.make_async_remote_copy` in compiled mode
    (TPU-only — a mesh of other devices raises RuntimeError at build time;
    multi-hop via one remote copy per `_band_schedule` hop, so T is
    bounded only by the global extent like the collective engine) and
    runs the schedule-faithful ppermute emulation in interpret mode
    (bitwise-equal to "collective", `tests/test_distributed_remote_dma.py`).
    `dma_block_index` is the substep block number k, selecting the
    engine's double-buffered recv slot (k % 2) DYNAMICALLY — alternating
    parity never retraces; `make_distributed_run` threads a traced counter
    through K blocks in one program so block k+1's bands land beside
    block k's.

    `local_kernel` selects the per-shard slab update: "reference" is the
    jnp T-substep loop; "fused" streams the slab through the v4 Pallas
    kernel (one HBM pass for all T substeps), passing the global-interior
    masks as the kernel's `(x_interior_mask, y_interior_mask)` and
    composing with the kernel's in-grid `(y_tile, x)` tiling via `y_tile`
    — the shard slab keeps a VMEM-bounded register no matter how wide the
    shard is.

    `interpret=None` and `local_kernel=None` derive from the mesh
    (`resolve_modes`): on TPU devices the compiled fused kernel, anywhere
    else the Pallas interpreter and the jnp reference loop. Compiled, the
    program takes and returns its fields in the kernels' row-major layout
    (`field_formats`).

    `overlap=True` additionally computes the halo-independent interior of
    each shard in a pass that consumes NO exchange output, so it can run
    concurrently with both exchange phases (the paper's §IV DMA/compute
    overlap, chip-to-chip); only the T-deep boundary bands then wait on
    the exchange. The boundary pass covers the whole slab (the repo's
    established overlap idiom, cf. `make_distributed_advect`) — the cost
    is one extra local pass, the win is that the exchange latency is
    hidden behind a full interior update; how much is hidden per engine is
    `roofline.overlap_efficiency_model`'s business.

    Wire cost: T rows per neighbour per exchange (per `roofline.
    halo_wire_bytes_model`, identical for both engines), so bytes-on-wire
    per substep are flat in T while the exchange *count* falls as 1/T —
    latency-bound small halos amortise T×.

    `verify_integrity=True` rides a `kernels.advection.band_checksum`
    uint32 word on every band message of both ppermute transports and
    returns one more output: the per-shard mismatch count (pass to
    `check_integrity` to raise `HaloCorrupted`). The fields are
    bit-untouched — the verified step is BITWISE-equal to the unchecked
    one on clean wires, and the extra bytes are priced by
    `roofline.integrity_bytes_model` / counted by
    `count_integrity_bytes` (both held equal by
    `tests/test_recovery_distributed.py`).
    `corrupt_halo=(field_idx, rows, value)` is the matching fault hook:
    wire damage to one received band, injected after the send-side
    checksum so a verified step MUST flag it. Both knobs need the
    ppermute transports (interpret mode or the collective engine); the
    compiled Mosaic DMA path rejects them at build time.
    """
    interpret, local_kernel = resolve_modes(mesh, interpret, local_kernel)
    op = _operator(params, spec, spec_params, T, dt, local_kernel, interpret)
    _check_config(op, T, local_kernel, exchange, interpret, mesh,
                  verify_integrity, corrupt_halo)
    local_block = _build_local_block(
        mesh, op, axis=axis, x_axis=x_axis, T=T, local_kernel=local_kernel,
        y_tile=y_tile, interpret=interpret, overlap=overlap,
        exchange=exchange, verify_integrity=verify_integrity,
        corrupt_halo=corrupt_halo)

    def local(*fields):
        return local_block(fields, dma_block_index)

    return _wrap_shard_map(local, mesh, op.n_fields, axis, x_axis,
                           local_kernel, exchange, interpret,
                           integrity=verify_integrity)


def _make_run_core(mesh: Mesh, op: _Operator, *, axis: str,
                   x_axis: Optional[str], T: int, local_kernel: str,
                   y_tile: Optional[int], interpret: bool, overlap: bool,
                   exchange: str, verify_integrity: bool,
                   donate: bool = False):
    """The span-generic run program: ``core(*fields, start, end)`` runs
    blocks [start, end) with BOTH bounds traced, so one trace serves the
    full run, every checkpoint interval, and every resume continuation —
    interval boundaries never retrace and the per-block wire/integrity
    counts stay span-independent (the trace-once gate). With
    `verify_integrity` the core returns one more output: per-shard
    mismatch counts ACCUMULATED over the span. `donate` hands the field
    arguments' buffers to the program.
    """
    _check_config(op, T, local_kernel, exchange, interpret, mesh,
                  verify_integrity)
    local_block = _build_local_block(
        mesh, op, axis=axis, x_axis=x_axis, T=T, local_kernel=local_kernel,
        y_tile=y_tile, interpret=interpret, overlap=overlap,
        exchange=exchange, verify_integrity=verify_integrity)

    def local(*args):
        fields, start, end = args[:-2], args[-2], args[-1]
        if verify_integrity:
            def body(k, carry):
                with jax.named_scope("block"):
                    out = local_block(carry[:-1], k)
                return out[:-1] + (carry[-1] + out[-1],)
            # the carried count varies over the mesh axes as the block
            # counts it sums do, or a checked shard_map refuses the loop
            zero = jnp.zeros(_flag_shape(x_axis), jnp.uint32)
            init = fields + (jax.lax.pcast(
                zero, tuple(a for a in (x_axis, axis) if a), to="varying"),)
        else:
            def body(k, carry):
                with jax.named_scope("block"):
                    return local_block(carry, k)
            init = fields
        return jax.lax.fori_loop(start, end, body, init)

    return _wrap_shard_map(local, mesh, op.n_fields, axis, x_axis,
                           local_kernel, exchange, interpret,
                           integrity=verify_integrity, n_scalars=2,
                           donate=donate)


def _run_state(u, v, w, block: int, flags) -> dict:
    """The checkpoint leaf dict: sharded fields host-gathered, plus the
    logical block index and the recv-slot parity the remote-DMA engine's
    double buffering depends on (stored redundantly — `resume` refuses a
    checkpoint whose parity disagrees with its block index)."""
    state = {"u": np.asarray(u), "v": np.asarray(v), "w": np.asarray(w),
             "block": np.int64(block), "parity": np.int64(block % 2)}
    if flags is not None:
        state["mismatches"] = np.asarray(flags, dtype=np.uint32)
    return state


def _checkpointed_segments(core, checkpoint_dir, u, v, w, *, start: int,
                           n_blocks: int, every: int, verify: bool,
                           flags, keep_last: int, save_initial: bool):
    """Drive `core` over [start, n_blocks) in `every`-block segments,
    checkpointing at each boundary (and the final block) through
    `training.checkpoint`'s atomic writes. `flags` carries the mismatch
    counts accumulated BEFORE `start` (restored on resume) so the
    resumed run's flag output equals the uninterrupted run's."""
    from repro.training import checkpoint as CKPT

    if verify and flags is None:
        raise ValueError("verify requires restored-or-zero flags")
    if save_initial:
        CKPT.save(checkpoint_dir, _run_state(u, v, w, start, flags),
                  start, keep_last=keep_last)
    b = start
    while b < n_blocks:
        e = min(b + every, n_blocks)
        out = core(u, v, w, b, e)
        if verify:
            u, v, w, fl = out
            flags = np.asarray(flags + np.asarray(fl), dtype=np.uint32)
        else:
            u, v, w = out
        b = e
        CKPT.save(checkpoint_dir, _run_state(u, v, w, b, flags), b,
                  keep_last=keep_last)
    if verify:
        return u, v, w, jnp.asarray(flags)
    return u, v, w


def make_distributed_run(mesh: Mesh, params: AdvectParams, *,
                         n_blocks: int, axis: str = "data",
                         x_axis: Optional[str] = None,
                         T: int = 1, dt: float = 1.0,
                         local_kernel: Optional[str] = None,
                         y_tile: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         overlap: bool = False,
                         exchange: str = "collective",
                         verify_integrity: bool = False,
                         checkpoint_every: Optional[int] = None,
                         checkpoint_dir=None,
                         keep_last: int = 3,
                         spec=None, spec_params=None,
                         donate: bool = False):
    """Returns run(*fields): `n_blocks` substep-blocks (n_blocks * T Euler
    substeps, ONE depth-T exchange per block) in ONE traced program — the
    pipelined multi-block driver the remote-DMA engine's double-buffered
    recv slabs exist for.

    The block counter is a `lax.fori_loop` induction variable threaded —
    TRACED — into the exchange engine (`dma_block_index` in the one-block
    `make_distributed_step`): the remote-DMA engine's recv-slot parity is
    selected dynamically per block (`lax.rem`-indexed, SMEM `step_ref` in
    the kernel), so alternating parity across blocks costs NO retrace or
    recompile — the step body appears exactly once in the jaxpr for any
    `n_blocks`, and block k+1's bands always have a vacant recv slot to
    land in while block k's interior pass computes. The loop BOUNDS are
    traced too (`_make_run_core`), so the checkpointing driver below runs
    every interval through the same single trace.
    `roofline.pipeline_efficiency_model` prices that INTENDED schedule
    (one fill block, steady-state hidden fraction); scope honesty: the
    traced body still orders exchange before compute within a block, so
    the cross-block landing is what the parity/slots make POSSIBLE, not
    yet what XLA is forced to do — the boundary-first async continuation
    is the ROADMAPped follow-on, and `benchmarks/pipeline_sweep.py` gates
    what IS delivered: one trace for all K blocks and bitwise
    equivalence. Semantics are exactly K sequential
    `make_distributed_step` calls with `dma_block_index = 0..K-1` —
    bitwise, the acceptance gate.

    `verify_integrity` adds the checksummed exchange of
    `make_distributed_step` to every block; the run returns one more
    output accumulating the per-shard mismatch counts over all blocks.

    `checkpoint_every=k` with `checkpoint_dir=` turns the returned run
    into a host-side driver that snapshots the sharded (u, v, w) plus the
    logical block index and recv-slot parity through
    `training.checkpoint`'s atomic writes at every k-block boundary (and
    block 0 and the final block), `keep_last` bounding disk. A run killed
    mid-way resumes via `resume_distributed_run` BITWISE-equal to the
    uninterrupted run (`tests/test_recovery_distributed.py`) because every
    segment replays through the same traced core with the restored block
    index feeding the recv-slot parity. Without checkpointing the
    returned run is a pure jitted program (traceable — the byte-counting
    gates `jax.make_jaxpr` it).

    `donate=True` hands the field arguments' buffers to the program (they
    are deleted by the call): the loop state then lives in them, which is
    what lets a grid the size of one chip's HBM fit once in and once out.

    All other arguments mean what they mean on `make_distributed_step`.
    """
    if n_blocks < 1:
        raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
    if (checkpoint_every is None) != (checkpoint_dir is None):
        raise ValueError("checkpoint_every and checkpoint_dir come "
                         "together: both or neither")
    interpret, local_kernel = resolve_modes(mesh, interpret, local_kernel)
    op = _operator(params, spec, spec_params, T, dt, local_kernel, interpret)
    if checkpoint_every is not None and not op.checkpoints:
        raise ValueError(
            "checkpointing is not wired to the spec-driven run yet "
            "(the snapshot leaf dict is (u, v, w)-specific); run "
            "without spec= or without checkpoint_every=")
    core = _make_run_core(
        mesh, op, axis=axis, x_axis=x_axis, T=T, local_kernel=local_kernel,
        y_tile=y_tile, interpret=interpret, overlap=overlap,
        exchange=exchange, verify_integrity=verify_integrity, donate=donate)

    if checkpoint_every is None:
        def run(*fields):
            return core(*fields, 0, n_blocks)
        return run

    if checkpoint_every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, "
                         f"got {checkpoint_every}")
    flag0 = (np.zeros(_global_flag_shape(mesh, axis, x_axis), np.uint32)
             if verify_integrity else None)

    def run_ck(u, v, w):
        return _checkpointed_segments(
            core, checkpoint_dir, u, v, w, start=0, n_blocks=n_blocks,
            every=checkpoint_every, verify=verify_integrity, flags=flag0,
            keep_last=keep_last, save_initial=True)
    return run_ck


def _global_flag_shape(mesh: Mesh, axis: str, x_axis: Optional[str]):
    return ((mesh.shape[axis],) if x_axis is None
            else (mesh.shape[x_axis], mesh.shape[axis]))


def resume_distributed_run(mesh: Mesh, params: AdvectParams, u, v, w, *,
                           n_blocks: int, checkpoint_dir,
                           checkpoint_every: Optional[int] = None,
                           step: Optional[int] = None,
                           axis: str = "data",
                           x_axis: Optional[str] = None,
                           T: int = 1, dt: float = 1.0,
                           local_kernel: Optional[str] = None,
                           y_tile: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           overlap: bool = False,
                           exchange: str = "collective",
                           verify_integrity: bool = False,
                           keep_last: int = 3):
    """Restore the latest (or `step=`) checkpoint a checkpointing
    `make_distributed_run` wrote under `checkpoint_dir` and continue to
    `n_blocks`, returning what the uninterrupted run would have —
    BITWISE (`tests/test_recovery_distributed.py`): the restored block
    index feeds the recv-slot parity through the same traced core, so
    replayed intervals are the intervals the dead run would have
    executed.

    (u, v, w) are templates for structure/dtype only — their VALUES are
    replaced by the restored snapshot (restoring from the block-0
    checkpoint replays the whole run). `checkpoint_every=None` continues
    in one segment, still writing the final checkpoint. A checkpoint
    whose stored recv-slot parity disagrees with its block index (or
    whose manifest step disagrees with the stored block) is refused with
    a ValueError naming the inconsistency rather than resumed into a
    silently wrong parity. Build arguments must match the original run's.
    """
    from repro.training import checkpoint as CKPT

    if n_blocks < 1:
        raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
    interpret, local_kernel = resolve_modes(mesh, interpret, local_kernel)
    core = _make_run_core(
        mesh, _operator(params, None, None, T, dt, local_kernel, interpret),
        axis=axis, x_axis=x_axis, T=T, local_kernel=local_kernel,
        y_tile=y_tile, interpret=interpret, overlap=overlap,
        exchange=exchange, verify_integrity=verify_integrity)
    like = _run_state(u, v, w, 0,
                      np.zeros(_global_flag_shape(mesh, axis, x_axis),
                               np.uint32) if verify_integrity else None)
    state, disk_step = CKPT.restore(checkpoint_dir, like, step=step)
    block = int(state["block"])
    parity = int(state["parity"])
    if parity != block % 2:
        raise ValueError(
            f"checkpoint step {disk_step} under {checkpoint_dir} is "
            f"inconsistent: stored recv-slot parity {parity} != block "
            f"{block} % 2; refusing to resume into a wrong DMA slot")
    if disk_step != block:
        raise ValueError(
            f"checkpoint step {disk_step} under {checkpoint_dir} stores "
            f"block index {block}; refusing to resume an inconsistent "
            f"snapshot")
    u, v, w = (jnp.asarray(state["u"]), jnp.asarray(state["v"]),
               jnp.asarray(state["w"]))
    flags = (np.asarray(state["mismatches"], dtype=np.uint32)
             if verify_integrity else None)
    if block >= n_blocks:
        if verify_integrity:
            return u, v, w, jnp.asarray(flags)
        return u, v, w
    every = checkpoint_every if checkpoint_every else n_blocks - block
    if every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {every}")
    return _checkpointed_segments(
        core, checkpoint_dir, u, v, w, start=block, n_blocks=n_blocks,
        every=every, verify=verify_integrity, flags=flags,
        keep_last=keep_last, save_initial=False)


# The jaxpr traversal and byte attribution live in `repro.analysis` now
# (ONE walker instead of four copies); re-exported under the old private
# names so existing callers and tests need no edits, and the four
# counters below are thin wrappers whose values are byte-identical to
# the pre-refactor implementations (tests/test_analysis_ledger.py pins
# the equivalence).
from repro.analysis.jaxpr import iter_jaxprs as _iter_jaxprs  # noqa: E402
from repro.analysis.ledger import (  # noqa: E402
    MovementLedger as _MovementLedger,
    count_ppermute_bytes as _count_ppermute_bytes)


def count_exchange_wire_bytes(fn, *args) -> int:
    """Per-shard FIELD bytes `fn` puts on the wire: the summed sizes of
    every rank >= 3 `ppermute` operand in its (recursively walked) jaxpr.

    Inside `shard_map` tracing shapes are per-shard, so each ppermute
    operand is exactly one shard's send buffer. This covers BOTH interpret
    engines — the collective exchange and the remote-DMA emulation, whose
    band messages are one ppermute operand each. Rank >= 3 selects
    exactly the (x, y, z) band payloads; the rank-1 uint32 checksum words
    a `verify_integrity=True` program additionally permutes are counted
    by `count_integrity_bytes` instead, so THIS count is identical with
    verification on or off, as `tests/test_recovery_distributed.py`
    holds (the integrity layer may not change what the band model
    prices). The
    compiled remote-DMA kernel's transfers live inside a `pallas_call`
    and are priced instead by `remote_dma_schedule_wire_bytes` (the same
    `_band_schedule` message sizes the kernel issues), which the overlap
    tests pin to `roofline.halo_wire_bytes_model` exactly. This function
    is the measured counterpart of that model; the scaling2d and overlap
    benchmarks gate the two against each other exactly.

    On a `make_distributed_run` program the `fori_loop` body jaxpr is
    walked ONCE, so the count is the PER-BLOCK wire bytes independent of
    `n_blocks` — which is itself the pipeline benchmark's trace-once
    gate: a driver that unrolled or retraced per block would count K
    times the model.
    """
    return _MovementLedger.of(fn, *args).total("ppermute_wire")


def count_integrity_bytes(fn, *args) -> int:
    """Per-shard CHECKSUM bytes `fn` puts on the wire: the summed sizes
    of every rank < 3 `ppermute` operand in its (recursively walked)
    jaxpr — the `(1,)`-shaped uint32 `band_checksum` words the verified
    exchange rides on each band message, and nothing else (field bands
    are rank 3; `count_exchange_wire_bytes` owns them). Zero on an
    unverified program. The measured counterpart of
    `roofline.integrity_bytes_model`;
    `tests/test_recovery_distributed.py` holds the two equal EXACTLY, per
    block even on a `make_distributed_run` program (the fori body is
    walked once — same trace-once argument as the wire count)."""
    return _MovementLedger.of(fn, *args).total("integrity_words")


def count_pallas_hbm_bytes(fn, *args) -> int:
    """HBM bytes `fn`'s Pallas kernels stream: the summed sizes of every
    rank->=3 operand and result of each `pallas_call` in its (recursively
    walked) jaxpr.

    Rank >= 3 selects exactly the field arrays — the (X, Y, Z) /
    slot-stacked (B, X, Y, Z) inputs the kernel reads once and the outputs
    it writes once. The O(X + Y + Z) control operands (the packed
    coefficient vectors and the interior masks) are deliberately excluded:
    they are scalar-pipeline traffic the analytic model never charged.
    For the fused kernel on lane-aligned Z this count equals
    ``kernels.advection.hbm_bytes_model(..., "fused", grid_tiled=True)``
    EXACTLY (and the batched mega-launch counts B times that) — the
    measured counterpart of the model, held equal to it by
    `tests/test_stencil_serving.py` the way `tests/test_distributed_2d.py`
    holds `count_exchange_wire_bytes` to the wire model.

    The ledger splits the guard pass's field re-read into its own
    category; this counter keeps the legacy semantics (EVERY
    pallas_call's rank >= 3 operands, guard included), so it sums the
    `pallas_hbm` and `guard_field_reads` categories.
    """
    return _MovementLedger.of(fn, *args).total(
        "pallas_hbm", "guard_field_reads")


def count_guard_bytes(fn, *args) -> int:
    """HBM bytes of the finite-guard pass: for every `pallas_call` in
    `fn`'s (recursively walked) jaxpr whose results are ALL rank < 3 —
    the guard kernel's signature; flags are (X,) / vmapped (B, X) while
    every field-moving kernel emits rank >= 3 results — sum the sizes of
    its operands AND results: the field re-read plus the flag words.

    The advection kernels proper are never miscounted (their field
    results are rank >= 3, counted by `count_pallas_hbm_bytes` and
    untouched by guarding), so this isolates exactly the detection
    traffic. Held by `tests/test_faults.py` equal to
    `roofline.guard_bytes_model` EXACTLY — the recovery tier's detection
    traffic priced under the same model-equals-counted discipline as the
    field and wire bytes.
    """
    return _MovementLedger.of(fn, *args).total(
        "guard_field_reads", "guard_flag_words")


def reference_global(u, v, w, params: AdvectParams):
    """Single-device oracle for the distributed version."""
    return pw_advect_ref(u, v, w, params)


def reference_global_step(u, v, w, params: AdvectParams, *, T: int = 1,
                          dt: float = 1.0):
    """Single-device T-substep oracle for `make_distributed_step`."""
    for _ in range(T):
        u, v, w = pw_step_ref(u, v, w, params, dt)
    return u, v, w


def reference_global_spec_step(fields, spec_params, spec, *, T: int = 1,
                               dt: float = 1.0):
    """Single-device T-step oracle for the spec-driven distributed step:
    `spec_multistep`'s zero_source wall is exactly the global-interior
    mask every shard applies, so the sharded program must reproduce this
    BITWISE for any mesh shape."""
    return SP.spec_multistep(fields, spec_params, spec, T, dt)
