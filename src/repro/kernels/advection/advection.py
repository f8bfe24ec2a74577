"""Pallas TPU kernels for PW advection — the paper's Fig. 3 ladder on TPU.

FPGA -> TPU mapping of the paper's stages:

  v1 `blocked`   : grid over x; each step fetches the (x-1, x, x+1) z-y slices
                   of all three fields from HBM into VMEM (three index-mapped
                   views per field). This is the paper's *initial* BRAM-blocked
                   kernel: correct, pipelined by Pallas, but each slice is
                   fetched three times — the "pipeline drains / re-reads"
                   regime.

  v2 `dataflow`  : grid over x with a persistent VMEM shift-register
                   (3, Y, Z) per field. Each step fetches exactly ONE new
                   slice and rotates the register — the paper's "shift the
                   current slices down by one, retrieve x+1" (Listing 1 lines
                   9-13) fused with its dataflow pipeline (Fig. 4): the Pallas
                   grid pipeline double-buffers the incoming slice against
                   compute, so load/compute/store overlap structurally.
                   HBM traffic drops 3x vs v1 — the Fig. 3 rows 3-5 move.
                   `fuse_update=True` additionally folds the explicit-Euler
                   update into the kernel (advanced fields out, not sources),
                   dropping the separate full-field read+write the host-side
                   `f + dt*s` pass would pay.

  v3 `wide`      : v2 with lane-aligned slices (Z a multiple of 128, f32
                   (8,128) tiling). One HBM->VMEM transaction carries 128
                   lanes — the 64->256-bit port widening of Fig. 3 rows 6-7.
                   Kernel body is identical; alignment is a contract on the
                   data layout (checked), and the benchmark charges misaligned
                   grids the measured lane-efficiency penalty.

  v4 `fused`     : temporal blocking — T explicit-Euler steps per HBM pass.
                   The shift register widens to T stacked 3-slice rings, one
                   per time level: as input slice x=i streams in (level 0),
                   level k produces its slice x=i-k from level k-1's ring, so
                   the step-T field leaves the chip the only time it touches
                   HBM. Per T steps the kernel reads 3·X and writes 3·X
                   slices where v2/v3 read+write 6·T·X — HBM traffic drops
                   ~T× (the on-chip-reuse endgame of the paper's Fig. 3
                   progression; cf. Brown 2020/2021 on amortising MONC
                   advection transfers across reuse). Register cost is
                   3 fields × 3T slices; with Y-tiling (halo T per side)
                   it is VMEM-bounded at (3T, TY+2T, Z) per field for any Y.

Grid-tiled execution contract (the `y_tile` path, `tiling="grid"`):

  `blocked`/`dataflow`/`wide`/`fused` accept `y_tile` and run the whole
  domain in ONE kernel launch over a 2D `(y_tile, x)` grid — the y-tile
  index is the outer (slow) grid dimension, x the inner streaming one.
  Element-indexed (`pl.Element`) block dims select each tile's slab
  (`y_tile + 2*halo` rows, clipped flush into the domain at the edges) and
  write each tile's owned rows in place, so there is no host-side restitch
  (`jnp.concatenate`) and no per-tile dispatch. The ring register is sized
  to the slab, `(3, y_tile+2*halo, Z)` / `(T, 3, y_tile+2*T, Z)`, keeping
  VMEM bounded irrespective of Y; it is never cleared between tiles — the
  same startup masking that walls off x<0 slices walls off the stale ring
  content at each tile switch. The stencil's halo re-reads hit the
  VMEM-resident slab rather than issuing per-tile host restaging: the
  write side and the per-tile dispatch/concat are eliminated outright,
  and `hbm_bytes_model(..., grid_tiled=True)` charges the read side at
  compulsory traffic (zero halo overlap), with `vmem_halo_bytes_model`
  carrying the relocated bytes — an idealisation of slab residency: the
  interpret-mode reference still materialises each slab window per grid
  step, so the analytic model (not a measured counter) is the contract
  here, as everywhere in this repo's Fig. 3/8/9 tables. Slab edges behave
  as walls (zero source), exactly like global boundaries; every owned row
  keeps >= halo true rows of margin to a cut edge, so grid-tiled outputs
  are bitwise equal to the untiled kernel. Tiles whose slab would not fit
  (`y_tile + 2*halo > Y`) fall back to the untiled path.

  `wide` grid-tiles with a sublane-rounded fetch halo of 8 rows, so every
  slab keeps the (8,128) layout contract per tile — large-Y grids finally
  get a lane-aligned tiled path (`y_tile` must be a multiple of 8).

  The old host-side loop is retained as `tiling="host"` (`_y_tiled_host`):
  one `pallas_call` per halo-overlapped block plus a host restitch — kept
  as the measurable anti-pattern baseline (the paper's "data movement
  overhead" regime) for BENCH_tiling.json. `wide` still rejects host
  tiling (tile+halo rows cannot satisfy its sublane contract there).

Distributed composition (the 2D (x, y) mesh decomposition of PR 3 and the
exchange engines of PR 4): `stencil.distributed.make_distributed_step`
streams each (X/nx, Y/ny, Z) shard's halo'd slab through the v4 kernel with
ONE depth-T two-phase x-then-y exchange per T substeps (corners ride the y
phase on the x-extended slab), freezing wrapped periodic halo planes/rows
via `(x_interior_mask, y_interior_mask)`. `halo_band_exchange_dma` (below)
is the in-kernel transport for that exchange: the T-deep boundary bands
move by `pltpu.make_async_remote_copy` issued from inside a Pallas kernel
— one copy per `_band_schedule` hop, multi-hop for T beyond the local
extent — into double-buffered recv slabs whose slot parity is selected by
a TRACED block counter, so `stencil.distributed.make_distributed_run` can
alternate slots across K substep-blocks inside one traced program instead
of trusting XLA to schedule a `ppermute` — the paper's §IV "do the data
movement yourself" lesson at the chip-to-chip level.

Compiled vs interpreted: every entry point takes `interpret=None` and
resolves it with `resolve_interpret` — Mosaic where the data lives on a
TPU, the Pallas interpreter elsewhere. The compiled path keeps to what
Mosaic lowers: element-indexed slab blocks whose row offsets are aligned to
the 8-row sublane tile (`_grid_geometry(align=8)` rounds the fetch halo up),
coefficient rows ``(1, Z+2)`` and mask columns ``(N, 1)`` instead of rank-1
vectors, tiled outputs copied out of a VMEM slab at a dynamic row offset,
and the guard's flags in SMEM. Fields stay in the row-major HBM layout
`field_format` names. The fused kernel keeps its ring lane-full: Z rounded
up to the 128-lane vreg, sources computed on whole slices at offset 0 with
neighbours by `pltpu.roll`, one coefficient row ``(1, lanes)`` each.

Validated with interpret=True against ref.pw_advect_ref, the f64 oracle, and
the multi-step f64 oracle (fused) across shape/dtype/T/y_tile sweeps in
tests/test_advection_kernels.py, tests/test_advection_fused.py and
tests/test_advection_grid_tiled.py; tests/test_tpu_compile.py compiles the
main-path kernels (the remote-DMA band kernel included) for a described
TPU v5e at real size, and chip_smoke.py runs them on the chip.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Format, Layout
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.advection.ref import AdvectParams
from repro.launch.mesh import dma_neighbor_coords

TILINGS = ("grid", "host")
_WIDE_HALO = 8   # sublane-rounded fetch halo: keeps wide's (8,128) contract
_SUBLANE = 8     # f32 sublane tile: compiled slab/output row offsets align
_LANE = 128      # vreg lane width: the compiled fused ring's Z rounds up


def resolve_interpret(interpret: Optional[bool] = None, *arrays,
                      mesh=None) -> bool:
    """The Pallas mode when the caller did not choose one: compiled Mosaic
    where the data lives on a TPU, the interpreter anywhere else. A mesh
    decides by its devices and a concrete array by its own; a traced
    value carries no device, so the default backend decides for it."""
    if interpret is not None:
        return bool(interpret)
    if mesh is not None:
        return mesh.devices.flat[0].platform != "tpu"
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            return next(iter(a.devices())).platform != "tpu"
    return jax.default_backend() != "tpu"


def field_format(sharding) -> Format:
    """The row-major HBM layout the kernels stream fields in, on `sharding`.

    XLA's default TPU layout for a (..., Y, Z) f32 array with Z < 128 puts
    Y on the lanes, while the kernels read (rows, Z) slabs with Z on the
    lanes. A compiled program whose field arguments keep the default
    layout copies every field into the kernel's layout on entry and back
    on exit; placing the fields in this layout (`jax.device_put(x, fmt)`)
    and compiling with it (`in_shardings`/`out_shardings`) keeps them in
    place. At Z=64 this layout pads each row to 128 lanes."""
    return Format(Layout(major_to_minor=(0, 1, 2)), sharding)


def _pack_coeffs(p: AdvectParams):
    """Scalars + z-metrics packed into two ``(1, Z+2)`` rows
    ``[tcx, tcy, tzc...]`` — one lane-major row per metric, the layout
    Mosaic accepts for a small operand (rank 1 is refused)."""
    t1 = jnp.concatenate([p.tcx[None], p.tcy[None], p.tzc1])
    t2 = jnp.concatenate([p.tcx[None], p.tcy[None], p.tzc2])
    return t1[None, :], t2[None, :]


def _coeffs(t1_ref, t2_ref):
    """(tcx, tcy, tzc1, tzc2) from the packed rows: ``(1, 1)`` scalars and
    the ``(1, Z-2)`` z-interior metrics, broadcast over (rows, Z-2)."""
    t1, t2 = t1_ref[...], t2_ref[...]
    n = t1.shape[1] - 1
    return 0.0 + t1[:, 0:1], t1[:, 1:2], t1[:, 3:n], t2[:, 3:n]


def _masks(x_interior_mask, y_interior_mask, X: int, Y: int):
    """The interior masks as ``(X, 1)`` / ``(Y, 1)`` f32 columns (all-ones
    when absent): one mask word per row of a sublane-major operand."""
    out = []
    for m, n, name in ((x_interior_mask, X, "x"), (y_interior_mask, Y, "y")):
        m = (jnp.ones((n,), jnp.float32) if m is None
             else jnp.asarray(m, jnp.float32))
        if m.shape != (n,):
            raise ValueError(f"{name}_interior_mask must have shape ({n},), "
                             f"got {m.shape}")
        out.append(m[:, None])
    return tuple(out)


def _plane_ok(xm_ref, j, X: int):
    """``(1, 1)`` flag: may x-plane j (clipped into the domain) take a
    source? A dynamic one-row read of the ``(X, 1)`` x-mask column."""
    return xm_ref[pl.ds(jnp.clip(j, 0, X - 1), 1), :] > 0.0


def _source_slices(um, uc, up, vm, vc, vp, wm, wc, wp, tcx, tcy, t1, t2):
    """PW source terms for one x-slice. Inputs (rows, Z) f32 views;
    `t1`/`t2` are the z-interior metrics (see `_coeffs`)."""
    def inner(f):
        return f[1:-1, 1:-1]

    def sh(f_m, f_c, f_p, di, dj, dk):
        f = {-1: f_m, 0: f_c, 1: f_p}[di]
        Y, Z = f.shape
        return f[1 + dj:Y - 1 + dj, 1 + dk:Z - 1 + dk]

    def source(fm, fc, fp):
        fx = tcx * (sh(um, uc, up, -1, 0, 0) * (inner(fc) + inner(fm))
                    - sh(um, uc, up, 1, 0, 0) * (inner(fc) + inner(fp)))
        fy = tcy * (sh(vm, vc, vp, 0, -1, 0) * (inner(fc) + fc[0:-2, 1:-1])
                    - sh(vm, vc, vp, 0, 1, 0) * (inner(fc) + fc[2:, 1:-1]))
        fz = (t1 * sh(wm, wc, wp, 0, 0, -1) * (inner(fc) + fc[1:-1, 0:-2])
              - t2 * sh(wm, wc, wp, 0, 0, 1) * (inner(fc) + fc[1:-1, 2:]))
        return fx + fy + fz

    return (source(um, uc, up), source(vm, vc, vp), source(wm, wc, wp))


def _pad_edges(s):
    return jnp.pad(s, ((1, 1), (1, 1)))


# ---------------------------------------------------------------------------
# in-grid (y_tile, x) tiling geometry
# ---------------------------------------------------------------------------


def _check_tiling(tiling: str) -> None:
    if tiling not in TILINGS:
        raise ValueError(f"tiling must be one of {TILINGS}, got {tiling!r}")


def _check_y_tile(y_tile: Optional[int]) -> None:
    if y_tile is not None and y_tile < 1:
        raise ValueError(f"y_tile must be >= 1, got {y_tile}")


def _grid_geometry(Y: int, y_tile: Optional[int], halo: int,
                   align: int = 1) -> Tuple[int, int, int]:
    """(TY, S, n_ty): owned rows per tile, static slab rows, tile count.

    Untiled (or a slab that would not fit the domain) degenerates to one
    full-domain tile (Y, Y, 1) — the 2D grid with n_ty=1 IS the untiled
    kernel, so there is a single code path. `align` > 1 (the compiled
    path: Mosaic's pipelined DMAs start on a sublane tile) rounds the
    slab's fetch halo up to a multiple of it, so every slab and output
    row offset is aligned; the tile and Y must then be multiples of it.
    The slab's fetch halo is ``(S - TY) // 2``.
    """
    if y_tile is None or y_tile >= Y:
        return Y, Y, 1
    halo = -(-halo // align) * align
    if y_tile + 2 * halo > Y:
        return Y, Y, 1
    if y_tile % align or Y % align:
        raise ValueError(
            f"compiled y-tiling needs y_tile and Y to be multiples of the "
            f"{align}-row sublane tile, got y_tile={y_tile}, Y={Y}")
    return y_tile, y_tile + 2 * halo, -(-Y // y_tile)


def _aligned(x, align: int):
    return pl.multiple_of(x, align) if align > 1 else x


def _slab_lo(t, Y: int, TY: int, S: int, align: int = 1):
    """Global row of slab row 0 for tile t, clipped flush into the domain."""
    return _aligned(jnp.clip(t * TY - (S - TY) // 2, 0, Y - S), align)


def _out_lo(t, Y: int, TY: int, align: int = 1):
    """Global row of the tile's (1, TY, Z) output block; the remainder tile
    slides down so its static-shaped block stays in bounds — its extra rows
    overlap the previous tile's and are rewritten with identical values
    (every row it emits has >= halo rows of slab margin)."""
    return _aligned(jnp.minimum(t * TY, Y - TY), align)


def _own_start(t, Y: int, TY: int, S: int, align: int = 1):
    """Slab-local row where the tile's owned output rows begin."""
    return _aligned(_out_lo(t, Y, TY) - _slab_lo(t, Y, TY, S), align)


def _slab_specs(X: int, Y: int, Z: int, TY: int, S: int, lag: int,
                align: int):
    """(field slab in, owned rows out, y-mask) block specs of the in-grid
    (y_tile, x) launch: grid step (t, i) reads x-slice min(i, X-1) of tile
    t's slab and writes x-slice clip(i - lag) of its owned rows. Every dim
    is element-indexed (Mosaic takes Element dims all-or-none)."""
    E = pl.Element
    in_spec = pl.BlockSpec(
        (E(1), E(S), E(Z)),
        lambda t, i: (jnp.minimum(i, X - 1), _slab_lo(t, Y, TY, S, align), 0))
    out_spec = pl.BlockSpec(
        (E(1), E(TY), E(Z)),
        lambda t, i: (jnp.clip(i - lag, 0, X - 1), _out_lo(t, Y, TY, align),
                      0))
    ym_spec = pl.BlockSpec((E(S), E(1)),
                           lambda t, i: (_slab_lo(t, Y, TY, S, align), 0))
    return in_spec, out_spec, ym_spec


def _out_scratch(TY: int, S: int, Z: int, dtype):
    """A tiled launch stages each output slab in VMEM and copies the owned
    rows out of it (a dynamic row window of a ref, which Mosaic lowers);
    an untiled one writes the slab straight to its block."""
    return [] if TY == S else [pltpu.VMEM((S, Z), dtype)]


def _write_owned(pairs, obuf, start, TY: int):
    """Write each ``(out_ref, slab_value)`` pair's owned rows
    ``[start, start + TY)`` to the (1, TY, Z) output block."""
    for ref, val in pairs:
        if obuf is None:
            ref[0] = val.astype(ref.dtype)
        else:
            obuf[...] = val.astype(obuf.dtype)
            ref[0] = obuf[pl.ds(start, TY), :]


def _emit_tile_outputs(refs, sources, cens, interior, start, fuse, dt,
                       obuf):
    """Shared v1/v2 epilogue: mask each slab source to the x-interior,
    optionally fold the Euler update in (`fuse`: advanced fields out), and
    write the tile's owned rows — the (1, TY, Z) output block — from slab
    row `start`."""
    pairs = []
    for ref, s, cen in zip(refs, sources, cens):
        if fuse:
            src = jnp.where(interior, _pad_edges(s), 0.0).astype(cen.dtype)
            val = cen + dt * src
        else:
            val = jnp.where(interior, _pad_edges(s), 0.0).astype(ref.dtype)
        pairs.append((ref, val))
    _write_owned(pairs, obuf, start, refs[0].shape[1])


# ---------------------------------------------------------------------------
# v1: blocked — three slice views per field, 3x HBM traffic
# ---------------------------------------------------------------------------


def _kernel_blocked(t1_ref, t2_ref,
                    um_ref, uc_ref, up_ref, vm_ref, vc_ref, vp_ref,
                    wm_ref, wc_ref, wp_ref,
                    su_ref, sv_ref, sw_ref, *obuf, X, Y, TY, S, align, fuse,
                    dt):
    t = pl.program_id(0)
    i = pl.program_id(1)
    args = [r[0] for r in (um_ref, uc_ref, up_ref, vm_ref, vc_ref, vp_ref,
                           wm_ref, wc_ref, wp_ref)]
    su, sv, sw = _source_slices(*args, *_coeffs(t1_ref, t2_ref))
    interior = (i >= 1) & (i <= X - 2)
    _emit_tile_outputs((su_ref, sv_ref, sw_ref), (su, sv, sw),
                       (args[1], args[4], args[7]), interior,
                       _own_start(t, Y, TY, S, align), fuse, dt,
                       obuf[0] if obuf else None)


def advect_blocked(u, v, w, p: AdvectParams, *,
                   interpret: Optional[bool] = None,
                   y_tile: int | None = None, tiling: str = "grid",
                   fuse_update: bool = False, dt: float = 1.0):
    _check_tiling(tiling)
    _check_y_tile(y_tile)
    interpret = resolve_interpret(interpret, u)
    X, Y, Z = u.shape
    if tiling == "host" and y_tile is not None and y_tile < Y:
        fn = lambda a, b, c: advect_blocked(a, b, c, p, interpret=interpret,
                                            fuse_update=fuse_update, dt=dt)
        return _y_tiled_host(fn, u, v, w, y_tile=y_tile, halo=1)
    align = 1 if interpret else _SUBLANE
    TY, S, n_ty = _grid_geometry(Y, y_tile, 1, align)
    E = pl.Element
    slice_spec = lambda off: pl.BlockSpec(
        (E(1), E(S), E(Z)),
        lambda t, i, off=off: (jnp.clip(i + off, 0, X - 1),
                               _slab_lo(t, Y, TY, S, align), 0))
    t1, t2 = _pack_coeffs(p)
    tz_spec = pl.BlockSpec(t1.shape, lambda t, i: (0, 0))
    out_spec = pl.BlockSpec((E(1), E(TY), E(Z)),
                            lambda t, i: (i, _out_lo(t, Y, TY, align), 0))
    out_shape = [jax.ShapeDtypeStruct((X, Y, Z), u.dtype)] * 3
    fn = pl.pallas_call(
        functools.partial(_kernel_blocked, X=X, Y=Y, TY=TY, S=S,
                          align=align, fuse=fuse_update, dt=dt),
        grid=(n_ty, X),
        in_specs=[tz_spec, tz_spec] + [slice_spec(o) for _ in range(3)
                                       for o in (-1, 0, 1)],
        out_specs=[out_spec] * 3,
        out_shape=out_shape,
        scratch_shapes=_out_scratch(TY, S, Z, u.dtype),
        interpret=interpret,
        name="advect_blocked",
    )
    return fn(t1, t2, u, u, u, v, v, v, w, w, w)


# ---------------------------------------------------------------------------
# v2: dataflow — persistent VMEM shift register, 1x HBM traffic
# ---------------------------------------------------------------------------


def _kernel_dataflow(t1_ref, t2_ref, u_ref, v_ref, w_ref,
                     su_ref, sv_ref, sw_ref,
                     ubuf, vbuf, wbuf, *obuf, X, Y, TY, S, align, fuse, dt):
    t = pl.program_id(0)
    i = pl.program_id(1)
    # 1) shift register: store the newly-arrived slice at ring position i%3.
    #    At a tile switch the ring holds the previous tile's slices; the
    #    interior mask below keeps them out of every unmasked output, so no
    #    explicit per-tile reset is needed.
    slot = jax.lax.rem(i, 3)
    load = i <= X - 1
    for buf, ref in ((ubuf, u_ref), (vbuf, v_ref), (wbuf, w_ref)):
        cur = buf[slot]
        buf[slot] = jnp.where(load, ref[0], cur)
    # 2) compute x = i-1 from ring slots (i-2, i-1, i)
    m, c, pslot = (jax.lax.rem(i + 1, 3), jax.lax.rem(i + 2, 3),
                   jax.lax.rem(i, 3))
    args = [ubuf[m], ubuf[c], ubuf[pslot],
            vbuf[m], vbuf[c], vbuf[pslot],
            wbuf[m], wbuf[c], wbuf[pslot]]
    su, sv, sw = _source_slices(*args, *_coeffs(t1_ref, t2_ref))
    interior = (i >= 2) & (i <= X - 1)
    _emit_tile_outputs((su_ref, sv_ref, sw_ref), (su, sv, sw),
                       (args[1], args[4], args[7]), interior,
                       _own_start(t, Y, TY, S, align), fuse, dt,
                       obuf[0] if obuf else None)


def _y_tiled_host(fn, u, v, w, *, y_tile: int, halo: int):
    """HOST-side tiling (the retained anti-pattern baseline, `tiling="host"`):
    run a slice kernel over halo-overlapped y-blocks and restitch.

    Each block sees `halo` extra rows per interior side; the kernel treats
    block edges as boundaries (zero source), which contaminates at most
    `halo` rows per side after `halo` update sweeps — exactly the rows we
    trim. Global-edge blocks get no extra rows, so the true boundary
    condition lands on the block edge. Every halo row is restaged from HBM
    per block, and the restitch is a host `jnp.concatenate` — the cost
    `hbm_bytes_model(..., grid_tiled=False)` charges and the in-grid path
    eliminates.
    """
    Y = u.shape[1]
    outs = ([], [], [])
    for y0 in range(0, Y, y_tile):
        y1 = min(y0 + y_tile, Y)
        lo, hi = max(y0 - halo, 0), min(y1 + halo, Y)
        tile = fn(u[:, lo:hi], v[:, lo:hi], w[:, lo:hi])
        for acc, t in zip(outs, tile):
            acc.append(t[:, y0 - lo:y0 - lo + (y1 - y0)])
    return tuple(jnp.concatenate(a, axis=1) for a in outs)


def advect_dataflow(u, v, w, p: AdvectParams, *,
                    interpret: Optional[bool] = None,
                    y_tile: int | None = None, tiling: str = "grid",
                    fuse_update: bool = False, dt: float = 1.0,
                    _fetch_halo: int = 1):
    _check_tiling(tiling)
    _check_y_tile(y_tile)
    interpret = resolve_interpret(interpret, u)
    X, Y, Z = u.shape
    if tiling == "host" and y_tile is not None and y_tile < Y:
        fn = lambda a, b, c: advect_dataflow(a, b, c, p, interpret=interpret,
                                             fuse_update=fuse_update, dt=dt)
        return _y_tiled_host(fn, u, v, w, y_tile=y_tile, halo=1)
    align = 1 if interpret else _SUBLANE
    TY, S, n_ty = _grid_geometry(Y, y_tile, _fetch_halo, align)
    in_spec, out_spec, _ = _slab_specs(X, Y, Z, TY, S, 1, align)
    t1, t2 = _pack_coeffs(p)
    tz_spec = pl.BlockSpec(t1.shape, lambda t, i: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((X, Y, Z), u.dtype)] * 3
    fn = pl.pallas_call(
        functools.partial(_kernel_dataflow, X=X, Y=Y, TY=TY, S=S,
                          align=align, fuse=fuse_update, dt=dt),
        grid=(n_ty, X + 1),
        in_specs=[tz_spec, tz_spec, in_spec, in_spec, in_spec],
        out_specs=[out_spec] * 3,
        out_shape=out_shape,
        scratch_shapes=([pltpu.VMEM((3, S, Z), u.dtype) for _ in range(3)]
                        + _out_scratch(TY, S, Z, u.dtype)),
        interpret=interpret,
        name="advect_dataflow",
    )
    return fn(t1, t2, u, v, w)


# ---------------------------------------------------------------------------
# v3: wide — v2 with lane-aligned layout (Z % 128 == 0)
# ---------------------------------------------------------------------------


def advect_wide(u, v, w, p: AdvectParams, *,
                interpret: Optional[bool] = None,
                y_tile: int | None = None, tiling: str = "grid",
                fuse_update: bool = False, dt: float = 1.0):
    _check_tiling(tiling)
    _check_y_tile(y_tile)
    Z = u.shape[2]
    if Z % 128:
        raise ValueError(
            f"advect_wide requires lane-aligned Z (multiple of 128), got {Z}; "
            "use advect_dataflow and accept the lane-efficiency penalty")
    if u.shape[1] % 8:
        raise ValueError(f"Y must be a multiple of 8 (sublane), got {u.shape[1]}")
    if y_tile is not None and y_tile < u.shape[1]:
        if tiling == "host":
            # halo'd host blocks are y_tile+2 (edge: +1) rows — never a
            # sublane multiple, so host tiling would silently break the
            # layout contract this variant exists to enforce
            raise ValueError(
                "advect_wide cannot Y-tile host-side (tile+halo rows break "
                "the (8,128) sublane contract); use tiling='grid' (default), "
                "advect_dataflow(y_tile=...) or advect_fused")
        if y_tile % 8:
            raise ValueError(
                f"wide y_tile must be a multiple of 8 (sublane), got {y_tile}")
    # grid tiling keeps the contract per-tile: the fetch halo is rounded up
    # to a full sublane (8 rows), so slab row counts and element offsets all
    # stay multiples of 8 while the stencil only needs 1 halo row.
    return advect_dataflow(u, v, w, p, interpret=interpret, y_tile=y_tile,
                           tiling="grid", fuse_update=fuse_update, dt=dt,
                           _fetch_halo=_WIDE_HALO)


# ---------------------------------------------------------------------------
# v4: fused — temporal blocking, T Euler steps per HBM pass
# ---------------------------------------------------------------------------


def _ring_lanes(Z: int, interpret: bool) -> int:
    """Lane width of the fused kernel's ring: Z rounded up to the 128-lane
    vreg when compiled, so a z-neighbour is one lane rotate of whole
    vregs; Z itself in interpret mode (a lane tile of 1)."""
    lane = 1 if interpret else _LANE
    return -(-Z // lane) * lane


def _pack_coeff_rows(p: AdvectParams, Zl: int):
    """(tcx, tcy, tzc1, tzc2) as four ``(1, Zl)`` rows: the scalars
    broadcast over every lane, the z-metrics zero-padded past Z. One row
    per coefficient, since Mosaic broadcasts a row over sublanes but not
    a ``(1, 1)`` value over both axes, nor a row sliced at a lane offset."""
    row = lambda a: jnp.broadcast_to(a, (Zl,))[None, :]
    pad = lambda a: jnp.pad(a, (0, Zl - a.shape[0]))[None, :]
    return row(p.tcx), row(p.tcy), pad(p.tzc1), pad(p.tzc2)


def _neighbours(f):
    """(f[y-1], f[y+1], f[z-1], f[z+1]) of a ``(rows, lanes)`` slab at
    offset 0: one sublane or lane rotation each. Wrapped values land in
    the slab's edge rows and edge lanes, which the caller masks."""
    S, Zl = f.shape
    return (pltpu.roll(f, 1, 0), pltpu.roll(f, S - 1, 0),
            pltpu.roll(f, 1, 1), pltpu.roll(f, Zl - 1, 1))


def _source_rolled(um, uc, up, vm, vc, vp, wm, wc, wp, tcx, tcy, t1, t2):
    """`_source_slices` on the whole slab at offset 0: the same expression
    tree, term by term and operand by operand, with each y/z neighbour a
    rotation of the centre slice (`_neighbours`, once per field) in place
    of an offset slice. Valid on rows 1..S-2 and lanes 1..Z-2; the
    coefficients are the ``(1, Zl)`` rows of `_pack_coeff_rows`."""
    nu, nv, nw = _neighbours(uc), _neighbours(vc), _neighbours(wc)

    def source(fm, fc, fp, nb):
        y_m, y_p, z_m, z_p = nb
        fx = tcx * (um * (fc + fm) - up * (fc + fp))
        fy = tcy * (nv[0] * (fc + y_m) - nv[1] * (fc + y_p))
        fz = (t1 * nw[2] * (fc + z_m) - t2 * nw[3] * (fc + z_p))
        return fx + fy + fz

    return (source(um, uc, up, nu), source(vm, vc, vp, nv),
            source(wm, wc, wp, nw))


def _kernel_fused(tcx_ref, tcy_ref, t1_ref, t2_ref, xm_ref, ym_ref,
                  u_ref, v_ref, w_ref, *refs, X, Y, Z, TY, S, T, dt, align):
    """T stacked 3-slice rings: level k holds the step-k fields.

    At grid step (t, i) the newly-arrived input slice x=i of tile t's slab
    lands in level 0's ring; level k (k=1..T) then computes its slice x=i-k
    from level k-1's ring. Level k-1's slice x=j is stored at grid step
    j+k-1, so for every level the (x-1, x, x+1) operands sit at ring slots
    ((i+1)%3, (i+2)%3, i%3) and every level writes slot i%3 — the same
    rotation as v2, T-deep.

    Each ring slice is ``(S, Zl)``: the slab's Z lanes at offset 0, padded
    to `_ring_lanes`. A level computes its sources on the whole slice
    (`_source_rolled`: y/z neighbours by rotation) and one mask keeps them
    to rows 1..S-2 and lanes 1..Z-2 — exactly the cells an offset slice
    re-padded with zero edges would cover; every value a rotation wraps,
    and every pad lane, lands outside it.

    Startup/tail slices (x<0 or x>X-1) are garbage but provably walled off:
    a level's x=0 / x=X-1 output is a masked copy of its centre operand, and
    the depth-1 stencil cannot carry values past an unchanging slice. The
    same wall swallows the previous tile's stale ring content at each tile
    switch, so the ring needs no explicit per-tile reset.

    `ym_ref` is the slab's ``(S, 1)`` row-interior mask (1.0 = the row's
    source may be applied); all-ones reproduces the plain boundary
    behaviour, while the distributed depth-T halo exchange passes its
    global-interior mask so wrapped ppermute rows stay frozen walls.
    `xm_ref` is the ``(X, 1)`` per-slice analogue for the x dimension:
    slice j's sources are applied only when xm[j] is nonzero, so a 2D
    (x, y) decomposition can freeze wrapped x-halo planes the same way
    (the slab-edge wall at j=0 / j=X-1 stays structural either way).

    The finite guard deliberately does NOT live in this kernel: probing
    the output slice with `isfinite` inside the loop body changes the
    body's codegen enough to perturb float contraction by one ulp at
    most shapes. Detection is a separate pass — `_kernel_finite_guard`
    below — so this kernel's outputs stay bitwise-identical whether or
    not the caller asked for guarding.
    """
    ou_ref, ov_ref, ow_ref, ubuf, vbuf, wbuf = refs[:6]
    obuf = refs[6] if len(refs) > 6 else None
    Zl = ubuf.shape[-1]
    t = pl.program_id(0)
    i = pl.program_id(1)
    slot = jax.lax.rem(i, 3)
    m, c = jax.lax.rem(i + 1, 3), jax.lax.rem(i + 2, 3)
    rows = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, Zl), 1)
    row_ok = (ym_ref[...] > 0.0) & (rows >= 1) & (rows <= S - 2)
    col_ok = (cols >= 1) & (cols <= Z - 2)
    coeffs = (tcx_ref[...], tcy_ref[...], t1_ref[...], t2_ref[...])
    for buf, ref in ((ubuf, u_ref), (vbuf, v_ref), (wbuf, w_ref)):
        buf[0, slot, :, 0:Z] = ref[0]
    outs = None
    for k in range(1, T + 1):
        j = i - k
        args = [ubuf[k - 1, m], ubuf[k - 1, c], ubuf[k - 1, slot],
                vbuf[k - 1, m], vbuf[k - 1, c], vbuf[k - 1, slot],
                wbuf[k - 1, m], wbuf[k - 1, c], wbuf[k - 1, slot]]
        su, sv, sw = _source_rolled(*args, *coeffs)
        interior = (j >= 1) & (j <= X - 2) & _plane_ok(xm_ref, j, X)
        ok = jnp.broadcast_to(interior & row_ok, (S, Zl)) & col_ok
        new = []
        for cen, s in ((args[1], su), (args[4], sv), (args[7], sw)):
            src = jnp.where(ok, s, 0.0).astype(cen.dtype)
            new.append(cen + dt * src)
        if k < T:
            ubuf[k, slot], vbuf[k, slot], wbuf[k, slot] = new
        else:
            outs = [o[:, 0:Z] for o in new]
    _write_owned(zip((ou_ref, ov_ref, ow_ref), outs), obuf,
                 _own_start(t, Y, TY, S, align), TY)


def _kernel_finite_guard(u_ref, v_ref, w_ref, gf_ref):
    """Per-x-slice finite-guard: flag = 1.0 iff the (Y, Z) slice of all
    three fields is entirely finite. One grid step per x-slice keeps the
    VMEM working set at 3*Y*Z words regardless of X; the flags live in
    SMEM (one scalar word per slice — a rank-1 VMEM block of one word is
    not a layout Mosaic takes)."""
    ok = jnp.float32(1.0)
    for ref in (u_ref, v_ref, w_ref):
        ok = ok * jnp.all(jnp.isfinite(ref[0])).astype(jnp.float32)
    gf_ref[pl.program_id(0)] = ok


def finite_guard(u, v, w, *, interpret: Optional[bool] = None):
    """Scan the three fields for non-finite cells in ONE extra read pass.

    Returns f32 flags of shape ``(X,)``: ``flags[i] == 1.0`` iff x-slice
    i of `u`, `v` and `w` is entirely finite, so ``flags.min() > 0`` iff
    the whole state is. This is the serving tier's poisoned-slot
    detector, kept OUTSIDE the fused advection kernel on purpose: an
    in-loop `isfinite` probe perturbs the fused kernel's float
    contraction by one ulp, while a separate pass over the already-
    written outputs leaves them bitwise intact. The price is honest and
    exactly modelled: the pass re-reads all three fields and writes X
    flag words — `roofline.guard_bytes_model` bytes, which
    `stencil.distributed.count_guard_bytes` recounts from the jaxpr and
    BENCH_faults.json gates equal EXACTLY.
    """
    X, Y, Z = u.shape
    return pl.pallas_call(
        _kernel_finite_guard,
        grid=(X,),
        in_specs=[pl.BlockSpec((1, Y, Z), lambda i: (i, 0, 0))] * 3,
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((X,), jnp.float32),
        interpret=resolve_interpret(interpret, u),
        name="finite_guard",
    )(u, v, w)


def advect_fused(u, v, w, p: AdvectParams, *, T: int = 4, dt: float = 1.0,
                 interpret: Optional[bool] = None,
                 y_tile: int | None = None,
                 tiling: str = "grid", y_interior_mask=None,
                 x_interior_mask=None, guard: bool = False):
    """v4: advance the fields T explicit-Euler steps in ONE HBM pass.

    Returns the advanced `(u, v, w)` (not sources — the step is fused into
    the kernel). With `y_tile`, each in-grid tile's slab carries a T-deep
    halo so the register is VMEM-bounded at ``fused_register_bytes``
    irrespective of Y. `y_interior_mask` (shape (Y,), nonzero = source may
    be applied) lets callers freeze extra rows beyond the domain edges —
    the distributed depth-T halo exchange uses it to wall off wrapped
    ppermute rows while composing with in-grid tiles. `x_interior_mask`
    (shape (X,)) is the x-plane analogue, used by the 2D (x, y) mesh
    decomposition to freeze wrapped x-halo planes.

    `guard=True` returns ``(u, v, w, flags)`` where `flags` is the
    `finite_guard` pass over the three ADVANCED fields — f32 shape
    ``(X,)``, 1.0 iff that x-slice is finite across all three, so
    ``flags.min() > 0`` iff the whole advanced state is finite. The
    guard is a separate pallas pass over the outputs (NOT fused into
    the advection loop — an in-loop probe costs one ulp of drift), so
    the field outputs are bitwise-identical to a `guard=False` call.
    Its extra HBM bytes (one read pass + X flag words) are priced by
    `roofline.guard_bytes_model` and counted by
    `stencil.distributed.count_guard_bytes` — gated equal EXACTLY.
    """
    if T < 1:
        raise ValueError(f"T must be >= 1, got {T}")
    _check_tiling(tiling)
    _check_y_tile(y_tile)
    interpret = resolve_interpret(interpret, u)
    X, Y, Z = u.shape
    if tiling == "host" and y_tile is not None and y_tile < Y:
        if y_interior_mask is not None or x_interior_mask is not None:
            raise ValueError("interior masks require the grid-tiled path "
                             "(tiling='grid')")
        fn = lambda a, b, c: advect_fused(a, b, c, p, T=T, dt=dt,
                                          interpret=interpret)
        ou, ov, ow = _y_tiled_host(fn, u, v, w, y_tile=y_tile, halo=T)
        if guard:
            return ou, ov, ow, finite_guard(ou, ov, ow, interpret=interpret)
        return ou, ov, ow
    align = 1 if interpret else _SUBLANE
    TY, S, n_ty = _grid_geometry(Y, y_tile, T, align)
    xm, ym = _masks(x_interior_mask, y_interior_mask, X, Y)
    in_spec, out_spec, ym_spec = _slab_specs(X, Y, Z, TY, S, T, align)
    xm_spec = pl.BlockSpec((X, 1), lambda t, i: (0, 0))
    Zl = _ring_lanes(Z, interpret)
    coeffs = _pack_coeff_rows(p, Zl)
    c_spec = pl.BlockSpec((1, Zl), lambda t, i: (0, 0))
    fn = pl.pallas_call(
        functools.partial(_kernel_fused, X=X, Y=Y, Z=Z, TY=TY, S=S, T=T,
                          dt=dt, align=align),
        grid=(n_ty, X + T),
        in_specs=[c_spec] * 4 + [xm_spec, ym_spec,
                                 in_spec, in_spec, in_spec],
        out_specs=[out_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((X, Y, Z), u.dtype)] * 3,
        scratch_shapes=([pltpu.VMEM((T, 3, S, Zl), u.dtype)
                         for _ in range(3)]
                        + _out_scratch(TY, S, Z, u.dtype)),
        interpret=interpret,
        name="advect_fused",
    )
    ou, ov, ow = fn(*coeffs, xm, ym, u, v, w)
    if guard:
        return ou, ov, ow, finite_guard(ou, ov, ow, interpret=interpret)
    return ou, ov, ow


def _batch_axis(leaf, base_ndim: int):
    """vmap in_axis for an optionally slot-batched operand: a leading batch
    dimension on top of the unbatched rank maps (axis 0), anything else is
    shared across slots (axis None)."""
    nd = getattr(leaf, "ndim", 0)
    if nd == base_ndim:
        return None
    if nd == base_ndim + 1:
        return 0
    raise ValueError(
        f"operand rank {nd} is neither the unbatched rank {base_ndim} nor "
        f"batched rank {base_ndim + 1}")


def advect_fused_batched(u, v, w, p, *, T: int = 4, dt: float = 1.0,
                         interpret: Optional[bool] = None,
                         y_tile: int | None = None,
                         tiling: str = "grid", y_interior_mask=None,
                         x_interior_mask=None, guard: bool = False):
    """Batched mega-launch: advance B independent (X, Y, Z) domains with
    ONE fused-kernel dispatch — the serving tier's packing move.

    `u`, `v`, `w` are slot-stacked ``(B, X, Y, Z)`` fields. The batch rides
    an outer grid dimension via `jax.vmap` of the fused pallas_call (the
    vmap-with-shared-ring layout): Pallas's batching rule prepends the
    slot index to the `(n_ty, X + T)` grid, so slots stream through the
    SAME VMEM shift-register rings back to back — slot b+1's startup
    masking walls off slot b's stale ring content exactly as a y-tile
    switch does, and per-slot outputs are bitwise-identical to B
    sequential `advect_fused` calls (the BENCH_serving gate).

    `p` is an `AdvectParams` whose leaves are either shared (unbatched) or
    slot-stacked with a leading B — per-tenant advection coefficients.
    `x_interior_mask` / `y_interior_mask` may likewise be shared ``(X,)`` /
    ``(Y,)`` or per-slot ``(B, X)`` / ``(B, Y)``: a request SMALLER than
    the padded slot shape freezes everything outside its own extent (and
    its own boundary ring) with zeros in the mask, so the padded run
    reproduces the unpadded domain bitwise — the serving engine's
    pack-small-domains contract.

    HBM traffic is exactly B times the per-domain model
    (``hbm_bytes_model``): the batched pallas_call's field operands and
    results are the only rank->=3 arrays it touches, which is what
    `stencil.distributed.count_pallas_hbm_bytes` counts and
    BENCH_serving.json gates EXACTLY (lane-aligned Z).

    `guard=True` additionally returns slot-stacked finite-guard flags
    ``(B, X)`` (see `finite_guard`): ``flags[b].min() > 0`` iff slot b's
    advanced fields are entirely finite — the serving engine's per-slot
    quarantine signal, one extra guard pass over the mega-launch's
    outputs (viewed as B*X slices) that leaves the field outputs
    bitwise-identical to an unguarded call. The flag output is rank 1,
    so the main kernel's `count_pallas_hbm_bytes` is unchanged;
    `count_guard_bytes` isolates the guard pass's traffic,
    == `guard_bytes_model(batch=B)`.
    """
    for name, f in (("u", u), ("v", v), ("w", w)):
        if f.ndim != 4:
            raise ValueError(f"{name} must be slot-stacked (B, X, Y, Z), "
                             f"got rank {f.ndim}")
    if not (u.shape == v.shape == w.shape):
        raise ValueError(f"field shapes differ: {u.shape} {v.shape} "
                         f"{w.shape}")
    B, X, Y, Z = u.shape
    interpret = resolve_interpret(interpret, u)
    p_axes = AdvectParams(_batch_axis(p.tcx, 0), _batch_axis(p.tcy, 0),
                          _batch_axis(p.tzc1, 1), _batch_axis(p.tzc2, 1))
    xm = (jnp.ones((X,), jnp.float32) if x_interior_mask is None
          else jnp.asarray(x_interior_mask, jnp.float32))
    ym = (jnp.ones((Y,), jnp.float32) if y_interior_mask is None
          else jnp.asarray(y_interior_mask, jnp.float32))
    xm_ax, ym_ax = _batch_axis(xm, 1), _batch_axis(ym, 1)

    def one(uu, vv, ww, pp, xmm, ymm):
        return advect_fused(uu, vv, ww, pp, T=T, dt=dt, interpret=interpret,
                            y_tile=y_tile, tiling=tiling,
                            y_interior_mask=ymm, x_interior_mask=xmm)

    ou, ov, ow = jax.vmap(one, in_axes=(0, 0, 0, p_axes, xm_ax, ym_ax))(
        u, v, w, p, xm, ym)
    if not guard:
        return ou, ov, ow
    flat = [f.reshape(B * X, Y, Z) for f in (ou, ov, ow)]
    flags = finite_guard(*flat, interpret=interpret).reshape(B, X)
    return ou, ov, ow, flags


# ---------------------------------------------------------------------------
# spec-driven generalised fused kernel (the stencil-spec frontend's engine)
# ---------------------------------------------------------------------------


def _pad_r(s, r: int):
    return jnp.pad(s, ((r, r), (r, r)))


def _kernel_stencil_fused(*refs, X, Y, TY, S, T, dt, n_fields, n_params,
                          radius, stages, source, align):
    """Generalised temporal-blocking ring: `stages*T` stacked levels of
    `2*radius+1` slots per field, driven by a StencilSpec's source callback.

    Geometry (reduces EXACTLY to `_kernel_fused` at radius=1, stages=1):
    level 0 stores the arriving input slice x=i at slot i % W (W=2r+1);
    level k (k=1..L, L=stages*T) computes its slice j = i - k*r from level
    k-1's ring — level k-1's slice j+dx (|dx| <= r) was written at grid
    step j+dx+(k-1)*r = i-r+dx, i.e. slot (i-r+dx) % W, still resident in
    the W-deep rotation. Each level writes slot i % W; the output level L
    emits slice j = i - D (D = r*L, the spec halo depth).

    Integrators: euler spends one level per substep (new = cen + dt*src).
    Midpoint rk2 spends two — odd levels hold the half-step state
    g = cen + (dt/2)*src, even levels complete f_new = base + dt*src(g)
    where `base` is the PREVIOUS FULL level's (k-2) slice j, written at
    grid step i-2r and therefore the oldest still-resident slot
    (i-2r) % W. Masked slices copy through unchanged at every level
    (g=cen, f_new=base), so the startup/tail/tile-switch wall argument of
    `_kernel_fused` carries over for any radius and either integrator.
    """
    P, F = n_params, n_fields
    p_refs = refs[:P]
    xm_ref, ym_ref = refs[P], refs[P + 1]
    f_refs = refs[P + 2:P + 2 + F]
    out_refs = refs[P + 2 + F:P + 2 + 2 * F]
    bufs = refs[P + 2 + 2 * F:P + 2 + 3 * F]
    obuf = refs[P + 2 + 3 * F] if len(refs) > P + 2 + 3 * F else None
    r = radius
    W = 2 * r + 1
    L = stages * T
    D = r * L
    t = pl.program_id(0)
    i = pl.program_id(1)
    pv = tuple(pr[...] for pr in p_refs)
    row_ok = ym_ref[...] > 0.0
    slot = jax.lax.rem(i, W)
    for buf, ref in zip(bufs, f_refs):
        buf[0, slot] = ref[0]
    outs = None
    for k in range(1, L + 1):
        lvl = k - 1
        j = i - k * r

        def sh(fi, dx, dj, dk, _lvl=lvl):
            # (i + (W - r) + dx) % W == (i - r + dx) % W, kept non-negative
            sl = jax.lax.rem(i + (W - r) + dx, W)
            v = bufs[fi][_lvl, sl]
            return v[r + dj:v.shape[0] - r + dj, r + dk:v.shape[1] - r + dk]

        srcs = source(sh, pv)
        interior = (j >= r) & (j <= X - 1 - r) & _plane_ok(xm_ref, j, X)
        cslot = jax.lax.rem(i + (W - r), W)
        half_level = stages == 2 and k % 2 == 1
        step_dt = 0.5 * dt if half_level else dt
        new = []
        for fi, s in enumerate(srcs):
            if stages == 2 and k % 2 == 0:
                base = bufs[fi][k - 2, jax.lax.rem(i + (W - 2 * r), W)]
            else:
                base = bufs[fi][lvl, cslot]
            src = jnp.where(interior & row_ok, _pad_r(s, r),
                            0.0).astype(base.dtype)
            new.append(base + step_dt * src)
        if k < L:
            for fi, val in enumerate(new):
                bufs[fi][k, slot] = val
        else:
            outs = new
    _write_owned(zip(out_refs, outs), obuf, _own_start(t, Y, TY, S, align),
                 TY)


def stencil_fused(fields, params, spec, *, T: int = 4, dt: float = 1.0,
                  interpret: Optional[bool] = None,
                  y_tile: int | None = None,
                  y_interior_mask=None, x_interior_mask=None):
    """Spec-driven v4: advance a StencilSpec's fields T integrator steps in
    ONE HBM pass — the generalisation of `advect_fused` to any operator.

    `fields` is a tuple of `spec.n_fields` (X, Y, Z) arrays; `params` is
    whatever `spec.pack_params` consumes. Ring depth, startup masks, slab
    halo and the output lag are ALL derived from `spec.halo(T) =
    radius * stages * T` instead of the hand kernel's hard-coded halo=1
    per substep, so deeper stencils and multi-stage integrators ride the
    identical grid-tiled execution contract (`y_tile`, interior masks —
    same semantics as `advect_fused`). For the Piacsek-Williams spec this
    function is gated BITWISE-equal to `advect_fused`: the ring rotation,
    block specs and update arithmetic reduce exactly to `_kernel_fused`
    at radius=1, stages=1. VMEM cost is `fused_register_bytes(...,
    n_fields, n_slots=2r+1, n_levels=stages*T)`.
    """
    if T < 1:
        raise ValueError(f"T must be >= 1, got {T}")
    _check_y_tile(y_tile)
    fields = tuple(fields)
    if len(fields) != spec.n_fields:
        raise ValueError(
            f"spec {spec.name!r} has {spec.n_fields} fields "
            f"({spec.fields}), got {len(fields)} arrays")
    shape = fields[0].shape
    for name, f in zip(spec.fields, fields):
        if f.shape != shape:
            raise ValueError(f"field {name!r} shape {f.shape} != {shape}")
    X, Y, Z = shape
    r = spec.radius
    D = spec.halo(T)
    L = spec.stages * T
    interpret = resolve_interpret(interpret, fields[0])
    align = 1 if interpret else _SUBLANE
    TY, S, n_ty = _grid_geometry(Y, y_tile, D, align)
    xm, ym = _masks(x_interior_mask, y_interior_mask, X, Y)
    pv = tuple(spec.pack_params(params))
    for p in pv:
        if p.ndim != 1:
            raise ValueError(
                f"spec {spec.name!r}: pack_params must return 1-D vectors, "
                f"got shape {p.shape}")
    p_specs = [pl.BlockSpec(p.shape, lambda t, i: (0,)) for p in pv]
    in_spec, out_spec, ym_spec = _slab_specs(X, Y, Z, TY, S, D, align)
    xm_spec = pl.BlockSpec((X, 1), lambda t, i: (0, 0))
    fn = pl.pallas_call(
        functools.partial(_kernel_stencil_fused, X=X, Y=Y, TY=TY, S=S, T=T,
                          dt=dt, n_fields=spec.n_fields, n_params=len(pv),
                          radius=r, stages=spec.stages, source=spec.source,
                          align=align),
        grid=(n_ty, X + D),
        in_specs=p_specs + [xm_spec, ym_spec] + [in_spec] * spec.n_fields,
        out_specs=[out_spec] * spec.n_fields,
        out_shape=[jax.ShapeDtypeStruct((X, Y, Z), fields[0].dtype)
                   ] * spec.n_fields,
        scratch_shapes=([pltpu.VMEM((L, 2 * r + 1, S, Z), fields[0].dtype)
                         for _ in range(spec.n_fields)]
                        + _out_scratch(TY, S, Z, fields[0].dtype)),
        interpret=interpret,
        name="stencil_fused",
    )
    out = fn(*pv, xm, ym, *fields)
    return tuple(out) if isinstance(out, (tuple, list)) else (out,)


def stencil_fused_batched(fields, params, spec, *, T: int = 4,
                          dt: float = 1.0, interpret: Optional[bool] = None,
                          y_tile: int | None = None,
                          y_interior_mask=None, x_interior_mask=None):
    """Batched mega-launch of the spec kernel: B independent domains of any
    StencilSpec in ONE dispatch — the serving tier's packing move
    generalised beyond advection (cf. `advect_fused_batched`; slots stream
    back-to-back through the same VMEM rings, startup masking walls off
    the previous slot's stale ring content). `fields` are slot-stacked
    ``(B, X, Y, Z)``; `params` is shared across slots; interior masks may
    be shared ``(X,)``/``(Y,)`` or per-slot ``(B, X)``/``(B, Y)``."""
    fields = tuple(fields)
    for name, f in zip(spec.fields, fields):
        if f.ndim != 4:
            raise ValueError(f"field {name!r} must be slot-stacked "
                             f"(B, X, Y, Z), got rank {f.ndim}")
    shape = fields[0].shape
    for name, f in zip(spec.fields, fields):
        if f.shape != shape:
            raise ValueError(f"field {name!r} shape {f.shape} != {shape}")
    B, X, Y, Z = shape
    xm = (jnp.ones((X,), jnp.float32) if x_interior_mask is None
          else jnp.asarray(x_interior_mask, jnp.float32))
    ym = (jnp.ones((Y,), jnp.float32) if y_interior_mask is None
          else jnp.asarray(y_interior_mask, jnp.float32))
    xm_ax, ym_ax = _batch_axis(xm, 1), _batch_axis(ym, 1)

    def one(fs, xmm, ymm):
        return stencil_fused(fs, params, spec, T=T, dt=dt,
                             interpret=interpret, y_tile=y_tile,
                             y_interior_mask=ymm, x_interior_mask=xmm)

    return jax.vmap(one, in_axes=((0,) * len(fields), xm_ax, ym_ax))(
        fields, xm, ym)


# ---------------------------------------------------------------------------
# in-kernel halo-band exchange: async remote DMA (TPU, compiled mode)
# ---------------------------------------------------------------------------


def _band_schedule(L: int, depth: int):
    """Per-hop band messages of one exchange side, shared by every engine.

    Returns ``[(k, cnt, hi_off, lo_off), ...]``: hop k moves `cnt` =
    min(L, depth-(k-1)L) planes/rows to/from the k-away ring neighbour, and
    the received bands land at extended-slab offsets `hi_off` (band from
    the predecessor side, global coordinates ascending) and `lo_off` (from
    the successor side). Offsets partition the hi halo [0, depth) and the
    lo halo [depth+L, depth+L+depth) of the extended slab exactly — the
    recv-slab addresses the remote-DMA kernel writes and the emulation's
    assembly both use, and the operand sizes
    `stencil.distributed.remote_dma_schedule_wire_bytes` sums. Lives in
    the kernels layer because `_kernel_band_dma` issues exactly one
    `make_async_remote_copy` per (field, side, hop) entry of this list;
    `stencil.distributed` re-exports it for the ppermute emulation.
    """
    hops = -(-depth // L)
    sched = []
    for k in range(1, hops + 1):
        cnt = min(L, depth - (k - 1) * L)
        sched.append((k, cnt, depth - (k - 1) * L - cnt, depth + k * L))
    return sched


def band_checksum(band):
    """Integrity word over ONE `_band_schedule` message: the uint32
    wraparound sum of the band's raw 32-bit words, shaped ``(1,)`` so it
    can ride the same transport as the band itself.

    Exact and order-independent by construction — modular integer
    addition is associative/commutative, so sender and receiver compute
    the IDENTICAL word from identical bytes regardless of reduction
    order, and the verified exchange can gate BITWISE no-op against the
    unchecked one (a float reduction could not: its rounding depends on
    shape/order). Lives in the kernels layer beside `_band_schedule`
    because the word is part of the band-message wire format every
    engine shares; `stencil.distributed` verifies it per received band
    and `roofline.integrity_bytes_model` prices one word per message.

    Requires a 4-byte element type (the stencil fields are f32); other
    widths would need a different word packing and are rejected loudly.
    """
    if band.dtype.itemsize != 4:
        raise TypeError(
            f"band_checksum packs 32-bit words; got dtype {band.dtype} "
            f"(itemsize {band.dtype.itemsize})")
    bits = jax.lax.bitcast_convert_type(band, jnp.uint32)
    return jnp.sum(bits, dtype=jnp.uint32).reshape((1,))


def _band_messages(f, dim: int, sched):
    """The boundary bands of `f` that one exchange sends, in
    `_kernel_band_dma`'s message order: per `_band_schedule` hop, the
    tail (to the successor's hi halo), then the head (to the
    predecessor's lo halo). Each is viewed lane-dense, ``(rows, 128)``,
    wherever its size allows: Mosaic slices an HBM ref only in whole
    lane tiles, which a Z=64 minor dim is not."""
    L = f.shape[dim]
    out = []
    for _, cnt, _, _ in sched:
        for lo in (L - cnt, 0):
            idx = [slice(None)] * f.ndim
            idx[dim] = slice(lo, lo + cnt)
            band = f[tuple(idx)]
            lanes = 128 if band.size % 128 == 0 else band.shape[-1]
            out.append((band.reshape(-1, lanes), band.shape))
    return out


def _kernel_band_dma(step_ref, *refs, axis, mesh_axes, n, sched):
    """One depth-T band exchange along mesh axis `axis`, issued as async
    remote DMA from INSIDE the kernel — the paper's §IV move of the
    transfer schedule out of the tooling's hands and into the kernel's.

    `refs` holds the send bands (3 fields x hops x 2 sides, in
    `_band_messages` order, in HBM), then one double-buffered recv slab
    per band (same order, in HBM), then the send and recv DMA
    semaphores. Each band is `make_async_remote_copy`'d HBM to HBM into
    the recv slab of the same message index on its k-away ring
    neighbour: the tail lands in the successor's hi message, the head in
    the predecessor's lo message. All sends start before any wait, so
    the DMAs fly concurrently. The entry barrier is the capacity
    handshake: every hop partner has entered this block's exchange —
    and so holds the recv slabs being written — before any band lands.

    The recv slot is `step_ref[0] % 2` — a TRACED value read from SMEM,
    so a pipelined multi-block driver (`stencil.distributed.
    make_distributed_run`) threads the block counter through ONE traced
    program and alternates parity without retracing. Scope honesty: this
    call still waits all its DMAs before returning, so realising a
    cross-block landing needs the driver's ROADMAPped boundary-first
    continuation — what is delivered here is the dynamic parity and the
    multi-hop schedule.

    The traffic is ring-symmetric (for every hop k, everyone sends its
    tail forward-k and its head backward-k), so each device's descriptor
    also waits its OWN incoming bands: `rdma.wait()` blocks on the local
    send semaphore and on the recv semaphore its hop partner's copy
    signals.
    """
    hops = len(sched)
    nb = 3 * 2 * hops
    sends, recvs = refs[:nb], refs[nb:2 * nb]
    send_sem, recv_sem = refs[2 * nb:]
    slot = jax.lax.rem(step_ref[0], 2)
    coords = [jax.lax.axis_index(a) for a in mesh_axes]
    barrier = pltpu.get_barrier_semaphore()
    for k, _, _, _ in sched:
        for delta in (k, -k):
            dev = dma_neighbor_coords(mesh_axes, coords, axis, delta, n)
            pltpu.semaphore_signal(barrier, 1, device_id=dev,
                                   device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 2 * hops)
    rdmas = []
    for fi in range(3):
        for hk, (k, _, _, _) in enumerate(sched):
            for si, delta in enumerate((k, -k)):
                b = (fi * hops + hk) * 2 + si
                rdma = pltpu.make_async_remote_copy(
                    src_ref=sends[b], dst_ref=recvs[b].at[slot],
                    send_sem=send_sem.at[b], recv_sem=recv_sem.at[b],
                    device_id=dma_neighbor_coords(mesh_axes, coords, axis,
                                                  delta, n),
                    device_id_type=pltpu.DeviceIdType.MESH)
                rdma.start()
                rdmas.append(rdma)
    for rdma in rdmas:
        rdma.wait()


def halo_band_exchange_dma(u, v, w, *, axis: str, mesh_axes, n: int,
                           depth: int, dim: int, block_index=0,
                           collective_id: int = 0):
    """Exchange depth-`depth` boundary bands of three fields along mesh
    axis `axis` via in-kernel async remote DMA (TPU compiled mode ONLY —
    Mosaic semaphores have no interpret/CPU path; `stencil.distributed`
    runs its schedule-faithful ppermute emulation there instead, and the
    two are gated bitwise-equal).

    Returns ``((u_hi, u_lo), (v_hi, v_lo), (w_hi, w_lo))`` where `hi` is
    the band arriving from the ring predecessors (global coordinates just
    below the shard) and `lo` from the successors — the same contract as
    the collective `_exchange_halos`, so the caller-side slab assembly and
    the x-then-y corner ordering are engine-independent. Multi-hop: when
    `depth` exceeds the local extent, `_band_schedule` splits each side
    into ceil(depth/L) band messages and the kernel issues one
    `make_async_remote_copy` per (field, side, hop); the hops' bands are
    joined in global order here, so arbitrarily deep halos move without
    falling back to the collective engine (the caller still bounds
    T <= global extent - 2 — past that no interior cell exists whose
    cone the ring can serve).

    `block_index` is the substep-block number k — a Python int or a
    TRACED scalar: the receive slabs are double-buffered on k % 2 and the
    parity is selected dynamically (SMEM-read slot in the kernel,
    `dynamic_index_in_dim` on the outputs), so the pipelined multi-block
    driver alternates slots inside one traced program instead of
    rebuilding per block. `collective_id` must differ between the x and y
    phases so their barrier semaphores stay distinct. The kernel is named
    by its phase (`halo_band_exchange_dma_x` for dim 0, `_y` for dim 1),
    so a device trace tells the two apart.
    """
    if dim not in (0, 1):
        raise ValueError(f"dim must be 0 (x-planes) or 1 (y-rows), got {dim}")
    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    sched = _band_schedule(u.shape[dim], depth)
    msgs = [m for f in (u, v, w) for m in _band_messages(f, dim, sched)]
    sends = [band for band, _ in msgs]
    nb = len(sends)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    fn = pl.pallas_call(
        functools.partial(_kernel_band_dma, axis=axis,
                          mesh_axes=tuple(mesh_axes), n=n,
                          sched=tuple(sched)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [any_spec] * nb,
        out_specs=[any_spec] * nb,
        out_shape=[jax.ShapeDtypeStruct((2,) + b.shape, b.dtype)
                   for b in sends],
        scratch_shapes=[pltpu.SemaphoreType.DMA((nb,)),    # remote send
                        pltpu.SemaphoreType.DMA((nb,))],   # remote recv
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        name=f"halo_band_exchange_dma_{'xy'[dim]}",
    )
    block = jnp.asarray(block_index, jnp.int32)
    outs = fn(block.reshape((1,)), *sends)
    # dynamic parity: traced block counters (the pipelined driver's
    # fori_loop induction variable) select the recv slot without retracing
    slot = jax.lax.rem(block, 2)
    got = [jax.lax.dynamic_index_in_dim(o, slot, 0, keepdims=False)
           .reshape(shape) for o, (_, shape) in zip(outs, msgs)]
    hops = len(sched)
    bands = []
    for fi in range(3):
        msg = got[fi * 2 * hops:(fi + 1) * 2 * hops]
        his, los = msg[0::2], msg[1::2]
        # hi: farthest predecessor first so global coordinates ascend
        bands.append((jnp.concatenate(his[::-1], axis=dim),
                      jnp.concatenate(los, axis=dim)))
    return tuple(bands)


# ---------------------------------------------------------------------------
# analytic VMEM / HBM traffic models
# ---------------------------------------------------------------------------


def fused_register_bytes(T: int, y_rows: int, Z: int, itemsize: int = 4,
                         y_tile: int | None = None,
                         halo: int | None = None, *, n_fields: int = 3,
                         n_slots: int = 3,
                         n_levels: int | None = None) -> int:
    """VMEM footprint of the fused shift register: by default 3 fields x
    3T slices (the hand-written v4 ring).

    With Y-tiling each resident slice has ``y_tile + 2*halo`` rows (tile +
    slab halo; halo defaults to T, the fused contamination depth) no matter
    how large the grid's Y is — the Fig. 8 scaling contract, identical for
    the in-grid and host-tiled paths. Pass ``halo=8`` (the sublane-rounded
    fetch halo) to size the `wide` grid-tiled ring with T=1.

    The spec-driven generalised ring (`stencil_fused`) is sized by the
    same formula with `n_fields=spec.n_fields`,
    `n_slots=2*spec.radius + 1`, `n_levels=spec.stages*T` and
    `halo=spec.halo(T)`.
    """
    h = T if halo is None else halo
    levels = T if n_levels is None else n_levels
    rows = y_rows if y_tile is None else min(y_tile + 2 * h, y_rows)
    return n_fields * (n_slots * levels) * rows * Z * itemsize


def _n_y_tiles(Y: int, y_tile: int | None) -> int:
    if y_tile is None or y_tile >= Y:
        return 1
    return -(-Y // y_tile)


def _host_overlap_rows(Y: int, y_tile: int | None, halo: int) -> int:
    """Rows the HOST loop restages per x-slice: 2*halo per interior tile
    boundary. The host path tiles ANY y_tile >= 1 (edge blocks just clamp
    their halo), so this uses the plain ceil-div tile count — deliberately
    unlike `_grid_geometry`, whose untiled fallback models the in-grid
    kernel refusing slabs that cannot fit (`y_tile + 2*halo > Y`).
    `core.roofline.stencil_tiling_bytes_factor` is this same formula as a
    multiplier; tests pin the two together.
    """
    return 2 * halo * (_n_y_tiles(Y, y_tile) - 1)


def _check_wide_model_tile(Y: int, y_tile: int | None,
                           grid_tiled: bool) -> None:
    """Mirror advect_wide's tiling contract in the analytic models: no host
    path exists at all, and the in-grid path needs a sublane-multiple
    tile."""
    if y_tile is None or y_tile >= Y:
        return
    if not grid_tiled:
        raise ValueError("wide cannot Y-tile host-side; model grid_tiled=True"
                         " or use dataflow/fused")
    if y_tile % 8:
        raise ValueError(
            f"wide y_tile must be a multiple of 8 (sublane), got {y_tile}; "
            "no such execution path exists to model")


def hbm_bytes_model(X: int, Y: int, Z: int, itemsize: int, variant: str,
                    *, T: int = 1, y_tile: int | None = None,
                    grid_tiled: bool = True, fuse_update: bool = True,
                    n_fields: int = 3,
                    halo_depth: int | None = None) -> int:
    """Analytic HBM traffic per advection call (for the Fig. 3/9 tables).

    `T` is the number of explicit-Euler steps the call advances: the
    pre-fusion variants pay a full read+write pass per step, while `fused`
    streams each field in and out ONCE for all T steps — the ~T×
    amortisation of Fig. 9.

    `grid_tiled=True` (the kernels' default path) models the in-grid
    `(y_tile, x)` tiling at compulsory traffic: outputs are written in
    place (the host loop's write-side halo duplication is gone outright)
    and the read-side stencil halo is charged to VMEM slab residency
    rather than HBM, so the HBM term carries ZERO halo overlap — every
    domain byte moves exactly once per pass, independent of `y_tile`.
    The relocated halo bytes are reported by ``vmem_halo_bytes_model``.
    (This is the analytic contract for the Fig. 3/8/9 tables; the
    interpret-mode reference implementation still materialises each
    slab window per grid step.) `grid_tiled=False` models the retained
    host-side loop (`tiling="host"`), which restages `2*halo` rows per
    interior tile boundary from HBM on BOTH the read and write side.

    `fuse_update=False` additionally charges the separate explicit-Euler
    update pass the non-fused variants pay when the update is NOT fused
    into the kernel (read field + read source + write field per step —
    dense contiguous arrays, so no lane penalty); `fuse_update=True`
    matches kernels run with their `fuse_update=True` flag (and `fused`,
    where the update is inherently in-kernel).

    `n_fields` and `halo_depth` generalise the model to the stencil-spec
    frontend: the spec-driven fused kernel streams `spec.n_fields` fields
    per pass with a slab halo of `spec.halo(T) = radius*stages*T` (the
    default `halo_depth=None` keeps the hand-written ladder's depths —
    T for `fused`, 1 otherwise). HBM traffic per fused pass is
    `n_fields`-proportional and halo-independent on the grid-tiled path:
    one compulsory read + write of every field, exactly what the MONC
    multi-kernel amortisation story predicts when extra operators ride
    the same rings.
    """
    slice_b = Y * Z * itemsize
    lane_eff = 1.0 if Z % 128 == 0 else (Z % 128) / 128.0
    if variant == "wide":
        _check_wide_model_tile(Y, y_tile, grid_tiled)
    if halo_depth is None:
        halo = T if variant == "fused" else 1
    else:
        halo = halo_depth
    # host tiling: interior tile boundaries each re-read `halo` rows from
    # both sides; in-grid tiling serves those rows from VMEM instead
    overlap_rows = 0 if grid_tiled else _host_overlap_rows(Y, y_tile, halo)
    tiled_slice_b = (Y + overlap_rows) * Z * itemsize
    if variant == "blocked":
        # n_fields x 3 views x X slices
        reads = T * n_fields * 3 * X * tiled_slice_b
    elif variant in ("dataflow", "wide"):
        reads = T * n_fields * X * tiled_slice_b
    elif variant == "fused":
        reads = n_fields * X * tiled_slice_b   # ONE pass for all T steps
    elif variant == "pointwise":
        # naive per-point gathers (7-point)
        reads = T * n_fields * 7 * X * slice_b
    else:
        raise ValueError(variant)
    # host tiling: each block's kernel writes its full slab (halo rows
    # included, trimmed host-side), so the overlap is paid on the write side
    # too — except pointwise, which has no tiled execution path. In-grid
    # tiling writes every output row exactly once (overlap_rows == 0).
    w_slice_b = slice_b if variant == "pointwise" else tiled_slice_b
    writes = (1 if variant == "fused" else T) * n_fields * X * w_slice_b
    eff = lane_eff if variant != "wide" else 1.0
    total = (reads + writes) / eff
    if not fuse_update and variant != "fused":
        # unfused host-side `f + dt*s` pass: read field + read source +
        # write field, per field per step (contiguous, no lane penalty)
        total += T * 3 * n_fields * X * slice_b
    return int(total)


def vmem_halo_bytes_model(X: int, Y: int, Z: int, itemsize: int,
                          variant: str, *, T: int = 1,
                          y_tile: int | None = None, n_fields: int = 3,
                          halo_depth: int | None = None) -> int:
    """Halo re-read bytes the in-grid path serves from VMEM instead of HBM.

    This is the read-side overlap the host-tiled model charges to HBM
    (`2*halo` rows per interior tile boundary, per x-slice, per field,
    per view for `blocked`), relocated on-chip: the slab rows are already
    resident in the persistent shift register when the tile's stencil
    re-reads them. The halo is the slab's FETCH halo — T for `fused`,
    the sublane-rounded 8 rows for `wide` (matching what
    ``fused_register_bytes(halo=8)`` sizes), 1 for the other source
    kernels — and the untiled fallback (`y_tile + 2*halo > Y`, where the
    kernel runs a single full-domain tile) is mirrored, so configs with
    no tiled execution report zero. The host path's write-side overlap
    has no VMEM counterpart — in-grid outputs are simply written once.

    `n_fields` / `halo_depth` generalise to the stencil-spec frontend:
    the spec kernel's slab halo is `spec.halo(T)` deep and every one of
    `spec.n_fields` rings re-reads it from VMEM residency.
    """
    if variant == "pointwise":
        return 0   # no tiled execution path
    if variant == "wide":
        _check_wide_model_tile(Y, y_tile, grid_tiled=True)
    if halo_depth is None:
        halo = {"fused": T, "wide": _WIDE_HALO}.get(variant, 1)
    else:
        halo = halo_depth
    _, _, n_ty = _grid_geometry(Y, y_tile, halo)
    overlap_rows = 2 * halo * (n_ty - 1)
    views = 3 if variant == "blocked" else 1
    passes = 1 if variant == "fused" else T
    return passes * views * n_fields * X * overlap_rows * Z * itemsize
