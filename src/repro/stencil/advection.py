"""Domain-level PW advection: the paper's application, end to end.

`AdvectionDomain` owns the (X, Y, Z) wind fields and steps them with any of
the kernel-ladder variants (jnp reference = the paper's CPU baseline;
Pallas blocked/dataflow/wide/fused = the FPGA kernel stages v1-v4). The
stratus-cloud test-case initialisation mirrors the paper's standard MONC
case sizes (Fig. 8: 1M .. 268M grid points at z=64). A (mesh_nx, mesh_ny)
configuration additionally prices the 2D-decomposed distributed step —
per-shard HBM pass, depth-T exchange wire bytes, and (via `exchange` /
`overlap`) how much of that exchange the configured engine hides behind
the interior pass (`roofline_terms().collective_exposed_s`).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import roofline as R
from repro.kernels.advection import advection as K
from repro.kernels.advection import ref as REF

VARIANTS = ("reference", "blocked", "dataflow", "wide", "fused")

# the paper's experiment grid sizes (Fig. 8), (x, y, z)
PAPER_GRIDS = {
    "1M": (16, 1024, 64),
    "4M": (64, 1024, 64),
    "16M": (256, 1024, 64),   # Fig. 3/5 use 512x512x64 = 16.7M
    "67M": (1024, 1024, 64),
    "268M": (4096, 1024, 64),
}


def _stratus_f64(X: int, Y: int, Z: int, seed: int):
    """Yield the stratus (u, v, w) in float64, one at a time (a 268M-cell
    field is 2 GiB at this precision)."""
    rng = np.random.default_rng(seed)
    kx = np.linspace(0, 2 * np.pi, X)[:, None, None]
    ky = np.linspace(0, 2 * np.pi, Y)[None, :, None]
    kz = np.linspace(0, np.pi, Z)[None, None, :]
    for smooth in (lambda: 5.0 * np.sin(kx + 0.5) * np.cos(ky)
                   * np.sin(kz + 0.1),
                   lambda: 4.0 * np.cos(kx) * np.sin(ky + 0.3) * np.sin(kz),
                   lambda: 0.5 * np.sin(kx) * np.sin(ky) * np.cos(kz)):
        f = smooth()
        f += 0.01 * rng.normal(size=f.shape)
        yield f


def stratus_fields(X: int, Y: int, Z: int, seed: int = 0,
                   dtype=jnp.float32) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Smooth, divergence-ish wind fields standing in for the stratus case."""
    return tuple(jnp.asarray(f, dtype) for f in _stratus_f64(X, Y, Z, seed))


def stratus_fields_host(X: int, Y: int, Z: int, seed: int = 0,
                        dtype=np.float32):
    """`stratus_fields` as host numpy arrays, for placing a grid straight
    into a sharding or layout with `jax.device_put`."""
    return tuple(np.asarray(f, dtype) for f in _stratus_f64(X, Y, Z, seed))


@dataclasses.dataclass(frozen=True)
class AdvectionDomain:
    """Frozen: the jitted kernel is memoized on first use, so mutable config
    would silently run a stale kernel. Use dataclasses.replace to vary."""
    X: int
    Y: int
    Z: int
    variant: str = "dataflow"
    interpret: Optional[bool] = None  # None: compiled on TPU, else interpret
    dtype: str = "float32"
    fuse_T: int = 4                   # fused (v4): Euler steps per HBM pass
    y_tile: Optional[int] = None      # y-tiles (VMEM-bounded register)
    tiling: str = "grid"              # "grid": in-grid (y_tile, x) 2D grid;
                                      # "host": retained per-block loop
    fuse_update: bool = False         # v1-v3: fold f + dt*s into the kernel
    dt: float = 1.0
    mesh_nx: int = 1                  # 2D (x, y) mesh decomposition shape,
    mesh_ny: int = 1                  # for the per-shard accounting below
                                      # (step() itself stays single-shard;
                                      # make_distributed_step runs the mesh)
    exchange: str = "collective"      # halo-band transport engine and
    overlap: bool = False             # interior/boundary split, for the
                                      # overlap-efficiency accounting below
    n_blocks: int = 1                 # substep-blocks per pipelined
                                      # make_distributed_run program
                                      # (1 = the one-block step)
    batch: int = 1                    # serving-tier slots: independent
                                      # domains of this shape packed into
                                      # one mega-launch. Pure per-tenant
                                      # ACCOUNTING — step() stays
                                      # single-domain; the flops/bytes/wire
                                      # methods and vmem_register_bytes
                                      # scale by it, and
                                      # serving_throughput() prices the
                                      # packed launch in domains/s

    def __post_init__(self):
        if self.exchange not in ("collective", "remote_dma"):
            raise ValueError(f"exchange must be 'collective' or "
                             f"'remote_dma', got {self.exchange!r}")
        if self.n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {self.n_blocks}")
        if self.batch < 1:
            raise ValueError(f"batch must be >= 1, got {self.batch}")
        object.__setattr__(self, "params",
                           REF.default_params(self.Z,
                                              dtype=jnp.dtype(self.dtype)))
        object.__setattr__(self, "_kernel", None)

    def kernel(self) -> Callable:
        """Jitted kernel for the configured variant, built once: jit caches
        by function identity, so rebuilding per call would retrace (and
        re-lower the Pallas kernel) on every step."""
        if self._kernel is not None:
            return self._kernel
        p = self.params
        v = self.variant
        if v == "reference":
            if self.fuse_update:
                fn = lambda u, vv, w: REF.pw_step_ref(u, vv, w, p, self.dt)
            else:
                fn = lambda u, vv, w: REF.pw_advect_ref(u, vv, w, p)
        elif v in ("blocked", "dataflow", "wide"):
            kern = {"blocked": K.advect_blocked, "dataflow": K.advect_dataflow,
                    "wide": K.advect_wide}[v]
            fn = lambda u, vv, w: kern(u, vv, w, p,
                                       interpret=self.interpret,
                                       y_tile=self.y_tile,
                                       tiling=self.tiling,
                                       fuse_update=self.fuse_update,
                                       dt=self.dt)
        elif v == "fused":
            fn = lambda u, vv, w: K.advect_fused(u, vv, w, p, T=self.fuse_T,
                                                 dt=self.dt,
                                                 interpret=self.interpret,
                                                 y_tile=self.y_tile,
                                                 tiling=self.tiling)
        else:
            raise ValueError(v)
        object.__setattr__(self, "_kernel", jax.jit(fn))
        return self._kernel

    def init(self, seed: int = 0):
        return stratus_fields(self.X, self.Y, self.Z, seed,
                              jnp.dtype(self.dtype))

    def sources(self, u, v, w):
        if self.variant == "fused":
            raise ValueError("fused advances fields in-kernel; use step()")
        if self.fuse_update:
            raise ValueError("fuse_update kernels advance fields in-kernel; "
                             "use step()")
        return self.kernel()(u, v, w)

    def step(self, u, v, w, dt: Optional[float] = None):
        """One advection update. For `fused` (and the v1-v3 rungs with
        `fuse_update=True`) this is the fast path: the kernel advances the
        fields in a single HBM pass with dt baked in (dt override is
        rejected there), instead of writing sources and paying an extra
        full-field read at update time."""
        if self.variant == "fused" or self.fuse_update:
            if dt is not None and dt != self.dt:
                raise ValueError("the fused-update kernel bakes dt in; set "
                                 "AdvectionDomain(dt=...) instead")
            return self.kernel()(u, v, w)
        dt = self.dt if dt is None else dt
        su, sv, sw = self.sources(u, v, w)
        return u + dt * su, v + dt * sv, w + dt * sw

    def substeps_per_step(self) -> int:
        """Euler substeps one step() call advances (T for fused, else 1)."""
        return self.fuse_T if self.variant == "fused" else 1

    def advance(self, u, v, w, n_substeps: int):
        """Run `n_substeps` Euler substeps, using the fused fast path in
        chunks of `fuse_T` when the variant supports it."""
        per = self.substeps_per_step()
        if n_substeps % per:
            raise ValueError(f"n_substeps={n_substeps} not a multiple of "
                             f"fuse_T={per}")
        for _ in range(n_substeps // per):
            u, v, w = self.step(u, v, w)
        return u, v, w

    def flops_per_step(self) -> int:
        cells = (self.X - 2) * (self.Y - 2) * (self.Z - 2)
        return (cells * REF.flops_per_cell() * self.substeps_per_step()
                * self.batch)

    def _hbm_bytes_pass(self, X: int, Y: int) -> int:
        """One kernel pass over an (X, Y, Z) extent on the configured
        execution path — the single pricing point `hbm_bytes_per_step`
        (global) and `hbm_bytes_per_shard_step` (halo'd shard slab) share,
        so the two can never desynchronise."""
        fused_upd = self.variant == "fused" or self.fuse_update
        return K.hbm_bytes_model(X, Y, self.Z,
                                 jnp.dtype(self.dtype).itemsize,
                                 self.variant if self.variant != "reference"
                                 else "pointwise",
                                 T=self.substeps_per_step(),
                                 y_tile=self.y_tile,
                                 grid_tiled=self.tiling == "grid",
                                 fuse_update=fused_upd)

    def hbm_bytes_per_step(self) -> int:
        """Modelled HBM bytes per step() call (fused: per T-step pass).

        Prices the configured execution path: in-grid vs host tiling, and
        whether the Euler update is fused in-kernel or paid as a separate
        full-field pass (always separate for `reference`). A `batch` > 1
        charges every packed slot's pass — slots share nothing.
        """
        return self._hbm_bytes_pass(self.X, self.Y) * self.batch

    def vmem_halo_bytes_per_step(self) -> int:
        """Halo re-read bytes served from VMEM by the in-grid tiled path."""
        if self.tiling != "grid":
            return 0
        return K.vmem_halo_bytes_model(self.X, self.Y, self.Z,
                                       jnp.dtype(self.dtype).itemsize,
                                       self.variant
                                       if self.variant != "reference"
                                       else "pointwise",
                                       T=self.substeps_per_step(),
                                       y_tile=self.y_tile) * self.batch

    def shard_shape(self) -> Tuple[int, int]:
        """Owned (Xl, Yl) per-shard dims on the (mesh_nx, mesh_ny) mesh."""
        if self.mesh_nx < 1 or self.mesh_ny < 1:
            raise ValueError(f"mesh shape must be >= 1, got "
                             f"({self.mesh_nx}, {self.mesh_ny})")
        if self.X % self.mesh_nx or self.Y % self.mesh_ny:
            raise ValueError(
                f"grid ({self.X}, {self.Y}) not divisible by mesh "
                f"({self.mesh_nx}, {self.mesh_ny}); shard_map requires "
                "even shards")
        return self.X // self.mesh_nx, self.Y // self.mesh_ny

    def hbm_bytes_per_shard_step(self) -> int:
        """Per-shard HBM bytes per step(): the kernel pass over the halo'd
        (Xl+2T, Yl+2T, Z) shard slab `make_distributed_step` streams — the
        quantity that must FALL as the mesh grows for the 268M grid to
        become per-device feasible (the scaling2d gate)."""
        Xl, Yl = self.shard_shape()
        T = self.substeps_per_step()
        Xs = Xl + (2 * T if self.mesh_nx > 1 else 0)
        Ys = Yl + (2 * T if self.mesh_ny > 1 else 0)
        return self._hbm_bytes_pass(Xs, Ys) * self.batch

    def halo_wire_bytes_per_step(self) -> int:
        """Per-shard wire bytes for the ONE depth-T exchange a distributed
        step() performs (zero on a 1x1 mesh), per packed batch slot."""
        return R.halo_wire_bytes_model(self.X, self.Y, self.Z,
                                       jnp.dtype(self.dtype).itemsize,
                                       nx=self.mesh_nx, ny=self.mesh_ny,
                                       T=self.substeps_per_step()) * self.batch

    def overlap_efficiency(self) -> float:
        """Modelled fraction of the depth-T exchange the configured engine
        hides behind the halo-independent interior pass
        (`roofline.overlap_efficiency_model` over this domain's shard
        geometry). 0.0 on a 1x1 mesh or with overlap=False."""
        if self.mesh_nx * self.mesh_ny == 1:
            return 0.0
        Xl, Yl = self.shard_shape()
        frac = R.interior_compute_fraction(Xl, Yl, self.substeps_per_step(),
                                           nx=self.mesh_nx, ny=self.mesh_ny)
        return R.overlap_efficiency_model(overlap=self.overlap,
                                          exchange=self.exchange,
                                          interior_fraction=frac)

    def pipeline_efficiency(self) -> float:
        """Per-block hidden fraction over an `n_blocks`-block pipelined
        run (`roofline.pipeline_efficiency_model` over this domain's
        shard geometry): the remote-DMA engine's cross-block
        double-buffered hiding pays one pipeline-fill block, the
        collective engine's within-block figure is K-independent. Equals
        `overlap_efficiency()` for the collective engine; 0.0 on a 1x1
        mesh, with overlap=False, or for an isolated remote-DMA block
        (n_blocks=1 — its kernel serialises its own waits)."""
        if self.mesh_nx * self.mesh_ny == 1:
            return 0.0
        Xl, Yl = self.shard_shape()
        frac = R.interior_compute_fraction(Xl, Yl, self.substeps_per_step(),
                                           nx=self.mesh_nx, ny=self.mesh_ny)
        return R.pipeline_efficiency_model(n_blocks=self.n_blocks,
                                           overlap=self.overlap,
                                           exchange=self.exchange,
                                           interior_fraction=frac)

    def roofline_terms(self) -> R.RooflineTerms:
        """Three-term roofline of one distributed step() on the configured
        (mesh_nx, mesh_ny) mesh, with the exchange bytes feeding
        ``collective_s`` and the engine's overlap efficiency splitting it
        into hidden vs exposed seconds. With `n_blocks > 1` the split uses
        the pipelined per-block efficiency (`pipeline_efficiency`) — the
        terms then price one block of the `make_distributed_run` program;
        `n_blocks=1` keeps the single-block `overlap_efficiency` figure
        (back-compat: BENCH_overlap's ladder)."""
        n_dev = self.mesh_nx * self.mesh_ny
        eff = (self.pipeline_efficiency() if self.n_blocks > 1
               else self.overlap_efficiency())
        return R.RooflineTerms(
            flops_per_dev=self.flops_per_step() / n_dev,
            hbm_bytes_per_dev=self.hbm_bytes_per_shard_step(),
            ici_wire_bytes=self.halo_wire_bytes_per_step(),
            dcn_wire_bytes=0.0,
            n_chips=n_dev,
            overlap_efficiency=eff)

    def vmem_register_bytes(self) -> int:
        """VMEM shift-register footprint of the current configuration —
        one ring per packed batch slot (the batched-grid layout keeps
        every resident slot's ring on chip so the batch dimension can
        pipeline; `serving_throughput` binds on this)."""
        depth = self.fuse_T if self.variant == "fused" else 1
        itemsize = jnp.dtype(self.dtype).itemsize
        # wide's grid-tiled slab carries the sublane-rounded fetch halo
        halo = K._WIDE_HALO if (self.variant == "wide"
                                and self.tiling == "grid"
                                and self.y_tile is not None) else None
        return K.fused_register_bytes(depth, self.Y, self.Z, itemsize,
                                      y_tile=self.y_tile, halo=halo
                                      ) * self.batch

    def guard_bytes_per_step(self) -> int:
        """Extra HBM bytes per mega-launch of the finite-guard pass
        (`roofline.guard_bytes_model`): one read pass over the three
        advanced fields plus X flag words per packed slot. The serving
        tier's fault detection priced next to the field bytes it watches
        — half the fused six-array pass, amortised over the fuse_T Euler
        steps each pass carries, and gated counted == modelled EXACTLY
        in BENCH_faults.json."""
        if self.variant != "fused":
            raise ValueError("the finite guard rides the fused kernel; "
                             f"variant={self.variant!r} has no guard path")
        return R.guard_bytes_model(self.X, self.Y, self.Z,
                                   batch=self.batch)

    def serving_throughput(self) -> float:
        """Modelled domains/s of serving `batch` independent copies of
        this domain per mega-launch (`roofline.serving_throughput_model`):
        the fixed launch overhead amortised over the packed slots against
        each slot's HBM pass and exposed wire seconds. Strictly rises in
        `batch` until the per-slot rings exceed the VMEM budget
        (`roofline.serving_max_batch`), where the model refuses — the
        BENCH_serving gate pair."""
        t = self.roofline_terms()
        return R.serving_throughput_model(
            self.batch,
            hbm_bytes_per_domain=t.hbm_bytes_per_dev / self.batch,
            ring_bytes_per_slot=self.vmem_register_bytes() // self.batch,
            exposed_wire_s_per_domain=t.collective_exposed_s / self.batch)
