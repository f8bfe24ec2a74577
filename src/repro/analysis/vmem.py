"""VMEM budget pass: statically sum every named on-chip buffer a config
will allocate and refuse to build past `roofline.VMEM_PER_CORE` —
BEFORE anything compiles.

The serving tier already had this discipline for ONE buffer class
(`roofline.serving_max_batch` bounds the batched slot rings); this pass
generalises it to every rung of the ladder: the fused kernel's
shift-register ring (`kernels.advection.fused_register_bytes`, spec
geometry included) on every shard of a distributed block, and the
serving engine's per-slot rings. (The remote-DMA exchange moves its
bands HBM to HBM, so it holds no VMEM.) A `VmemPlan` is a list of named
buffers plus the budget; `check()` raises `VmemBudgetExceeded` NAMING
the largest offender, so an over-budget config fails at build/trace
time with the buffer to shrink instead of at compile time with a Mosaic
allocation error (or, worse, on hardware).

Builders return plans; the distributed drivers and the serving engine
call `check()` on them at trace/alloc time, and `scripts/lint_movement.py`
audits representative ladder configs without building anything.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

from repro.core import roofline as R
from repro.kernels.advection import advection as K

__all__ = [
    "VmemBudgetExceeded", "VmemBuffer", "VmemPlan", "fused_ring_plan",
    "distributed_block_plan", "serving_ring_plan", "plan_max_batch",
]


class VmemBudgetExceeded(ValueError):
    """A statically-planned VMEM footprint exceeds the per-core budget.
    The message names every buffer and the largest offender — the knob
    to shrink (y_tile, T, batch, depth) is always one of the named
    buffers' parameters."""


@dataclass(frozen=True)
class VmemBuffer:
    """One named on-chip allocation: `name` is what the error reports,
    `note` records the sizing formula's inputs for the audit trail."""
    name: str
    nbytes: int
    note: str = ""


@dataclass(frozen=True)
class VmemPlan:
    """A static VMEM plan: named buffers vs the per-core budget."""
    buffers: Tuple[VmemBuffer, ...]
    budget: int = R.VMEM_PER_CORE
    context: str = ""

    def total(self) -> int:
        return sum(b.nbytes for b in self.buffers)

    def headroom(self) -> int:
        return self.budget - self.total()

    def fits(self) -> bool:
        return self.total() <= self.budget

    def table(self) -> str:
        lines = [f"  {b.nbytes:>12d} B  {b.name}"
                 + (f"  ({b.note})" if b.note else "")
                 for b in self.buffers]
        lines.append(f"  {self.total():>12d} B  TOTAL"
                     f"  (budget {self.budget} B)")
        return "\n".join(lines)

    def check(self) -> "VmemPlan":
        if not self.fits():
            worst = max(self.buffers, key=lambda b: b.nbytes)
            where = f" [{self.context}]" if self.context else ""
            raise VmemBudgetExceeded(
                f"static VMEM plan{where} needs {self.total()} B, budget "
                f"is {self.budget} B ({R.VMEM_PER_CORE} per core); "
                f"largest buffer: {worst.name!r} at {worst.nbytes} B"
                + (f" ({worst.note})" if worst.note else "")
                + f"\n{self.table()}")
        return self


# ---- builders ----------------------------------------------------------

def fused_ring_plan(y_rows: int, Z: int, *, T: int, itemsize: int = 4,
                    y_tile: Optional[int] = None,
                    halo: Optional[int] = None, n_fields: int = 3,
                    n_slots: int = 3, n_levels: Optional[int] = None,
                    batch: int = 1, budget: int = R.VMEM_PER_CORE,
                    context: str = "") -> VmemPlan:
    """The fused kernel's shift-register ring (`fused_register_bytes`,
    spec geometry via n_fields/n_slots/n_levels/halo), `batch` slots of
    it for the batched mega-launch."""
    per_slot = K.fused_register_bytes(
        T, y_rows, Z, itemsize, y_tile, halo,
        n_fields=n_fields, n_slots=n_slots, n_levels=n_levels)
    name = ("fused shift-register ring" if batch == 1
            else f"batched slot rings (batch={batch})")
    note = (f"{per_slot} B/slot: {n_fields} fields x "
            f"{n_slots}x{T if n_levels is None else n_levels} slices, "
            f"y_tile={y_tile}, Z={Z}")
    buf = VmemBuffer(name, batch * per_slot, note)
    return VmemPlan((buf,), budget=budget, context=context)


def distributed_block_plan(shard_shape: Tuple[int, int, int], *, T: int,
                           itemsize: int = 4, local_kernel: str,
                           y_tile: Optional[int] = None, nx: int = 1,
                           ny: int = 1, spec=None,
                           budget: int = R.VMEM_PER_CORE,
                           context: str = "") -> VmemPlan:
    """Static per-shard VMEM plan of one distributed substep block: the
    fused ring over the halo-EXTENDED slab (when `local_kernel="fused"`;
    the jnp reference loop holds no VMEM of its own, and either exchange
    engine moves its bands HBM to HBM). `spec` switches the ring to the
    generalised `stencil_fused` geometry and the exchange depth to
    `spec.halo(T)`.
    """
    Xl, Yl, Z = shard_shape
    depth = spec.halo(T) if spec is not None else T
    dx = depth if nx > 1 else 0
    dy = depth if ny > 1 else 0
    buffers = []
    if local_kernel == "fused":
        ring_kw = {}
        if spec is not None:
            ring_kw = dict(n_fields=spec.n_fields,
                           n_slots=2 * spec.radius + 1,
                           n_levels=spec.stages * T, halo=depth)
        per = K.fused_register_bytes(T, Yl + 2 * dy, Z, itemsize, y_tile,
                                     **ring_kw)
        buffers.append(VmemBuffer(
            "fused shift-register ring (halo-extended shard slab)", per,
            f"slab {(Xl + 2 * dx, Yl + 2 * dy, Z)}, y_tile={y_tile}, "
            f"T={T}, depth={depth}"))
    return VmemPlan(tuple(buffers), budget=budget, context=context)


def serving_ring_plan(Y: int, Z: int, *, batch: int, T: int,
                      itemsize: int = 4, y_tile: Optional[int] = None,
                      n_fields: int = 3, budget: int = R.VMEM_PER_CORE,
                      context: str = "") -> VmemPlan:
    """The serving engine's batched slot rings — the buffer class
    `roofline.serving_max_batch` bounds; `plan_max_batch` proves the two
    agree."""
    return fused_ring_plan(Y, Z, T=T, itemsize=itemsize, y_tile=y_tile,
                           n_fields=n_fields, batch=batch, budget=budget,
                           context=context)


def plan_max_batch(Y: int, Z: int, *, T: int, itemsize: int = 4,
                   y_tile: Optional[int] = None, n_fields: int = 3,
                   budget: int = R.VMEM_PER_CORE) -> int:
    """Largest batch whose `serving_ring_plan` fits: defined THROUGH
    `roofline.serving_max_batch` so the serving-only check and the
    generalised pass can never drift apart (a test pins the
    equivalence)."""
    per_slot = K.fused_register_bytes(T, Y, Z, itemsize, y_tile,
                                      n_fields=n_fields)
    return R.serving_max_batch(per_slot, vmem_budget=budget)
