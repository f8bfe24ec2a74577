"""MovementLedger: ONE jaxpr walk attributing every moved byte to a
category — the pass that subsumes the four copy-pasted `count_*`
counters in `stencil/distributed.py` (now thin wrappers over this).

Categories (the paper's profiling-table rows, trace-time edition):

  ppermute_wire      rank >= 3 ppermute operands — the halo band
                     payloads both exchange engines put on the wire
                     (priced by `roofline.halo_wire_bytes_model`).
  integrity_words    rank < 3 ppermute operands — the uint32
                     `band_checksum` words a verified exchange rides on
                     each band (`roofline.integrity_bytes_model`).
  pallas_hbm         field operands/results (rank >= 3, both trailing
                     dims > 1) of field-moving `pallas_call`s — the HBM
                     streams (`kernels.advection.hbm_bytes_model`).
  guard_field_reads  field operands of guard-pass `pallas_call`s (no
                     result is a field — the guard signature): the
                     detection re-read of the fields.
  guard_flag_words   non-field operands/results of guard-pass calls: the
                     flag words. guard_field_reads + guard_flag_words
                     is `roofline.guard_bytes_model`'s quantity.
  pallas_control     non-field operands/results of field-moving
                     `pallas_call`s — packed coefficient rows and
                     interior mask columns, scalar-pipeline traffic the
                     analytic models deliberately never charged (the
                     documented exclusion in `count_pallas_hbm_bytes`);
                     the coverage pass treats it as unpriced-by-design.
  all_gather         operands of `all_gather` — NEW visibility: the
  psum               elastic regather / reduction traffic no legacy
  all_to_all         counter saw. No model term prices these yet, so
                     any nonzero total FAILS the coverage pass until a
                     model claims it — "anything uncounted is an
                     error".
  host_transfer      operands of `device_put` — explicit host/device
                     traffic inside a traced program.

The model-coverage pass (`check_model_coverage`) closes the loop: given
the ledger and a dict of analytic claims {category: exact bytes}, it
fails on (a) counted bytes no claim covers, (b) a claim the count
contradicts, and (c) a claim for bytes the trace never moves. The
legacy gates checked only the bytes they knew about; this makes new
movement a PR introduces break the gate instead of sliding past it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.extend.core as jax_core

from repro.analysis.jaxpr import aval_bytes, walk_jaxpr

__all__ = [
    "CATEGORIES", "MovementRecord", "MovementLedger", "audit_movement",
    "count_ppermute_bytes",
    "CoverageFailure", "CoverageReport", "check_model_coverage",
    "ModelCoverageError",
]

CATEGORIES = (
    "ppermute_wire", "integrity_words", "pallas_hbm",
    "guard_field_reads", "guard_flag_words", "pallas_control",
    "all_gather", "psum", "all_to_all", "host_transfer",
)

# collectives recorded under their own primitive name
_COLLECTIVES = ("all_gather", "psum", "all_to_all")


@dataclass(frozen=True)
class MovementRecord:
    """One attributed operand: `nbytes` of `category` traffic moved by
    `primitive` (with the Pallas kernel name when there is one)."""
    category: str
    primitive: str
    nbytes: int
    shape: Tuple[int, ...]
    dtype: str
    kernel: str = ""


def _kernel_name(eqn) -> str:
    nsi = eqn.params.get("name_and_src_info")
    return str(getattr(nsi, "name", nsi or ""))


def _is_field(aval) -> bool:
    """A Pallas operand that is a field: rank >= 3 with both trailing dims
    wider than one. The kernels' control operands — coefficient rows
    ``(1, N)``, mask columns ``(N, 1)`` and their slot-stacked forms — are
    rank < 3 or have a unit trailing dim."""
    shape = tuple(getattr(aval, "shape", ()))
    return len(shape) >= 3 and min(shape[-2:]) > 1


class MovementLedger:
    """The attributed byte records of one traced program."""

    def __init__(self, records=()):
        self.records: list = list(records)

    # ---- construction -------------------------------------------------
    @classmethod
    def of(cls, fn, *args) -> "MovementLedger":
        """Trace `fn(*args)` (never executing it) and attribute every
        byte its jaxpr moves. Inside `shard_map` shapes are per-shard,
        so on a distributed driver the totals are per-shard — the same
        convention every legacy counter and analytic model uses."""
        return cls.from_traced(jax.make_jaxpr(fn)(*args))

    @classmethod
    def from_traced(cls, traced) -> "MovementLedger":
        jaxpr = (traced.jaxpr
                 if isinstance(traced, jax_core.ClosedJaxpr) else traced)
        records = []

        def add(category, eqn, var, kernel=""):
            aval = var.aval
            records.append(MovementRecord(
                category=category, primitive=eqn.primitive.name,
                nbytes=aval_bytes(aval),
                shape=tuple(getattr(aval, "shape", ())),
                dtype=str(getattr(aval, "dtype", "?")), kernel=kernel))

        def visit(eqn):
            name = eqn.primitive.name
            if name == "ppermute":
                for var in eqn.invars:
                    ndim = getattr(var.aval, "ndim", 0)
                    add("ppermute_wire" if ndim >= 3 else "integrity_words",
                        eqn, var)
            elif name == "pallas_call":
                kernel = _kernel_name(eqn)
                # the guard signature: NO result is a field (flags are
                # (X,) / (B*X,); field kernels emit fields)
                guard = not any(_is_field(v.aval) for v in eqn.outvars)
                for var in list(eqn.invars) + list(eqn.outvars):
                    field_ = _is_field(var.aval)
                    if guard:
                        cat = ("guard_field_reads" if field_
                               else "guard_flag_words")
                    else:
                        cat = "pallas_hbm" if field_ else "pallas_control"
                    add(cat, eqn, var, kernel)
            elif name in _COLLECTIVES:
                for var in eqn.invars:
                    add(name, eqn, var)
            elif name == "device_put":
                for var in eqn.invars:
                    add("host_transfer", eqn, var)

        walk_jaxpr(jaxpr, visit)
        return cls(records)

    # ---- queries ------------------------------------------------------
    def total(self, *categories: str) -> int:
        for c in categories:
            if c not in CATEGORIES:
                raise KeyError(f"unknown movement category {c!r}; "
                               f"one of {CATEGORIES}")
        return sum(r.nbytes for r in self.records if r.category in categories)

    def totals(self) -> Dict[str, int]:
        """Per-category byte totals — every category, zeros included."""
        out = {c: 0 for c in CATEGORIES}
        for r in self.records:
            out[r.category] += r.nbytes
        return out

    def grand_total(self) -> int:
        return sum(r.nbytes for r in self.records)

    def __len__(self) -> int:
        return len(self.records)

    def __repr__(self) -> str:
        nz = {c: b for c, b in self.totals().items() if b}
        return f"MovementLedger({len(self.records)} records, {nz})"


def audit_movement(fn, *args) -> MovementLedger:
    """Convenience alias: `MovementLedger.of(fn, *args)`."""
    return MovementLedger.of(fn, *args)


def count_ppermute_bytes(fn, args, keep) -> int:
    """Summed sizes of the ppermute operands selected by `keep(aval)` in
    `fn`'s recursively walked jaxpr — the generic form the wire and
    integrity counters in `stencil.distributed` partition by rank
    (moved here from that module; it re-exports this as
    `_count_ppermute_bytes` for backward compatibility)."""
    closed = jax.make_jaxpr(fn)(*args)
    total = [0]

    def visit(eqn):
        if eqn.primitive.name == "ppermute":
            for var in eqn.invars:
                if keep(var.aval):
                    total[0] += aval_bytes(var.aval)

    walk_jaxpr(closed.jaxpr, visit)
    return total[0]


# ---- model-coverage pass ----------------------------------------------

class ModelCoverageError(AssertionError):
    """The traced program moves bytes the analytic models do not claim
    (or a model claims bytes the trace contradicts). Raised by
    `CoverageReport.raise_if_failed`."""


@dataclass(frozen=True)
class CoverageFailure:
    category: str
    counted: int
    claimed: Optional[int]
    reason: str

    def __str__(self) -> str:
        return (f"[{self.category}] counted={self.counted} "
                f"claimed={self.claimed}: {self.reason}")


@dataclass
class CoverageReport:
    ok: bool
    failures: Tuple[CoverageFailure, ...]
    counted: Dict[str, int] = field(default_factory=dict)
    claims: Dict[str, int] = field(default_factory=dict)
    unpriced: Tuple[str, ...] = ()

    def raise_if_failed(self) -> None:
        if not self.ok:
            lines = "\n  ".join(str(f) for f in self.failures)
            raise ModelCoverageError(
                f"model coverage failed ({len(self.failures)} "
                f"failure(s)):\n  {lines}")


def check_model_coverage(ledger: MovementLedger,
                         claims: Dict[str, int], *,
                         unpriced: Tuple[str, ...] = ("pallas_control",),
                         ) -> CoverageReport:
    """Every counted byte must be claimed EXACTLY by an analytic model
    term, or appear in `unpriced` (categories documented as
    deliberately unpriced — default: the scalar-pipeline `pallas_control`
    traffic `count_pallas_hbm_bytes` always excluded). Conversely every
    claim must match the count exactly — a model pricing movement the
    trace does not perform is as wrong as unpriced movement."""
    counted = ledger.totals()
    failures = []
    for cat in CATEGORIES:
        if cat in unpriced:
            if cat in claims:
                failures.append(CoverageFailure(
                    cat, counted[cat], claims[cat],
                    "category is both claimed and declared unpriced — "
                    "pick one"))
            continue
        have = counted[cat]
        if cat in claims:
            want = int(claims[cat])
            if have != want:
                reason = ("model claims bytes the trace never moves"
                          if have == 0 else
                          "counted bytes contradict the model claim")
                failures.append(CoverageFailure(cat, have, want, reason))
        elif have:
            failures.append(CoverageFailure(
                cat, have, None,
                "unclaimed movement: no analytic model term prices these "
                "bytes (add a model claim or an explicit unpriced entry)"))
    unknown = sorted(set(claims) - set(CATEGORIES))
    for cat in unknown:
        failures.append(CoverageFailure(
            cat, 0, claims[cat],
            f"claim names no ledger category (one of {CATEGORIES})"))
    return CoverageReport(ok=not failures, failures=tuple(failures),
                          counted=counted, claims=dict(claims),
                          unpriced=tuple(unpriced))
