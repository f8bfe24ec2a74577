"""Tiling-contract linter: check every `pallas_call` in a traced program
against the TPU tiling contract — statically, from the grid mapping the
trace already carries.

Three checks per block mapping:

  lane / sublane  (warn) the last two block-shape dims should be
                  multiples of the (8, 128) sublane/lane tile
                  (`roofline` and every kernel docstring's contract).
                  Misaligned blocks LOWER correctly but pad each
                  vregister — the ladder's lane-efficiency penalty
                  (`hbm_bytes_model`'s Z % 128 discount) made that cost
                  visible; the linter makes it enumerable. Warnings,
                  not errors: the interpret-mode compute grids are
                  deliberately tiny and misaligned.
  element-oob     (error) for `pl.Element` block dims the index map
                  returns ELEMENT offsets with no XLA clamp semantics:
                  the linter evaluates the index-map jaxpr over the
                  launch grid (every point up to `max_grid_points`,
                  corners beyond) and flags any block reaching outside
                  the operand extent — the out-of-bounds read/write a
                  wrong `_slab_lo` clip would cause, caught before
                  anything runs.  Blocked dims of the same mapping are
                  checked too, their block index scaled to elements.
  alias-*         (error) `input_output_aliases` pairs update a buffer
                  in place: operand/result extents must match
                  (alias-shape) and, when either side has an Element
                  dim, their index maps must address the same window at
                  every grid point (alias-window) — otherwise the
                  in-place write lands somewhere the aliased read
                  didn't come from.

`lint_tiling(fn, *args)` walks the whole traced program (pjit /
shard_map / loop bodies included) and returns a `TilingReport`;
`scripts/lint_movement.py` gates errors == 0 over the ladder configs
and pins the warning census in BENCH_analysis.json.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Tuple

import jax
import numpy as np

from repro.analysis.jaxpr import walk_jaxpr

__all__ = ["TilingIssue", "TilingReport", "lint_tiling",
           "SUBLANE", "LANE"]

SUBLANE, LANE = 8, 128


@dataclass(frozen=True)
class TilingIssue:
    severity: str      # "error" | "warn"
    kind: str          # "lane" | "sublane" | "element-oob" | "alias-*"
    kernel: str
    operand: str
    detail: str

    def __str__(self) -> str:
        return (f"{self.severity.upper()} [{self.kind}] {self.kernel}"
                f" / {self.operand}: {self.detail}")


@dataclass
class TilingReport:
    issues: Tuple[TilingIssue, ...]
    kernels: int

    @property
    def errors(self) -> Tuple[TilingIssue, ...]:
        return tuple(i for i in self.issues if i.severity == "error")

    @property
    def warnings(self) -> Tuple[TilingIssue, ...]:
        return tuple(i for i in self.issues if i.severity == "warn")

    def raise_if_errors(self) -> None:
        if self.errors:
            lines = "\n  ".join(str(i) for i in self.errors)
            raise AssertionError(
                f"tiling contract violated ({len(self.errors)} "
                f"error(s)):\n  {lines}")


def _kernel_name(eqn) -> str:
    nsi = eqn.params.get("name_and_src_info")
    return str(getattr(nsi, "name", nsi or "pallas_call"))


def _grid_points(grid, max_grid_points):
    """Every launch-grid point when the grid is small, corners beyond —
    index maps in this repo are affine in each grid index, so corners
    bound the extrema; exhaustive evaluation below the cap keeps the
    check assumption-free where it is cheap."""
    sizes = [int(g) for g in grid]
    if not sizes:
        return [()]
    total = int(np.prod(sizes))
    if total <= max_grid_points:
        return list(itertools.product(*(range(s) for s in sizes)))
    return list(itertools.product(*(sorted({0, s - 1}) for s in sizes)))


def _eval_index_map(index_map_jaxpr, point):
    vals = jax.core.eval_jaxpr(index_map_jaxpr.jaxpr,
                               index_map_jaxpr.consts,
                               *[np.int32(p) for p in point])
    return tuple(int(v) for v in vals)


def _block_size(b) -> int:
    """Elements a block dim spans: a plain int, or the `block_size` of a
    `pl.Blocked` / `pl.Element` dim (squeezed dims span 1)."""
    if isinstance(b, (int, np.integer)):
        return int(b)
    return int(getattr(b, "block_size", 1) or 1)


def _block_dims(block_shape):
    """(dim, size) of every block dim that occupies a tile dimension
    (squeezed dims are pallas-internal sentinels — skipped)."""
    return [(d, _block_size(b)) for d, b in enumerate(block_shape)
            if isinstance(b, (int, np.integer))
            or type(b).__name__ in ("Blocked", "Element")]


def _is_element(b) -> bool:
    return type(b).__name__ == "Element"


def _has_element_dim(bm) -> bool:
    return any(_is_element(b) for b in getattr(bm, "block_shape", ()) or ())


def _check_mapping(bm, *, kernel, operand, grid, max_grid_points, issues,
                   sublane, lane):
    block = list(getattr(bm, "block_shape", ()) or ())
    dims = _block_dims(block)
    shape = tuple(_block_size(b) for b in block)
    arr = getattr(getattr(bm, "array_aval", None), "shape", None)
    # ---- (8, 128) contract: warn on misaligned trailing dims
    if dims:
        last_d, last_b = dims[-1]
        if last_b % lane:
            issues.append(TilingIssue(
                "warn", "lane", kernel, operand,
                f"block shape {shape} last dim {last_b} is not a "
                f"multiple of the {lane}-lane tile — every vregister is "
                f"padded (the hbm model's lane_eff penalty)"))
        if len(dims) >= 2:
            sub_d, sub_b = dims[-2]
            if sub_b % sublane:
                issues.append(TilingIssue(
                    "warn", "sublane", kernel, operand,
                    f"block shape {shape} dim {sub_d} ({sub_b} "
                    f"rows) is not a multiple of the {sublane}-sublane "
                    f"tile"))
    # ---- Element (element-offset) bounds vs operand extent
    if not _has_element_dim(bm) or arr is None:
        return
    if any(any(int(x) for x in getattr(b, "padding", (0, 0)))
           for b in block if _is_element(b)):
        return  # padded refs extend the addressable window by design
    imap = getattr(bm, "index_map_jaxpr", None)
    if imap is None:
        return
    try:
        starts_per_point = [(_eval_index_map(imap, pt), pt)
                            for pt in _grid_points(grid, max_grid_points)]
    except Exception as e:  # unevaluable map: surface, don't crash
        issues.append(TilingIssue(
            "warn", "index-map-uneval", kernel, operand,
            f"could not evaluate Element index map statically: {e!r}"))
        return
    for starts, pt in starts_per_point:
        # the index map returns an element offset for Element dims and a
        # block index for Blocked ones (scaled to elements here)
        for d, idx in enumerate(starts):
            if d >= len(arr) or d >= len(block):
                continue
            size = _block_size(block[d])
            start = idx if _is_element(block[d]) else idx * size
            extent = int(arr[d])
            if start < 0 or start + size > extent:
                issues.append(TilingIssue(
                    "error", "element-oob", kernel, operand,
                    f"grid point {pt}: block window "
                    f"[{start}, {start + size}) exceeds operand extent "
                    f"{extent} in dim {d} (operand shape {tuple(arr)})"))
                return  # one witness per operand is enough


def _lint_pallas_eqn(eqn, *, max_grid_points, sublane, lane, issues):
    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return
    kernel = _kernel_name(eqn)
    grid = tuple(getattr(gm, "grid", ()) or ())
    if any(not isinstance(g, (int, np.integer)) for g in grid):
        return  # dynamic grids cannot be swept statically
    mappings = list(getattr(gm, "block_mappings", ()) or ())
    n_in = int(getattr(gm, "num_inputs", len(mappings)))
    for i, bm in enumerate(mappings):
        operand = (f"in[{i}]" if i < n_in else f"out[{i - n_in}]")
        origin = getattr(bm, "origin", "")
        if origin:
            operand += f" ({origin})"
        _check_mapping(bm, kernel=kernel, operand=operand, grid=grid,
                       max_grid_points=max_grid_points, issues=issues,
                       sublane=sublane, lane=lane)
    # ---- in-place aliasing: operand/result windows must coincide
    aliases = eqn.params.get("input_output_aliases") or ()
    for in_idx, out_idx in aliases:
        if in_idx >= len(mappings) or n_in + out_idx >= len(mappings):
            continue
        bm_in, bm_out = mappings[in_idx], mappings[n_in + out_idx]
        shp_in = getattr(getattr(bm_in, "array_aval", None),
                         "shape", None)
        shp_out = getattr(getattr(bm_out, "array_aval", None),
                          "shape", None)
        pair = f"in[{in_idx}]<->out[{out_idx}]"
        if shp_in != shp_out:
            issues.append(TilingIssue(
                "error", "alias-shape", kernel, pair,
                f"aliased operand/result extents differ: {shp_in} vs "
                f"{shp_out} — the in-place update writes outside the "
                f"buffer it reads"))
            continue
        if _has_element_dim(bm_in) or _has_element_dim(bm_out):
            try:
                for pt in _grid_points(grid, max_grid_points):
                    si = _eval_index_map(bm_in.index_map_jaxpr, pt)
                    so = _eval_index_map(bm_out.index_map_jaxpr, pt)
                    if si != so:
                        issues.append(TilingIssue(
                            "error", "alias-window", kernel, pair,
                            f"grid point {pt}: aliased windows diverge "
                            f"(read at {si}, write at {so}) — the "
                            f"in-place write lands where the read did "
                            f"not come from"))
                        break
            except Exception as e:
                issues.append(TilingIssue(
                    "warn", "index-map-uneval", kernel, pair,
                    f"could not compare aliased index maps: {e!r}"))


def lint_tiling(fn, *args, sublane: int = SUBLANE, lane: int = LANE,
                max_grid_points: int = 4096) -> TilingReport:
    """Trace `fn(*args)` (never executing it) and lint every
    `pallas_call` — including those inside pjit / shard_map / loop
    bodies — against the tiling contract. Returns a `TilingReport`;
    `raise_if_errors()` is the gate."""
    closed = jax.make_jaxpr(fn)(*args)
    issues: list = []
    kernels = [0]

    def visit(eqn):
        if eqn.primitive.name == "pallas_call":
            kernels[0] += 1
            _lint_pallas_eqn(eqn, max_grid_points=max_grid_points,
                             sublane=sublane, lane=lane, issues=issues)

    walk_jaxpr(closed.jaxpr, visit)
    return TilingReport(issues=tuple(issues), kernels=kernels[0])
