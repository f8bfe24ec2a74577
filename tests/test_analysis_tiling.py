"""Tiling-contract linter: every Pallas block mapping checked statically
against the (8, 128) tile, Element bounds, and in-place alias windows.

All fast tier (1-device): the repo's own kernels lint error-free (the
lane/sublane warnings on deliberately-tiny interpret grids are warnings,
not errors); a fabricated Element kernel whose index map walks past
the operand extent is flagged "element-oob" with the offending grid
point and dim; aliased in-place windows that diverge are flagged
"alias-window"; a lane-aligned kernel produces no lane warnings. The
linter only TRACES (`jax.make_jaxpr`) — the broken fixtures never run.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import SUBLANE, LANE, lint_tiling
from repro.kernels.advection.advection import advect_fused
from repro.kernels.advection.ref import default_params

X, Y, Z = 4, 16, 128


def _copy_kernel(src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def _element_copy(x, *, n, stride, block, base=0):
    """`n` grid steps, each copying a `block` window read at Element
    row offset ``base + g * stride``. A stride (or base) walking
    past the operand extent fabricates the OOB the linter must catch;
    the program is traced, never run."""
    rows, lanes = block
    spec = pl.BlockSpec((pl.Element(rows), lanes),
                        lambda g: (base + g * stride, 0))
    out_spec = pl.BlockSpec((pl.Element(rows), lanes), lambda g: (0, 0))
    return pl.pallas_call(
        _copy_kernel, grid=(n,),
        in_specs=[spec], out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(block, x.dtype),
        interpret=True)(x)


def test_repo_fused_kernel_is_error_free():
    p = default_params(Z)
    f = jnp.zeros((X, Y, Z), jnp.float32)
    report = lint_tiling(
        lambda u, v, w: advect_fused(u, v, w, p, T=2, interpret=True,
                                     y_tile=8), f, f, f)
    assert report.kernels >= 1
    assert not report.errors
    report.raise_if_errors()            # no-op when green


def test_lane_aligned_kernel_has_no_lane_warnings():
    x = jnp.zeros((64, LANE), jnp.float32)
    report = lint_tiling(
        lambda a: _element_copy(a, n=2, stride=SUBLANE,
                                  block=(SUBLANE, LANE)), x)
    assert not report.errors
    assert not [w for w in report.warnings
                if w.kind in ("lane", "sublane")]


def test_misaligned_block_warns_not_errors():
    x = jnp.zeros((64, LANE), jnp.float32)
    report = lint_tiling(
        lambda a: _element_copy(a, n=1, stride=0, block=(3, 100)), x)
    assert not report.errors
    kinds = {w.kind for w in report.warnings}
    assert "lane" in kinds and "sublane" in kinds


def test_unblocked_oob_is_an_error():
    x = jnp.zeros((64, LANE), jnp.float32)
    # grid point 1 reads rows [60, 68) of a 64-row operand
    report = lint_tiling(
        lambda a: _element_copy(a, n=2, stride=60,
                                  block=(SUBLANE, LANE)), x)
    errs = [e for e in report.errors if e.kind == "element-oob"]
    assert errs, report.issues
    assert "extent 64" in errs[0].detail and "(1,)" in errs[0].detail
    with pytest.raises(AssertionError, match="element-oob"):
        report.raise_if_errors()
    # a negative element offset is equally out of bounds
    neg = lint_tiling(
        lambda a: _element_copy(a, n=1, stride=0, base=-8,
                                  block=(SUBLANE, LANE)), x)
    assert any(e.kind == "element-oob" for e in neg.errors)


def _aliased_shift(x, *, shift):
    """In-place update whose write window is `shift` rows away from its
    read window — `shift != 0` fabricates the alias-window violation."""
    n = x.shape[0] // SUBLANE

    def kernel(src_ref, dst_ref):
        dst_ref[...] = src_ref[...] * 2.0

    return pl.pallas_call(
        kernel, grid=(n,),
        in_specs=[pl.BlockSpec((pl.Element(SUBLANE), LANE),
                               lambda g: (g * SUBLANE, 0))],
        out_specs=pl.BlockSpec((pl.Element(SUBLANE), LANE),
                               functools.partial(
                                   lambda g, s: (g * SUBLANE + s, 0),
                                   s=shift)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0},
        interpret=True)(x)


def test_alias_window_divergence_is_an_error():
    x = jnp.zeros((64, LANE), jnp.float32)
    clean = lint_tiling(lambda a: _aliased_shift(a, shift=0), x)
    assert not clean.errors
    bad = lint_tiling(lambda a: _aliased_shift(a, shift=SUBLANE), x)
    errs = [e for e in bad.errors if e.kind == "alias-window"]
    assert errs, bad.issues
    assert "in[0]<->out[0]" in errs[0].operand


def test_grid_cap_falls_back_to_corners():
    # a grid bigger than max_grid_points still catches a corner OOB:
    # only the LAST grid point (g=39, rows [78, 86)) exceeds 64 rows
    x = jnp.zeros((64, LANE), jnp.float32)
    report = lint_tiling(
        lambda a: _element_copy(a, n=40, stride=2,
                                  block=(SUBLANE, LANE)),
        x, max_grid_points=4)
    assert any(e.kind == "element-oob" for e in report.errors)
