"""Shared jaxpr traversal for the static-analysis passes.

Everything in `repro.analysis` works on the TRACED program — the jaxpr
`jax.make_jaxpr` returns — never on a running computation. The walkers
here are the substrate: `iter_jaxprs` flattens any sub-jaxpr an equation
carries in its params (pjit / shard_map / scan / while / pallas_call /
custom-derivative bodies all stash their bodies differently),
`walk_jaxpr` applies a visitor to every equation recursively, and
`structural_fingerprint` hashes the trace STRUCTURE so the retrace
detector can tell "jit would reuse this trace" from "a static Python
value leaked in and forced a new one".

`iter_jaxprs` is the single source of truth moved out of
`stencil/distributed.py` (which re-exports it as `_iter_jaxprs` for
backward compatibility): the four legacy `count_*` byte counters and all
four analysis passes recurse through exactly the same param traversal,
so a control-flow primitive none of them knew about fails everywhere at
once instead of silently in one counter.
"""
from __future__ import annotations

import hashlib
import re

import jax
import jax.extend.core as jax_core
import numpy as np

__all__ = [
    "iter_jaxprs", "walk_jaxpr", "aval_bytes", "fingerprint_parts",
    "structural_fingerprint",
]


def iter_jaxprs(val):
    """Yield every `Jaxpr` reachable from an eqn param value: a
    ClosedJaxpr, a bare Jaxpr, or any list/tuple nesting of them.
    (Dict-valued params carry no jaxprs; mirroring the legacy counters,
    they are not descended into.)"""
    if isinstance(val, jax_core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jax_core.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from iter_jaxprs(v)


def walk_jaxpr(jaxpr, visit) -> None:
    """Depth-first visitor over every equation of `jaxpr` and of every
    sub-jaxpr carried in equation params. `visit(eqn)` runs on the
    equation BEFORE its children — the traversal order the legacy
    `count_*` walkers used, preserved so the refactor is byte-identical.
    """
    for eqn in jaxpr.eqns:
        visit(eqn)
        for pval in eqn.params.values():
            for sub in iter_jaxprs(pval):
                walk_jaxpr(sub, visit)


def aval_bytes(aval) -> int:
    """Size in bytes of an abstract value (0 for shapeless avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape)) * np.dtype(dtype).itemsize


def _aval_str(aval) -> str:
    return f"{getattr(aval, 'shape', '?')}:{getattr(aval, 'dtype', '?')}"


def _var_str(var) -> str:
    # Literal VALUES are abstracted away ("lit" + aval): arguments jit
    # receives at call time key the trace cache by aval only, so two
    # traces differing in nothing but literal operand values are
    # cache-compatible when those values arrive as arguments. Static
    # leaks of the PR 5 class resolve at trace time into eqn params or
    # structure (slice starts, unrolled bodies) and stay visible.
    if isinstance(var, jax_core.Literal):
        return "lit" + _aval_str(var.aval)
    return _aval_str(var.aval)


# reprs of params may embed object addresses (wrapped functions, trace
# debug info); scrub them so the fingerprint depends on structure only.
_ADDR = re.compile(r"0x[0-9a-fA-F]+")


def fingerprint_parts(jaxpr, _depth: int = 0) -> list:
    """One line per equation (recursing into sub-jaxprs) capturing the
    primitive, operand/result avals, literal layout and param values —
    the retrace detector diffs two of these lists to NAME the first
    structurally diverging equation."""
    pad = "  " * _depth
    parts = []
    for eqn in jaxpr.eqns:
        sub_parts = []
        param_bits = []
        for key in sorted(eqn.params):
            pval = eqn.params[key]
            subs = list(iter_jaxprs(pval))
            if subs:
                param_bits.append(f"{key}=<jaxpr>")
                for s in subs:
                    sub_parts.extend(fingerprint_parts(s, _depth + 1))
            else:
                param_bits.append(f"{key}={_ADDR.sub('0x', repr(pval))}")
        parts.append(pad + "|".join((
            eqn.primitive.name,
            ",".join(_var_str(v) for v in eqn.invars),
            ",".join(_aval_str(v.aval) for v in eqn.outvars),
            ";".join(param_bits))))
        parts.extend(sub_parts)
    return parts


def structural_fingerprint(traced) -> str:
    """Hex digest of the trace structure of `traced` (a ClosedJaxpr or
    Jaxpr). Two drivers with equal fingerprints lower to the same
    program modulo argument values; unequal fingerprints mean a config
    knob changed the TRACE itself — either legitimately (shapes, depth)
    or because a static Python value leaked in (the retrace detector's
    quarry)."""
    jaxpr = traced.jaxpr if isinstance(traced, jax_core.ClosedJaxpr) else traced
    digest = hashlib.sha256(
        "\n".join(fingerprint_parts(jaxpr)).encode()).hexdigest()
    return digest[:16]
