"""The pass registry: the four analysis passes behind one discoverable
surface.

Each pass is a callable registered under a stable name with a one-line
summary — `available()` is what `scripts/lint_movement.py --list` and
`docs/static-analysis.md` enumerate, and adding a pass is one
`@register_pass` away (the doc's "how to add a pass" recipe). The
registry deliberately does NOT normalise signatures: the passes take
what their problem needs (a traced fn, a driver factory, a static
config) and the registry's job is discovery and documentation, not
dispatch gymnastics.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Tuple

from repro.analysis.ledger import (MovementLedger, check_model_coverage)
from repro.analysis.retrace import detect_retrace
from repro.analysis.tiling import lint_tiling
from repro.analysis.vmem import VmemPlan

__all__ = ["AnalysisPass", "PASSES", "register_pass", "available",
           "get_pass"]


@dataclass(frozen=True)
class AnalysisPass:
    name: str
    summary: str
    run: Callable


PASSES: Dict[str, AnalysisPass] = {}


def register_pass(name: str, summary: str):
    """Register `fn` as the analysis pass `name`. Names are unique —
    re-registering is a bug, not an override."""
    def deco(fn):
        if name in PASSES:
            raise ValueError(f"analysis pass {name!r} already registered")
        PASSES[name] = AnalysisPass(name=name, summary=summary, run=fn)
        return fn
    return deco


def available() -> Tuple[Tuple[str, str], ...]:
    """(name, summary) of every registered pass, registration order."""
    return tuple((p.name, p.summary) for p in PASSES.values())


def get_pass(name: str) -> AnalysisPass:
    if name not in PASSES:
        known = ", ".join(PASSES)
        raise KeyError(f"no analysis pass {name!r}; registered: {known}")
    return PASSES[name]


# ---- the four shipped passes -------------------------------------------

@register_pass(
    "movement-ledger",
    "attribute every byte a traced program moves to a category "
    "(wire / HBM / integrity / guard / collective / host)")
def movement_ledger_pass(fn, *args) -> MovementLedger:
    return MovementLedger.of(fn, *args)


@register_pass(
    "model-coverage",
    "fail when the ledger holds bytes no analytic model term claims "
    "(or a claim the count contradicts)")
def model_coverage_pass(fn, *args, claims, unpriced=("pallas_control",)):
    return check_model_coverage(MovementLedger.of(fn, *args), claims,
                                unpriced=unpriced)


@register_pass(
    "retrace",
    "flag config knobs whose static Python values leak into the trace "
    "(the PR 5 dma_block_index bug class)")
def retrace_pass(factory, perturbations):
    return detect_retrace(factory, perturbations)


@register_pass(
    "vmem-budget",
    "statically sum named on-chip buffers against VMEM_PER_CORE and "
    "refuse over-budget configs before compile")
def vmem_budget_pass(plan: VmemPlan) -> VmemPlan:
    return plan.check()


@register_pass(
    "tiling-contract",
    "lint every pallas_call's block shapes against the (8, 128) tile, "
    "Element bounds and in-place aliasing windows")
def tiling_contract_pass(fn, *args, **kw):
    return lint_tiling(fn, *args, **kw)
