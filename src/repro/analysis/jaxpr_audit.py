"""Jaxpr escape auditor: find contractions that bypass the Engine.

The Engine's whole perf story (roofline, cycle model, CI flop/byte gates)
is event-driven — a GEMM that does not dispatch through
:mod:`repro.core.engine` is invisible to all of it.  This module makes
that blindness checkable: trace an entry point to a closed jaxpr under
:func:`engine.instrument`, collect every ``dot_general`` equation
(recursing through ``pjit`` / ``scan`` / ``while`` / ``cond`` / ``remat``
/ ``custom_vjp`` sub-jaxprs, multiplying ``scan`` trip counts into the
static multiplicity), and reconcile the multiset against the
``GemmEvent`` stream from the very same trace.

Reconciliation is by **dense flops**: every non-pass engine dispatch on
the XLA backend lowers to exactly one ``dot_general`` costing
``GemmSpec.dense_flops`` (ragged grouped GEMMs bill ``valid_rows`` in
:attr:`GemmSpec.flops` but the lowered dot is dense, hence the separate
hook), with trace multiplicity ``GemmEvent.count``.  Equations left over
after subtracting the engine footprint are *escaped GEMMs* — reported
with operand shapes, dtypes, and the contraction's dimension numbers.

The audit must run with the XLA backend (the default off-TPU): a
``pallas_call`` hides its in-kernel dots from the outer jaxpr, so the
event↔equation bijection only holds for ``xla``.  :func:`trace_entry`
forces it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Iterable, List, Sequence, Tuple

import jax
from jax.extend import core as jcore

from repro.core import engine


@dataclasses.dataclass(frozen=True)
class DotSite:
    """One ``dot_general`` equation observed in a walked jaxpr.

    ``count`` is the static trace multiplicity (product of enclosing
    ``scan`` lengths); ``unbounded`` marks sites under a ``while`` loop,
    whose trip count is not static — they reconcile at multiplicity 1 and
    are flagged in the report.  ``path`` names the enclosing call
    primitives, outermost first (e.g. ``('pjit', 'scan')``).
    """

    lhs_shape: Tuple[int, ...]
    rhs_shape: Tuple[int, ...]
    lhs_dtype: str
    rhs_dtype: str
    out_dtype: str
    dimension_numbers: Tuple[Tuple[Tuple[int, ...], Tuple[int, ...]],
                             Tuple[Tuple[int, ...], Tuple[int, ...]]]
    flops: int
    count: int
    path: Tuple[str, ...]
    unbounded: bool = False

    @property
    def fingerprint(self) -> str:
        """Stable identity for manifest matching: shapes, dtypes, and
        dimension numbers — everything but multiplicity and path."""
        (lc, rc), (lb, rb) = self.dimension_numbers
        return (f"{self.lhs_dtype}{list(self.lhs_shape)}·"
                f"{self.rhs_dtype}{list(self.rhs_shape)}->{self.out_dtype}"
                f" C{list(lc)};{list(rc)} B{list(lb)};{list(rb)}")

    def describe(self) -> str:
        where = "/".join(self.path) or "<top>"
        extra = " (inside while: trip count unknown)" if self.unbounded else ""
        return (f"{self.fingerprint} x{self.count} "
                f"[{self.flops} flops each, at {where}]{extra}")


def _dot_flops(lhs_shape, rhs_shape, dimension_numbers) -> int:
    (lc, rc), (lb, rb) = dimension_numbers
    b = math.prod(lhs_shape[i] for i in lb)
    k = math.prod(lhs_shape[i] for i in lc)
    m = math.prod(d for i, d in enumerate(lhs_shape) if i not in lb + lc)
    n = math.prod(d for i, d in enumerate(rhs_shape) if i not in rb + rc)
    return 2 * b * m * n * k


def _param_jaxprs(params: Dict[str, Any]) -> Iterable[jcore.Jaxpr]:
    """Yield every (sub-)jaxpr referenced by an equation's params —
    covers pjit (``jaxpr``), scan/while/cond (``jaxpr`` /
    ``cond_jaxpr``/``body_jaxpr`` / ``branches``), remat, custom_vjp/jvp
    call jaxprs, and any future call-like primitive, without naming them
    one by one."""
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for item in vs:
            if isinstance(item, jcore.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jcore.Jaxpr):
                yield item


def _iter_eqns(jaxpr: jcore.Jaxpr, mult: int, path: Tuple[str, ...],
               unbounded: bool):
    for eqn in jaxpr.eqns:
        yield eqn, mult, path, unbounded
        name = eqn.primitive.name
        sub_mult, sub_unb = mult, unbounded
        if name == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        elif name == "while":
            sub_unb = True
        for sub in _param_jaxprs(eqn.params):
            yield from _iter_eqns(sub, sub_mult, path + (name,), sub_unb)


def iter_eqns(closed: jcore.ClosedJaxpr):
    """Yield ``(eqn, multiplicity, path, unbounded)`` for every equation
    in a closed jaxpr, recursing through call-like primitives —
    multiplicity is the product of enclosing ``scan`` lengths, and
    ``unbounded`` marks equations under a ``while`` loop (also used by
    :mod:`repro.analysis.dtype_audit`)."""
    yield from _iter_eqns(closed.jaxpr, 1, (), False)


def _dot_site(eqn, mult: int, path: Tuple[str, ...],
              unbounded: bool) -> DotSite:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dnums = eqn.params["dimension_numbers"]
    return DotSite(
        lhs_shape=tuple(lhs.shape), rhs_shape=tuple(rhs.shape),
        lhs_dtype=str(lhs.dtype), rhs_dtype=str(rhs.dtype),
        out_dtype=str(eqn.outvars[0].aval.dtype),
        dimension_numbers=tuple((tuple(a), tuple(b)) for a, b in dnums),
        flops=_dot_flops(lhs.shape, rhs.shape, dnums),
        count=mult, path=path, unbounded=unbounded)


def collect_dots(closed: jcore.ClosedJaxpr) -> List[DotSite]:
    """All ``dot_general`` sites in a closed jaxpr, recursively, with
    fingerprint-identical sites merged (counts summed)."""
    raw = [_dot_site(eqn, mult, path, unb)
           for eqn, mult, path, unb in iter_eqns(closed)
           if eqn.primitive.name == "dot_general"]
    merged: Dict[Tuple[str, bool], DotSite] = {}
    for site in raw:
        key = (site.fingerprint, site.unbounded)
        if key in merged:
            prev = merged[key]
            merged[key] = dataclasses.replace(
                prev, count=prev.count + site.count)
        else:
            merged[key] = site
    return sorted(merged.values(),
                  key=lambda s: (-s.flops, s.fingerprint))


@dataclasses.dataclass(frozen=True)
class AuditResult:
    """Outcome of one entry-point reconciliation."""

    entry: str
    escapes: Tuple[DotSite, ...]       # jaxpr dots no event accounts for
    unmatched_events: Dict[int, int]   # dense_flops -> dispatch surplus
    n_dots: int                        # distinct dot sites walked
    n_events: int                      # engine events observed

    @property
    def clean(self) -> bool:
        return not self.escapes

    def to_json(self) -> Dict[str, Any]:
        return {
            "entry": self.entry,
            "n_dot_sites": self.n_dots,
            "n_engine_events": self.n_events,
            "escapes": [{
                "fingerprint": s.fingerprint,
                "flops": s.flops,
                "count": s.count,
                "path": list(s.path),
                "unbounded": s.unbounded,
            } for s in self.escapes],
            "unmatched_engine_dispatches": {
                str(f): n for f, n in sorted(self.unmatched_events.items())},
        }


def trace_entry(name: str, fn: Callable, args: Sequence[Any],
                ) -> Tuple[jcore.ClosedJaxpr, List[engine.GemmEvent]]:
    """Trace ``fn(*args)`` once, capturing the jaxpr and the engine events
    of the same trace, on the XLA backend (see module docstring)."""
    with engine.use_backend("xla"), engine.instrument() as events:
        closed = jax.make_jaxpr(fn)(*args)
    return closed, list(events)


def reconcile(entry: str, sites: Sequence[DotSite],
              events: Sequence[engine.GemmEvent]) -> AuditResult:
    """Subtract the engine dispatch footprint from the walked dot sites.

    Matching is greedy by dense flops: distinct GEMMs with identical
    dense flops are fungible (a swap would be flop-neutral by
    construction).  Sites under ``while`` match at multiplicity 1."""
    foot = engine.dispatch_footprint(events)
    escapes: List[DotSite] = []
    for site in sites:
        if site.flops <= 0:
            continue   # degenerate empty-dim contraction: no MACs to bill
        avail = foot.get(site.flops, 0)
        take = min(avail, site.count)
        foot[site.flops] = avail - take
        if take < site.count:
            escapes.append(dataclasses.replace(site, count=site.count - take))
    unmatched = {f: n for f, n in foot.items() if n > 0}
    return AuditResult(entry=entry, escapes=tuple(escapes),
                       unmatched_events=unmatched,
                       n_dots=len(sites), n_events=len(events))


def audit(entry: str, fn: Callable, args: Sequence[Any]) -> AuditResult:
    """Trace + walk + reconcile in one call (the test-facing surface)."""
    closed, events = trace_entry(entry, fn, args)
    return reconcile(entry, collect_dots(closed), events)
