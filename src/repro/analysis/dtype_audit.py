"""Precision-policy conformance over traced jaxprs.

The mixed-precision extension of the paper (arXiv:2301.03904) makes the
repro's precision story per-operand: storage dtypes (FP8/FP16) widen to
the policy's ``compute_dtype`` on load and accumulate in ``accum_dtype``
— never beyond.  Three things can silently violate that contract, and
all three are visible statically in a traced jaxpr:

* **fp64 anywhere** — nothing in the repo declares a float64 policy;
  any f64 value is an accidental promotion (a Python float leaking into
  a weak-typed op, a NumPy default) that doubles bytes on the affected
  path.
* **fp32 materialization off the accumulation path** — a ``dot_general``
  producing f32 is only conformant when some Engine policy observed in
  the same trace declares f32 as a compute/accum/output dtype (the
  router and attention-score policies do).  An f32 contraction with no
  such declaration is an escaped-precision GEMM.
* **FP8 operands reaching a non-capable backend** — an fp8-operand
  ``dot_general`` in the jaxpr means *someone* contracted raw FP8
  storage.  The Engine never does this on XLA (it widens to compute
  dtype around the dot; only backends declaring the
  ``"operand_dtypes"`` capability consume FP8 directly, inside their
  kernels where no outer ``dot_general`` exists).  Every such equation
  is therefore a conformance finding.

Findings carry the equation's primitive, dtypes, and call path; the
audit CLI (:mod:`repro.analysis.audit`) folds them into the
``static-gates`` report.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Set, Tuple

import jax.numpy as jnp
from jax.extend import core as jcore

from repro.analysis import jaxpr_audit
from repro.core import engine
from repro.core import precision as prec

_F64 = ("float64", "complex128")
_FP8 = ("float8_e4m3fn", "float8_e5m2")


@dataclasses.dataclass(frozen=True)
class DtypeFinding:
    kind: str        # "fp64" | "fp32_materialization" | "fp8_uncovered"
    detail: str
    path: Tuple[str, ...]
    count: int = 1

    def describe(self) -> str:
        where = "/".join(self.path) or "<top>"
        return f"[{self.kind}] {self.detail} x{self.count} (at {where})"


def declared_dtypes(events: Sequence[engine.GemmEvent]) -> Set[str]:
    """Every dtype some Engine policy in the event stream declares —
    compute, accumulator, output, and per-operand storage slots."""
    out: Set[str] = set()
    for ev in events:
        p = ev.spec.policy
        out.update(str(jnp.dtype(d)) for d in (
            p.compute_dtype, p.accum_dtype, p.out_dtype,
            p.x_storage_dtype, p.w_storage_dtype, p.grad_storage_dtype))
    return out


def audit_dtypes(closed: jcore.ClosedJaxpr,
                 events: Sequence[engine.GemmEvent],
                 extra_allowed: Sequence[str] = (),
                 ) -> List[DtypeFinding]:
    """Run all three conformance checks over one traced jaxpr.

    ``extra_allowed`` admits additional f32-materialization dtypes for
    entry points with no engine events (pure-escape toy traces in
    tests)."""
    allowed = declared_dtypes(events) | set(extra_allowed)
    merged: Dict[Tuple[str, str, Tuple[str, ...]], int] = {}

    def add(kind: str, detail: str, path: Tuple[str, ...], count: int):
        key = (kind, detail, path)
        merged[key] = merged.get(key, 0) + count

    for eqn, mult, path, _unb in jaxpr_audit.iter_eqns(closed):
        name = eqn.primitive.name
        for ov in eqn.outvars:
            aval = getattr(ov, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in _F64:
                add("fp64", f"{name} -> {dt}", path, mult)
        if name != "dot_general":
            continue
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        out_dt = str(eqn.outvars[0].aval.dtype)
        ldt, rdt = str(lhs.dtype), str(rhs.dtype)
        if ldt in _FP8 or rdt in _FP8:
            add("fp8_uncovered",
                f"dot_general {ldt}{list(lhs.shape)} x "
                f"{rdt}{list(rhs.shape)} contracts raw FP8 storage — only "
                f"backends declaring 'operand_dtypes' may consume FP8 "
                f"operands (and they do it in-kernel, not via dot_general)",
                path, mult)
        elif out_dt == "float32" and "float32" not in allowed:
            add("fp32_materialization",
                f"dot_general {ldt}{list(lhs.shape)} x "
                f"{rdt}{list(rhs.shape)} -> float32, but no Engine policy "
                f"in this trace declares an f32 compute/accum/output slot",
                path, mult)

    return sorted(
        (DtypeFinding(kind=k, detail=d, path=p, count=n)
         for (k, d, p), n in merged.items()),
        key=lambda f: (f.kind, f.detail))


def check_shipped_policies() -> List[str]:
    """Static invariants of every policy shipped in
    :mod:`repro.core.precision` — no trace required.  Returns a list of
    violation strings (empty = conformant)."""
    problems: List[str] = []
    for name in prec.known_policies():
        p = prec.resolve(name)
        for field in ("compute_dtype", "accum_dtype", "out_dtype",
                      "x_storage_dtype", "w_storage_dtype",
                      "grad_storage_dtype"):
            dt = jnp.dtype(getattr(p, field))
            if str(dt) in _F64:
                problems.append(f"policy {name!r}: {field} is {dt}")
        if (jnp.dtype(p.accum_dtype).itemsize
                < jnp.dtype(p.compute_dtype).itemsize):
            problems.append(
                f"policy {name!r}: accumulator {jnp.dtype(p.accum_dtype)} "
                f"narrower than compute {jnp.dtype(p.compute_dtype)}")
        if p.scaled:
            # FP8 storage needs an upcast-capable backend to exist
            capable = [b for b in engine.registered_backends()
                       if engine.backend_supports(b, "operand_dtypes")]
            if not capable:
                problems.append(
                    f"policy {name!r} declares FP8 storage but no "
                    f"registered backend supports 'operand_dtypes'")
    return problems
