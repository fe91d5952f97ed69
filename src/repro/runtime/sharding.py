"""Logical-axis sharding rules (DP / TP / EP / SP / FSDP).

Model code annotates parameters and activations with *logical* axis names
("batch", "embed", "heads", "experts", ...).  This module maps them onto the
physical mesh axes ``("pod", "data", "model")`` built by ``launch/mesh.py``:

* DP   — "batch" over ``("pod", "data")``;
* TP   — "heads"/"ff"/"vocab" over ``"model"`` (Megatron column/row pairs
         around every RedMulE GEMM);
* EP   — "experts" over ``"model"``;
* SP   — "seq_sharded" over ``"model"`` (sequence parallelism for the
         norm/residual segments between TP blocks — enabled per-config);
* FSDP — "embed" additionally over ``("pod", "data")`` (ZeRO-3 style) when
         ``fsdp=True`` (a hillclimb option, off in the paper-faithful
         baseline).

Rules are carried in a thread-local context so model code stays functional:
``with use_rules(Rules(...)): ...``; outside any context, annotations are
no-ops (single-device tests).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

__all__ = [
    "Rules",
    "use_rules",
    "current_rules",
    "logical_spec",
    "constrain",
    "DATA_AXES",
    "MODEL_AXIS",
]

DATA_AXES: Tuple[str, ...] = ("pod", "data")
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class Rules:
    """Logical-axis -> mesh-axes mapping."""

    fsdp: bool = False
    sequence_parallel: bool = False
    # decode-time: pin attention dots to the sequence-sharded KV layout
    serve_attention: bool = False
    # overrides win over the built-in table (hillclimb hook)
    overrides: Tuple[Tuple[str, Optional[Tuple[str, ...]]], ...] = ()

    def table(self) -> Dict[str, Optional[Tuple[str, ...]]]:
        t: Dict[str, Optional[Tuple[str, ...]]] = {
            "batch": DATA_AXES,
            "seq": None,
            "seq_sharded": (MODEL_AXIS,) if self.sequence_parallel else None,
            "embed": DATA_AXES if self.fsdp else None,
            "embed_unsharded": None,
            "vocab": (MODEL_AXIS,),
            "heads": (MODEL_AXIS,),
            "kv_heads": (MODEL_AXIS,),
            "head_dim": None,
            "ff": (MODEL_AXIS,),
            "experts": (MODEL_AXIS,),
            "expert_ff": None,
            "kv_rank": None,
            # decode-time KV cache sequence dim; serve rules override to
            # ("model",) so 32k-500k caches shard over TP (KV heads are
            # almost always < 16 and replicate)
            "kv_seq": None,
            "state": None,
            "layers": None,
            "ae_hidden": None,
            None: None,
        }
        t.update(dict(self.overrides))
        return t


_state = threading.local()


def current_rules() -> Optional[Rules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    old = current_rules()
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = old


def logical_spec(axes: Tuple[Optional[str], ...], rules: Optional[Rules] = None) -> P:
    """Translate logical axis names to a PartitionSpec under the rules."""
    rules = rules if rules is not None else current_rules()
    if rules is None:
        return P()
    table = rules.table()
    parts = []
    used: set = set()
    for a in axes:
        mesh_axes = table.get(a)
        if mesh_axes is None:
            parts.append(None)
            continue
        free = tuple(m for m in mesh_axes if m not in used)
        used.update(free)
        parts.append(free if len(free) != 1 else free[0])
        if not free:
            parts[-1] = None
    return P(*parts)


def _filter_known(part, mesh):
    """Drop mesh-axis names the mesh doesn't have (e.g. 'pod' on single-pod)."""
    if part is None:
        return None
    if isinstance(part, tuple):
        kept = tuple(n for n in part if n in mesh.shape)
        if not kept:
            return None
        return kept[0] if len(kept) == 1 else kept
    return part if part in mesh.shape else None


def _axis_size(mesh, name) -> int:
    if isinstance(name, tuple):
        s = 1
        for n in name:
            s *= mesh.shape[n]
        return s
    return mesh.shape[name]


def sanitize_spec(spec: P, shape: Tuple[int, ...], mesh) -> P:
    """Drop mesh axes the mesh doesn't define, and spec entries that don't
    divide the dimension (e.g. 5 KV heads on a 16-way model axis fall back
    to replication, the Megatron rule)."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, part in zip(shape, parts):
        part = _filter_known(part, mesh)
        if part is None:
            out.append(None)
        elif dim % _axis_size(mesh, part) == 0:
            out.append(part)
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def constrain_fb(x: jax.Array, fwd_axes: Tuple[Optional[str], ...],
                 bwd_axes: Optional[Tuple[Optional[str], ...]] = None) -> jax.Array:
    """Constrain the value (fwd_axes) AND its cotangent (bwd_axes).

    GSPMD re-propagates shardings through the transposed (backward)
    scatter/gathers of remat'd regions and can pick cross-shard layouts
    (observed: the MoE dispatch-gather's transpose all-reducing full fp32
    slot tensors).  At a *layout-change* point the two directions need
    different pins: e.g. the MoE dispatch buffer is expert-sharded going
    forward but its cotangent must be batch-local going backward."""
    bwd_axes = bwd_axes if bwd_axes is not None else fwd_axes

    @jax.custom_vjp
    def _ident(v):
        return constrain(v, *fwd_axes)

    def _fwd(v):
        return constrain(v, *fwd_axes), None

    def _bwd(_, g):
        return (constrain(g, *bwd_axes),)

    _ident.defvjp(_fwd, _bwd)
    return _ident(x)


def constrain_both(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain the value and its cotangent to the same layout."""
    return constrain_fb(x, axes)


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint under the current rules.

    No-op outside a rules context or outside a mesh; mesh-axis entries that
    don't divide the corresponding dimension are dropped (replicated)."""
    rules = current_rules()
    if rules is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    spec = sanitize_spec(logical_spec(axes, rules), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)
