"""One source of truth for decode-cache sharding specs.

Both ``launch/serve.py`` (``cache_spec_tree``) and ``launch/dryrun.py``'s
decode cells route through :func:`decode_cache_specs`, so the cache's
abstract shapes and PartitionSpecs cannot drift between the two drivers.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.models import transformer
from repro.runtime import sharding

__all__ = ["decode_cache_specs"]


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(a, (str, type(None))) for a in x)


def decode_cache_specs(cfg, rules, mesh, batch: int, max_len: int, *,
                       dtype=None,
                       storage_dtype: Optional[str] = None) -> Tuple:
    """(abstract cache tree, sanitized PartitionSpec tree) for decode.

    ``storage_dtype`` grows the FP8 serving cache's scale leaves
    in both trees (mirror of ``transformer.init_cache``).
    """
    axes = transformer.cache_axes(cfg, storage_dtype)
    abstract = jax.eval_shape(lambda: transformer.init_cache(
        cfg, batch, max_len, dtype=dtype, storage_dtype=storage_dtype))
    spec = jax.tree.map(
        lambda ax: sharding.logical_spec(ax, rules), axes, is_leaf=_is_axes)
    spec = jax.tree.map(
        lambda s, a: sharding.sanitize_spec(s, a.shape, mesh),
        spec, abstract, is_leaf=lambda x: isinstance(x, P))
    return abstract, spec
