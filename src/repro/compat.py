"""One home for the jax spellings that drifted across releases.

Policy (DESIGN.md §10): repro code reaches the drift-prone jax APIs below only
through this module, so a version bump is a one-file change and the rest of
the tree stays on a stable surface.

Targets the installed jax 0.9.x:

* ``tree_flatten_with_path``   — ``jax.tree.flatten_with_path``.
* ``axis_size``                — ``lax.axis_size``, extended to a tuple of
  axes (the product of their sizes).
* ``cost_analysis_dict``       — ``Compiled.cost_analysis()`` normalized to
  one flat dict (it has returned a dict, a list of dicts or ``None``).
* ``shard_map``                — ``jax.shard_map``; the tree keeps the older
  ``check_rep`` keyword, passed on as ``check_vma``.
* ``with_sharding_constraint`` — ``lax.with_sharding_constraint``.

The ``repro-lint`` compat-boundary rule enforces this policy mechanically:
any use of the raw spellings above outside this file is a finding.
"""
from __future__ import annotations

from typing import Any, Mapping, Sequence, Union

import jax
from jax import lax

__all__ = ["tree_flatten_with_path", "axis_size", "cost_analysis_dict",
           "shard_map", "with_sharding_constraint"]

AxisName = Union[str, Sequence[str]]


def tree_flatten_with_path(tree: Any):
    """(path, leaf) pairs + treedef."""
    return jax.tree.flatten_with_path(tree)


def axis_size(axis_name: AxisName) -> int:
    """Size of one mesh axis, or the product over a tuple of axes."""
    if isinstance(axis_name, (tuple, list)):
        s = 1
        for a in axis_name:
            s *= lax.axis_size(a)
        return s
    return lax.axis_size(axis_name)


def shard_map(f: Any, *, mesh: Any, in_specs: Any, out_specs: Any,
              check_rep: bool = False, **kwargs: Any):
    """``jax.shard_map`` with ``check_rep`` spelled as ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_rep, **kwargs)


def with_sharding_constraint(x: Any, shardings: Any):
    """``lax.with_sharding_constraint``."""
    return lax.with_sharding_constraint(x, shardings)


def cost_analysis_dict(compiled: Any) -> Mapping[str, float]:
    """Normalize ``Compiled.cost_analysis()`` to a single flat dict."""
    cost = compiled.cost_analysis()
    if cost is None:
        return {}
    if isinstance(cost, (list, tuple)):
        merged: dict[str, float] = {}
        for entry in cost:
            if isinstance(entry, Mapping):
                merged.update(entry)
        return merged
    return cost
