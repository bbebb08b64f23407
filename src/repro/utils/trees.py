"""Pytree / state-dict utilities shared across the framework.

The FL message layer works on *state dicts* — flat ``{name: array}``
mappings, the JAX analogue of a torch ``state_dict`` and the unit of
transmission in the paper (one dict item == one "layer" for container
streaming). Models internally use nested pytrees; these helpers convert
between the two and provide byte/param accounting used by the Table II/III
benchmarks.
"""
from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import jax
import numpy as np

SEP = "."


def tree_bytes(tree: Any) -> int:
    """Total payload bytes of every leaf array in ``tree``."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def tree_param_count(tree: Any) -> int:
    return sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree) if hasattr(l, "shape")
    )


def flatten_state_dict(tree: Any, prefix: str = "") -> dict[str, Any]:
    """Flatten a nested dict/pytree of arrays to ``{dotted.name: array}``.

    Ordering is deterministic (sorted at each level) so that sender and
    receiver agree on the container-streaming item order without
    negotiation.
    """
    out: dict[str, Any] = {}
    _flatten_into(out, tree, prefix)
    return out


def _flatten_into(out: dict[str, Any], node: Any, path: str) -> None:
    # a module-level recursion, not a closure that calls itself: such a
    # closure is a reference cycle holding ``out`` — and with it every
    # array of the state dict, device memory included — until Python's
    # cyclic collector happens to run
    if isinstance(node, Mapping):
        for key in sorted(node.keys()):
            _flatten_into(out, node[key], f"{path}{SEP}{key}" if path else str(key))
    elif isinstance(node, (list, tuple)):
        for i, item in enumerate(node):
            _flatten_into(out, item, f"{path}{SEP}{i}" if path else str(i))
    else:
        out[path if path else "_"] = node


def unflatten_state_dict(flat: Mapping[str, Any]) -> dict[str, Any]:
    """Inverse of :func:`flatten_state_dict` (lists come back as dicts of

    int-keyed entries converted to lists when keys are contiguous ints).
    """
    nested: dict[str, Any] = {}
    for name, value in flat.items():
        parts = name.split(SEP)
        node = nested
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value

    def fix_lists(node: Any) -> Any:
        if not isinstance(node, dict):
            return node
        keys = list(node.keys())
        if keys and all(k.isdigit() for k in keys):
            idx = sorted(int(k) for k in keys)
            if idx == list(range(len(idx))):
                return [fix_lists(node[str(i)]) for i in idx]
        return {k: fix_lists(v) for k, v in node.items()}

    return fix_lists(nested)
