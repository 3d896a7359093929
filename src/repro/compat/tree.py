"""Pytree utilities with path support.

Re-exports the ``jax.tree`` helpers so callers depend on ONE tree API,
plus :func:`path_key` and :func:`path_str`, which normalize the standard
``DictKey``/``SequenceKey``/``GetAttrKey`` path entries to plain strings
(checkpoint manifests, optimizer masks).
"""
from __future__ import annotations

from typing import Any

import jax

flatten = jax.tree.flatten
unflatten = jax.tree.unflatten
leaves = jax.tree.leaves
structure = jax.tree.structure
map = jax.tree.map  # noqa: A001 - mirrors jax.tree.map
flatten_with_path = jax.tree.flatten_with_path
map_with_path = jax.tree.map_with_path
leaves_with_path = jax.tree.leaves_with_path


def path_key(entry: Any) -> str:
    """One path entry → its plain-string key.

    Handles DictKey (.key), GetAttrKey (.name), SequenceKey (.idx) and
    falls back to str() for anything exotic a custom pytree registers.
    """
    for attr in ("key", "name", "idx"):
        v = getattr(entry, attr, None)
        if v is not None:
            return str(v)
    return str(entry)


def path_str(path, sep: str = "/") -> str:
    """Full key path → a stable flat name (checkpoint leaf names)."""
    return sep.join(path_key(k) for k in path)
