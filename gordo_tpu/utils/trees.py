"""Pytree helpers shared across the framework."""

from __future__ import annotations

from typing import Any

import jax
import numpy as np


def to_host(tree: Any) -> Any:
    """Pull every jax array leaf to host numpy (device-independent pickling)."""

    def _leaf(x):
        if isinstance(x, jax.Array):
            return np.asarray(jax.device_get(x))
        return x

    return jax.tree_util.tree_map(_leaf, tree)


def start_fetch(tree: Any) -> None:
    """Start every jax array leaf on its way to the host and return at once.

    A copy needs only its own array to be ready, not the device's queue to
    be empty, so bytes started here cross while a later program runs;
    :func:`to_host` then finds them on the host."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and leaf.is_fully_addressable:
            leaf.copy_to_host_async()


def tree_size_bytes(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(getattr(l, "nbytes", 0) for l in leaves)


def param_count(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(getattr(l, "size", 0) for l in leaves))
