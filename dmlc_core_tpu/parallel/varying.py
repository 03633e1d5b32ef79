"""Varying-type marking shared by the shard_map-based parallel lanes.

Under shard_map's varying-type discipline, values entering a shard body as
replicated must be explicitly cast to device-varying before they mix with
collective outputs (ppermute carries, psum'd cotangents) — otherwise
autodiff's transpose rule inserts implicit cross-device psums that
double-count by the axis size, or scan rejects the carry type.
"""

from __future__ import annotations

import jax
from jax import lax

__all__ = ["mark_varying"]


def mark_varying(tree, axes):
    """Cast every leaf of `tree` to device-varying over `axes` (a tuple of
    mesh axis names). Accepts a single array or any pytree."""
    return jax.tree.map(lambda t: lax.pcast(t, axes, to="varying"), tree)
