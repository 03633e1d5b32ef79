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

__all__ = ["mark_varying", "gather_unvarying"]


def mark_varying(tree, axes):
    """Cast every leaf of `tree` to device-varying over `axes` (a tuple of
    mesh axis names). Accepts a single array or any pytree."""
    return jax.tree.map(lambda t: lax.pcast(t, axes, to="varying"), tree)


def gather_unvarying(tree, axis: str):
    """Every device's ``[...]`` leaf stacked to ``[D, ...]`` in the mesh's
    order along ``axis``, the same on every device and typed so (unvarying):
    what a shard body may return under ``out_specs=P()`` or scatter into
    replicated state. ``lax.all_gather`` moves the same bytes but types its
    result varying, and nothing public casts that back; the invariant form
    is not exported by this jax (0.9), hence the private import."""
    from jax._src.lax.parallel import all_gather_invariant
    return all_gather_invariant(tree, axis)
