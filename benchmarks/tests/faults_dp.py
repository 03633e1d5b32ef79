"""The fault that only a job of several replicas can have, planted as
``faults.py`` plants its own: underneath the harness, in the hook every form
of the step takes its loss through. The reference is untouched: it steps the
whole global batch."""

from __future__ import annotations


def drop_shard(session) -> None:
    """One shard's part left out of the sum: the last replica's rows get
    weight 0, so zeros stand in its place in all three ``psum``s (loss sum,
    weight sum, gradient) and every replica applies the mean over the other
    shards' rows. The replicas stay identical: only the comparison with the
    reference can see it."""
    import jax
    import jax.numpy as jnp
    learner = session.learner
    real = learner._shard_loss
    last = learner.mesh.devices.size - 1

    def shard_loss(params, shard, rows_per_shard):
        keep = jax.lax.axis_index(learner.axis_name) != last
        shard = dict(shard)
        shard["weight"] = shard["weight"] * keep.astype(jnp.float32)
        return real(params, shard, rows_per_shard)
    learner._shard_loss = shard_loss


FAULTS = {"drop_shard": {"after_build": drop_shard}}
