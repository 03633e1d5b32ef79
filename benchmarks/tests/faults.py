"""The faults a training cell can have, planted underneath the harness: in
the program (its step, its loss) or in the file it reads. The reference is
untouched: it follows the generator's rows. Used by the tests, which see
``correct`` come out false, and by ``chip_readings.py``, which reads how far
each fault moves each number at the cell's own size."""

from __future__ import annotations

import numpy as np

from harness import datagen


def state_unchanged(session) -> None:
    """A step that returns its state unchanged."""
    real = session.learner.step

    def step(params, batch):
        _, loss = real(params, batch)
        return params, loss
    session.learner.step = step


def half_batch(session) -> None:
    """Half of the batch left out, the mean taken over the rest: the second
    half of every shard's rows gets weight 0."""
    import jax.numpy as jnp
    real = session.learner._shard_loss

    def shard_loss(params, shard, rows_per_shard):
        keep = (jnp.arange(rows_per_shard) < rows_per_shard // 2)
        shard = dict(shard)
        shard["weight"] = shard["weight"] * keep.astype(jnp.float32)
        return real(params, shard, rows_per_shard)
    session.learner._shard_loss = shard_loss


def drop_last_token(session) -> None:
    """A token altered where it is produced: the file the program reads
    lacks the last feature of every row, as a tokenizer that loses a line's
    last token would give it."""
    real = datagen.render_text

    def render(block, fmt):
        ends = np.cumsum(block.lens)
        keep = np.ones(block.col.size, bool)
        keep[(ends - 1)[block.lens > 1]] = False
        cut = datagen.RowBlock(block.label,
                               block.lens - (block.lens > 1),
                               block.col[keep], block.field[keep],
                               block.val[keep])
        return real(cut, fmt)
    session.render_text = render


FAULTS = {
    "state_unchanged": {"after_build": state_unchanged},
    "half_batch": {"after_build": half_batch},
    "drop_last_token": {"before_data": drop_last_token},
}
