"""The faults a hashing text lane can have, planted as ``faults.py`` plants
its own: underneath the harness. The program's iterator keeps its assembler
(``HostBatcher``: nnz ladder, dedupe), its put and its step, and is fed by
the package's numpy statement of the ``criteo`` format with one rule of it
broken, in the native parser's place. The reference is untouched: it hashes
the generator's cells. Used by the tests, which see ``correct`` come out
false, and by ``chip_readings_criteo.py``, which reads how far each fault
moves each number at the cell's own size."""

from __future__ import annotations

from dmlc_core_tpu.data import criteo
from dmlc_core_tpu.tpu.device_iter import HostBatcher


class _OneBlock:
    """A parser over rows already in memory: one block an epoch."""

    def __init__(self, rows):
        self.rows, self.served = rows, False

    def next_block(self):
        if self.served:
            return None
        self.served = True
        return self.rows

    def before_first(self):
        self.served = False

    def set_epoch(self, epoch):
        return False

    def bytes_read(self):
        return 0

    def close(self):
        pass


def _broken_lane(session, keep=lambda column, cell: bool(cell),
                 hashed_as=lambda column: column) -> None:
    """Feed the session's iterator the file's rows with a cell kept where
    ``keep`` says and hashed with the column ``hashed_as`` gives."""
    it = session.it
    labels, lens, columns, cells = [], [], [], []
    with open(session.uri.split("?")[0], "rb") as f:
        for line in f.read().split(b"\n"):
            if not line:
                continue
            label, row = criteo.split_line(line)
            kept = [c for c, cell in enumerate(row) if keep(c, cell)]
            labels.append(label)
            lens.append(len(kept))
            columns.extend(hashed_as(c) for c in kept)
            cells.extend(row[c] for c in kept)
    rows = criteo.rows_of(labels, lens, columns, cells,
                          int(session.cfg["hash_bits"]))
    it.batcher.close()
    it.parser = _OneBlock(rows)
    it.batcher = HostBatcher(it.parser, it.batch_rows,
                             int(it.mesh.devices.size))


def drop_last_column(session) -> None:
    """A tokenizer that loses a line's last cell: C26 never becomes an
    entry."""
    _broken_lane(session, keep=lambda c, cell: bool(cell)
                 and c != criteo.COLUMNS - 1)


def column_not_hashed(session) -> None:
    """The column left out of the hash: equal strings in different columns
    become one feature."""
    _broken_lane(session, hashed_as=lambda c: 0)


def empty_cell_hashed(session) -> None:
    """An empty cell hashed instead of skipped: a missing value becomes a
    feature of its column."""
    _broken_lane(session, keep=lambda c, cell: True)


FAULTS = {
    "drop_last_column": {"after_build": drop_last_column},
    "column_not_hashed": {"after_build": column_not_hashed},
    "empty_cell_hashed": {"after_build": empty_cell_hashed},
}
