"""The faults a worker's part of a data set of objects can have, planted as
``faults.py`` and ``faults_criteo.py`` plant theirs: underneath the harness.
The reference is untouched: it states the part by the rule from the objects
as the generator wrote them, and its rows from the generator's memory. Used
by the tests, which see ``correct`` come out false, and by
``chip_readings_s3.py``, which reads how far each fault moves each number at
the cell's own size."""

from __future__ import annotations

import os

import numpy as np

from dmlc_core_tpu.tpu import DeviceRowBlockIter


def part_begins_a_line_late(session) -> None:
    """A boundary probe that skips one record head too many: the part's
    first line goes to nobody. The iterator (parser, assembler, put, step)
    reads the part's own bytes less that line, from a local file."""
    text = session.origin_text
    data = np.fromfile(text, np.uint8)
    ends = np.flatnonzero(data == 10) + 1
    starts = np.concatenate([[0], ends[:-1]])
    pieces = [data[starts[i0]:ends[i1 - 1]]
              for _, i0, i1 in session.part.spans]
    late = np.concatenate(pieces)[ends[session.part.first[1]]
                                  - starts[session.part.first[1]]:]
    path = os.path.join(os.path.dirname(text), "late.tsv")
    late.tofile(path)
    it = session.it
    session.it = DeviceRowBlockIter(
        f"{path}?hash_bits={int(session.cfg['hash_bits'])}", mesh=it.mesh,
        batch_rows=it.batch_rows, fmt=session.cfg["format"])
    it.close()
    session._stream = iter(session.it)


def object_left_out(session) -> None:
    """``day_02`` missing from the listing: every part is cut from 23
    objects, and this one from other bytes."""
    session.keys.remove("day_02")


def short_batch_dropped(session) -> None:
    """A loop that throws an epoch's short last batch away (the old way to
    keep one compiled shape)."""
    inner = session.next_batch

    def next_batch():
        batch = inner()
        if batch is not None and batch.total_rows < session.batch_rows:
            return inner()
        return batch
    session.next_batch = next_batch


FAULTS = {
    "part_begins_a_line_late": {"after_build": part_begins_a_line_late},
    "object_left_out": {"before_data": object_left_out},
    "short_batch_dropped": {"after_build": short_batch_dropped},
}
