"""dmlc-core's part rule for text, by its definition and in numpy: which lines
of a data set of many objects worker ``part`` of ``npart`` reads.

``InputSplit::Create(uri, part, npart, "text")`` over a directory (reference
``include/dmlc/io.h``, ``src/io/input_split_base.cc``):

- the objects are laid end to end in the listing's order (sorted by name;
  empty ones left out), ``T`` bytes in all;
- part ``k`` is the bytes ``[min(T, k * step), min(T, (k + 1) * step))`` with
  ``step = ceil(T / npart)``;
- each edge is then moved forward to the next record head. An edge at byte 0,
  at an object's first byte or at ``T`` stays. Any other edge moves to just
  past the first newline at or after it, within its object; the object's end
  counts as a head. So an edge that falls on a line's first byte moves past
  that whole line, at both ends alike, and every line belongs to exactly one
  part: the one whose moved range holds the line's first byte.

Nothing here reads the program or what it parsed: the inputs are the
objects' sizes and, for each object, where its lines end.

Departure from the reference noted: where an object does not end in a
newline and the part goes on into the next object, this repo (as upstream
since dmlc-core PRs 385 and 452) puts a newline between them, so an object's
last line is a line of its own; older upstream joined it to the next
object's first. The count of lines here takes the first reading: an
unterminated last line is a line.
"""

from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import numpy as np


class Part(NamedTuple):
    begin: int                       # the moved range, bytes of the whole
    end: int
    first: Tuple[int, int]           # (object, line) of the first row
    last: Tuple[int, int]            # (object, line) of the last row
    spans: List[Tuple[int, int, int]]  # (object, line0, line1) in order
    rows: int


def line_ends(line_bytes: np.ndarray) -> np.ndarray:
    """Where each line of an object ends (one past its newline; an
    unterminated last line ends with the object), from the lines' byte
    lengths as they lie in the object."""
    return np.cumsum(np.asarray(line_bytes, np.int64))


def line_ends_of_text(text: np.ndarray) -> np.ndarray:
    """``line_ends`` of an object given as its bytes (uint8)."""
    ends = np.flatnonzero(text == 10).astype(np.int64) + 1
    if not ends.size or ends[-1] != text.size:
        ends = np.append(ends, text.size)
    return ends


def _moved(edge: int, starts: np.ndarray, total: int,
           ends: Sequence[np.ndarray]) -> int:
    if edge <= 0 or edge >= total:
        return min(max(edge, 0), total)
    k = int(np.searchsorted(starts, edge, side="right")) - 1
    local = edge - int(starts[k])
    if local == 0:
        return edge
    # the end of the line that holds byte ``local``
    i = int(np.searchsorted(ends[k], local, side="right"))
    return int(starts[k]) + int(ends[k][i])


def part_of(sizes: Sequence[int], ends: Sequence[np.ndarray], part: int,
            npart: int) -> Part:
    """The lines of part ``part`` of ``npart`` over objects of ``sizes``
    bytes in listing order, ``ends[k]`` being ``line_ends`` of object k
    (objects with the same content may share one array)."""
    if not 0 <= part < npart:
        raise ValueError("part index out of range")
    sizes = np.asarray(sizes, np.int64)
    if (sizes <= 0).any():
        raise ValueError("the listing leaves empty objects out")
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    total = int(sizes.sum())
    step = -(-total // npart)
    begin = _moved(min(total, step * part), starts, total, ends)
    end = _moved(min(total, step * (part + 1)), starts, total, ends)
    spans = []
    for k in range(len(sizes)):
        lo, hi = begin - int(starts[k]), end - int(starts[k])
        if hi <= 0 or lo >= int(sizes[k]):
            continue
        # lines of object k whose first byte lies in [lo, hi): line i
        # starts where line i - 1 ends
        line_starts = np.concatenate([[0], ends[k][:-1]])
        i0 = int(np.searchsorted(line_starts, lo, side="left"))
        i1 = int(np.searchsorted(line_starts, hi, side="left"))
        if i1 > i0:
            spans.append((k, i0, i1))
    rows = sum(i1 - i0 for _, i0, i1 in spans)
    first = (spans[0][0], spans[0][1]) if spans else (-1, -1)
    last = (spans[-1][0], spans[-1][2] - 1) if spans else (-1, -1)
    return Part(begin, end, first, last, spans, rows)


def row_sequence(p: Part) -> np.ndarray:
    """The part's rows in reading order as [rows, 2] (object, line)."""
    if not p.spans:
        return np.zeros((0, 2), np.int64)
    return np.concatenate([
        np.stack([np.full(i1 - i0, k, np.int64),
                  np.arange(i0, i1, dtype=np.int64)], axis=1)
        for k, i0, i1 in p.spans])
