"""The ``criteo`` text format stated in plain numpy: the oracle of the native
lane (``cpp/src/parser.cc`` ``CriteoParser``, ``cpp/src/criteo_hash.h``), as
``tpu.device_iter.col_slots`` is of ``cpp/src/col_slots.h``.

A line of the Criteo Terabyte click logs is ``label \\t I1 .. I13 \\t C1 ..
C26``: 40 tab-separated cells, an empty cell a missing value. The format
(``?format=criteo&hash_bits=B``) gives every present feature cell, integer
cells too, the id ``fold(hash64(column, cell bytes), B)`` with the column
counted 0..38 from I1, and the value 1; an empty cell gives nothing.
``doc/parsing.md`` has the definition with a worked id;
``tests/test_criteo_parser.py`` holds this file, the native parser and
``benchmarks/reference/criteo.py`` equal, id for id.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from dmlc_core_tpu.base import DMLCError

COLUMNS = 39   # 13 integer + 26 categorical
CELLS = 40     # the label and the columns

_SEED_MUL = np.uint64(0x9E3779B97F4A7C15)
_MUL1 = np.uint64(0xFF51AFD7ED558CCD)
_MUL2 = np.uint64(0xC4CEB9FE1A85EC53)


def hash64(columns, cells: Sequence[bytes]) -> np.ndarray:
    """``hash64(column, cell)`` of every pair, as uint64: the column enters
    first, then the cell eight bytes a little-endian word (the last word
    filled up with zero bytes), then its length, then MurmurHash3's 64-bit
    finalizer. All arithmetic is modulo 2**64."""
    n = np.fromiter((len(c) for c in cells), np.uint64, len(cells))
    width = max(8, -(-int(n.max(initial=0)) // 8) * 8)
    words = np.zeros((len(cells), width), np.uint8)
    words.view(f"S{width}")[:, 0] = cells   # zero-filled to the width
    words = words.view("<u8")
    h = (np.asarray(columns).astype(np.uint64) + np.uint64(1)) * _SEED_MUL
    for j in range(width // 8):
        mixed = (h ^ words[:, j]) * _MUL1
        mixed ^= mixed >> np.uint64(32)
        h = np.where(n > 8 * j, mixed, h)
    h ^= n
    h ^= h >> np.uint64(33)
    h *= _MUL1
    h ^= h >> np.uint64(33)
    h *= _MUL2
    h ^= h >> np.uint64(33)
    return h


def fold(h: np.ndarray, hash_bits: int) -> np.ndarray:
    """A 64-bit hash folded to an id below ``2**hash_bits``."""
    if not 1 <= int(hash_bits) <= 63:
        raise DMLCError(f"criteo: hash_bits={hash_bits} is outside 1..63")
    return (h ^ (h >> np.uint64(32))) & np.uint64((1 << int(hash_bits)) - 1)


def split_line(line: bytes) -> Tuple[float, List[bytes]]:
    """A line without its terminator as (label, the 39 feature cells); a
    line of another count of cells, or whose label is no number, is
    refused."""
    cells = line.split(b"\t")
    if len(cells) != CELLS:
        raise DMLCError(f"criteo: a line has {len(cells)} cells, not "
                        f"{CELLS}: {line[:60]!r}")
    try:
        label = float(cells[0])
    except ValueError:
        raise DMLCError(f"criteo: a line has a label that is not a number: "
                        f"{line[:60]!r}") from None
    return label, cells[1:]


@dataclass
class Rows:
    """CSR rows as the native parser's ``RowBlock`` shows them: no value
    (every value is 1), no weight, qid or field."""
    label: np.ndarray    # [R] float32
    offset: np.ndarray   # [R + 1] uint64
    index: np.ndarray    # [NNZ] uint32
    value: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    qid: Optional[np.ndarray] = None
    field: Optional[np.ndarray] = None

    @property
    def num_rows(self) -> int:
        return len(self.label)

    @property
    def nnz(self) -> int:
        return len(self.index)


def rows_of(labels: Sequence[float], lens: Sequence[int], columns,
            cells: Sequence[bytes], hash_bits: int) -> Rows:
    """Rows of ``lens[r]`` entries each from their cells in row order:
    entry ``i`` is ``cells[i]`` hashed in column ``columns[i]``."""
    ids = fold(hash64(columns, cells), hash_bits)
    offset = np.concatenate([[0], np.cumsum(lens)]).astype(np.uint64)
    return Rows(np.asarray(labels, np.float32), offset,
                ids.astype(np.uint32 if hash_bits <= 32 else np.uint64))


def parse(data: bytes, hash_bits: int) -> Rows:
    """Every line of ``data`` (``\\n`` or ``\\r\\n`` ends a line, the last
    may lack it; empty lines are skipped) as rows of hashed ids."""
    labels, lens, columns, cells = [], [], [], []
    for line in data.replace(b"\r", b"\n").split(b"\n"):
        if not line:
            continue
        label, row = split_line(line)
        present = [c for c, cell in enumerate(row) if cell]
        labels.append(label)
        lens.append(len(present))
        columns.extend(present)
        cells.extend(row[c] for c in present)
    return rows_of(labels, lens, columns, cells, hash_bits)
