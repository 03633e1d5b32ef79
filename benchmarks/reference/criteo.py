"""Plain reference of the ``criteo`` text format: a cell of a Criteo click-log
line to its feature id, and a line to its label and ids, by the definition
in ``doc/parsing.md``. It imports nothing of the program.

A line is ``label \\t I1 .. I13 \\t C1 .. C26``; the 39 feature cells are
numbered 0..38; an empty cell is a missing value and gives nothing; every
other cell, integer cells too, gives the id

    fold(hash64(column, cell bytes), hash_bits)        with value 1.

``hash64`` and ``fold`` are written out below in Python's own integers, one
cell at a time. ``cell_ids`` is the same arithmetic over many cells at once
(numpy, modulo 2**64), for the benchmark's check, whose cells come from the
generator's memory and never from what the program parsed; a test holds the
two equal.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

COLUMNS = 39
MASK = (1 << 64) - 1
SEED_MUL = 0x9E3779B97F4A7C15
MUL1 = 0xFF51AFD7ED558CCD
MUL2 = 0xC4CEB9FE1A85EC53


def hash64(column: int, cell: bytes) -> int:
    h = ((column + 1) * SEED_MUL) & MASK
    for i in range(0, len(cell), 8):
        word = int.from_bytes(cell[i:i + 8], "little")  # short: zero-filled
        h = ((h ^ word) * MUL1) & MASK
        h ^= h >> 32
    h ^= len(cell)
    h ^= h >> 33
    h = (h * MUL1) & MASK
    h ^= h >> 33
    h = (h * MUL2) & MASK
    h ^= h >> 33
    return h


def fold(h: int, hash_bits: int) -> int:
    return (h ^ (h >> 32)) & ((1 << hash_bits) - 1)


def cell_id(column: int, cell: bytes, hash_bits: int) -> int:
    return fold(hash64(column, cell), hash_bits)


def line_ids(line: bytes, hash_bits: int) -> Tuple[float, List[int]]:
    """(label, ids) of one line without its terminator."""
    cells = line.split(b"\t")
    if len(cells) != COLUMNS + 1:
        raise ValueError(f"a line has {len(cells)} cells, not {COLUMNS + 1}")
    return float(cells[0]), [cell_id(c, cell, hash_bits)
                             for c, cell in enumerate(cells[1:]) if cell]


def cell_ids(column: np.ndarray, cells: np.ndarray, lens: np.ndarray,
             hash_bits: int) -> np.ndarray:
    """``cell_id`` of ``N`` cells at once: ``cells`` is ``[N, W]`` uint8,
    each cell's bytes from the left, zero bytes after them, ``W`` a multiple
    of 8; ``lens`` their lengths. uint64 arithmetic wraps modulo 2**64."""
    u = np.uint64
    words = np.ascontiguousarray(cells).view("<u8")
    n = lens.astype(u)
    h = (column.astype(u) + u(1)) * u(SEED_MUL)
    for j in range(words.shape[1]):
        mixed = (h ^ words[:, j]) * u(MUL1)
        mixed ^= mixed >> u(32)
        h = np.where(n > 8 * j, mixed, h)
    h ^= n
    h ^= h >> u(33)
    h *= u(MUL1)
    h ^= h >> u(33)
    h *= u(MUL2)
    h ^= h >> u(33)
    return (h ^ (h >> u(32))) & u((1 << hash_bits) - 1)
