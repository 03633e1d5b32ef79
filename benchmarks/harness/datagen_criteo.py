"""The general generator's rows as lines of the Criteo click logs.

``datagen.make_block`` draws, for every field of a configuration's ``data``
block, a skewed rank scattered over the field's ids, a presence and a
planted label; a block still depends only on (seed, block number, block
size). Here a field is a *column* of the click logs (its ``column``: 0..12
the integer columns I1..I13, 13..38 the categorical C1..C26), a field's id
less its offset is the column's *value*, and a value is printed as the logs
print it: an integer column's as a decimal number, a categorical column's as
8 hex digits (the value times an odd constant modulo 2**32: distinct values
print distinct strings, and equal values of two columns print the same
string, as small integers do in the real logs). An absent field is an empty
cell. The cells, kept in memory, are what the plain reference hashes
(``reference/criteo.py``): it never reads what the program parsed.

The configuration lists a column that is never missing first, because the
generator never makes an empty row (``make_block`` keeps field 0 present).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np

from harness import datagen

INT_COLUMNS = 13
COLUMNS = 39
WIDTH = 8            # bytes of the longest cell: 8 hex digits
_HEX_MUL = 2654435761  # odd: value -> 32 bits is a bijection
_HEX_DIGITS = np.frombuffer(b"0123456789abcdef", np.uint8)
# a line as a fixed row of bytes: the label, 39 x (tab, cell), the newline
_LINE = 1 + COLUMNS * (1 + WIDTH) + 1


@dataclass
class Cells:
    """The present cells of a block's rows, row after row in line order."""
    column: np.ndarray   # [NNZ] int64, 0..38
    text: np.ndarray     # [NNZ, WIDTH] uint8: the bytes from the left, then 0
    lens: np.ndarray     # [NNZ] int64 bytes of each cell


def column_of_field(data: Dict) -> np.ndarray:
    col = np.array([f["column"] for f in data["fields"]], np.int64)
    if sorted(col.tolist()) != list(range(COLUMNS)):
        raise ValueError("the fields name the columns 0..38 once each")
    return col


def cells(data: Dict, block: datagen.RowBlock) -> Cells:
    """The cells of ``block`` (``make_block``'s or ``first_rows``'),
    reordered within each row from the fields' order to the line's."""
    tab = datagen.field_table(data)
    column = column_of_field(data)[block.field]
    row = np.repeat(np.arange(block.rows), block.lens)
    # through a dense [rows, 39] table in the line's order and out again
    dense = np.zeros((block.rows, COLUMNS), np.uint64)
    there = np.zeros((block.rows, COLUMNS), bool)
    dense[row, column] = block.col - tab["offset"][block.field]
    there[row, column] = True
    value = dense[there]
    column = np.broadcast_to(np.arange(COLUMNS), there.shape)[there]
    is_int = column < INT_COLUMNS
    digits = datagen._digits(value[is_int], WIDTH)   # right-aligned, 0-led
    hexed = ((value[~is_int] * np.uint64(_HEX_MUL))
             & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    nib = ((hexed[:, None] >> np.arange(28, -4, -4, dtype=np.uint32))
           & np.uint32(15)).astype(np.uint8)
    text = np.empty((column.size, WIDTH), np.uint8)
    text[is_int] = digits
    text[~is_int] = _HEX_DIGITS[nib]
    lens = (text != 0).sum(axis=1).astype(np.int64)
    # to the left: as a little-endian word the bytes move down by the
    # count of leading zero bytes
    word = text.view("<u8")[:, 0] >> (8 * (WIDTH - lens)).astype(np.uint64)
    return Cells(column, word.astype("<u8")[:, None].view(np.uint8), lens)


def render_text(data: Dict, block: datagen.RowBlock) -> bytes:
    """The block's rows as lines of 40 tab-separated cells: every line a
    fixed-width row of bytes with 0 where a cell is short or missing; one
    compress pass drops the 0 bytes."""
    c = cells(data, block)
    line = np.zeros((block.rows, _LINE), np.uint8)
    line[:, 0] = block.label.astype(np.uint8) + 48
    line[:, 1:-1:1 + WIDTH] = 9
    line[:, -1] = 10
    row = np.repeat(np.arange(block.rows), block.lens)
    start = 2 + c.column * (1 + WIDTH)
    for k in range(WIDTH):
        line[row, start + k] = c.text[:, k]
    flat = line.ravel()
    return flat[flat != 0].tobytes()
