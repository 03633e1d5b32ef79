"""Seeded, vectorised generator of sparse one-hot rows and their text files.

One general generator for every text cell: a configuration's ``data`` block
lists *fields* (cardinality, presence probability), a skew power and a planted
linear model; a traffic file says the text format and how many rows a file
holds. Rows are produced block by block as NumPy arrays (``RowBlock``) and
rendered to libfm or libsvm text by byte assembly, with no per-row Python
formatting. The same blocks, kept in memory, are what the plain reference is
given: it never reads what the program parsed.

A block depends only on (seed, block number, block size), so a file's first
rows can be made again without writing or reading the file.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Iterator, List, Tuple

import numpy as np

BLOCK_ROWS = 65536
_SCATTER = 2654435761  # prime above every cardinality: rank -> id is a bijection
_HASH = 0x9E3779B1


@dataclass
class RowBlock:
    """CSR rows as the generator made them: ``lens[r]`` tokens per row,
    ``col``/``field``/``val`` per token in row order, ``label`` per row."""
    label: np.ndarray   # [R] float32 in {0, 1}
    lens: np.ndarray    # [R] int64
    col: np.ndarray     # [NNZ] int64 global feature ids
    field: np.ndarray   # [NNZ] int64 field ordinal
    val: np.ndarray     # [NNZ] float32

    @property
    def rows(self) -> int:
        return int(self.label.size)

    def slice_rows(self, r0: int, r1: int) -> "RowBlock":
        off = np.concatenate([[0], np.cumsum(self.lens)])
        lo, hi = int(off[r0]), int(off[r1])
        return RowBlock(self.label[r0:r1], self.lens[r0:r1], self.col[lo:hi],
                        self.field[lo:hi], self.val[lo:hi])


def concat_blocks(blocks: List[RowBlock]) -> RowBlock:
    return RowBlock(*(np.concatenate([getattr(b, k) for b in blocks])
                      for k in ("label", "lens", "col", "field", "val")))


def field_table(data: Dict) -> Dict[str, np.ndarray]:
    """Cardinality, id offset and presence probability of every field; the
    offsets partition [first_id, first_id + sum(card))."""
    card = np.array([f["cardinality"] for f in data["fields"]], np.int64)
    present = np.array([f.get("present", 1.0) for f in data["fields"]],
                       np.float64)
    first = int(data.get("first_id", 0))
    offset = first + np.concatenate([[0], np.cumsum(card)[:-1]])
    return {"card": card, "offset": offset, "present": present,
            "num_features": first + int(card.sum())}


def planted_weight(col: np.ndarray, scale: float) -> np.ndarray:
    """The planted linear model's weight of each feature id: an integer hash
    mapped to [-scale, scale), so no table of the feature space is held."""
    h = (col.astype(np.uint64) * np.uint64(_HASH)) & np.uint64(0xFFFFFFFF)
    return ((h.astype(np.float64) / 2.0 ** 32) * 2.0 - 1.0) * scale


def make_block(data: Dict, seed: int, block: int, rows: int) -> RowBlock:
    """Block ``block`` of the stream for ``seed``: ``rows`` rows."""
    tab = field_table(data)
    card = tab["card"][None, :]
    nf = card.shape[1]
    rng = np.random.default_rng([int(seed), int(block), 0x6B6464])
    u = rng.random((rows, nf))
    power = int(data["skew_power"])
    if power != data["skew_power"] or power < 1:
        raise ValueError("skew_power is a whole number >= 1")
    rank = (card * u ** power).astype(np.int64)
    np.minimum(rank, card - 1, out=rank)
    ids = tab["offset"][None, :] + (rank * _SCATTER) % card
    always = bool(np.all(tab["present"] >= 1.0))
    if always:
        keep = None
        lens = np.full(rows, nf, np.int64)
        col = ids.ravel()
        field = np.tile(np.arange(nf, dtype=np.int64), rows)
    else:
        keep = rng.random((rows, nf)) < tab["present"][None, :]
        keep[:, 0] = True  # a row is never empty
        lens = keep.sum(axis=1).astype(np.int64)
        col = ids[keep]
        field = np.broadcast_to(np.arange(nf, dtype=np.int64)[None, :],
                                (rows, nf))[keep]
    val = np.full(col.size, float(data.get("value", 1.0)), np.float32)
    planted = data["planted"]
    contrib = planted_weight(ids, planted["scale"])
    if keep is not None:
        contrib = np.where(keep, contrib, 0.0)
    margin = planted["bias"] + contrib.sum(axis=1)
    label = (margin + rng.logistic(size=rows) > 0).astype(np.float32)
    return RowBlock(label, lens, col, field, val)


def block_sizes(total_rows: int) -> List[int]:
    return [min(BLOCK_ROWS, total_rows - r)
            for r in range(0, total_rows, BLOCK_ROWS)]


def iter_blocks(data: Dict, seed: int, total_rows: int) -> Iterator[RowBlock]:
    """The blocks of a file of ``total_rows`` rows, in order."""
    for b, n in enumerate(block_sizes(total_rows)):
        yield make_block(data, seed, b, n)


def _ordered_map(fn, n: int, threads: int):
    """``fn(0) .. fn(n-1)`` in order, made ahead on a pool of ``threads``
    (NumPy releases the GIL in the passes that matter)."""
    if threads <= 1 or n <= 1:
        for i in range(n):
            yield fn(i)
        return
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(threads) as pool:
        pending = []
        nxt = 0
        for _ in range(n):
            while nxt < n and len(pending) < 2 * threads:
                pending.append(pool.submit(fn, nxt))
                nxt += 1
            yield pending.pop(0).result()


def first_rows(data: Dict, seed: int, rows: int, total_rows: int
               ) -> RowBlock:
    """The first ``rows`` rows of a file of ``total_rows`` rows, as
    ``write_text`` writes them (a block's content depends on its size, so the
    blocks are made at the file's own sizes and then cut)."""
    got, blocks = 0, []
    for block in iter_blocks(data, seed, total_rows):
        blocks.append(block)
        got += block.rows
        if got >= rows:
            break
    return concat_blocks(blocks).slice_rows(0, rows)


# -- text rendering ----------------------------------------------------------

def _digits(x: np.ndarray, width: int) -> np.ndarray:
    """[N, width] ASCII digits of non-negative ints below 2**32, leading
    zeros as 0 bytes (dropped later by the compress pass); the value 0 keeps
    one '0'."""
    out = np.empty((x.size, width), np.uint8)
    rem = x.astype(np.uint32)
    for p in range(width - 1, -1, -1):
        q = rem // np.uint32(10)
        out[:, p] = rem - q * np.uint32(10)
        rem = q
    lead = np.logical_and.accumulate(out == 0, axis=1)
    lead[:, -1] = False
    out += 48
    out[lead] = 0
    return out


def render_text(block: RowBlock, fmt: str) -> bytes:
    """libfm (``label field:id:val``) or libsvm (``label id:val``) lines for
    one block; values are non-negative whole numbers and print as such.
    Every token is a fixed-width row of bytes, ``[label] ' ' [field ':'] id
    ':' val [newline]``, with 0 bytes where a row has nothing to say (the
    label on any token but a line's first, the newline on any but its last,
    leading zeros); one compress pass drops the 0 bytes."""
    nnz = block.col.size
    vals = block.val
    if (vals != np.floor(vals)).any() or (vals < 0).any():
        raise ValueError("the text writer renders non-negative whole "
                         "values only")
    ends = np.cumsum(block.lens)
    first = np.zeros(nnz, np.uint8)
    first[ends - block.lens] = block.label.astype(np.uint8) + 48
    last = np.zeros(nnz, np.uint8)
    last[ends - 1] = 10
    colon = np.full((nnz, 1), 58, np.uint8)
    pieces = [first[:, None], np.full((nnz, 1), 32, np.uint8)]
    if fmt == "libfm":
        pieces += [_digits(block.field, len(str(int(block.field.max())))),
                   colon]
    elif fmt != "libsvm":
        raise ValueError(f"unknown text format {fmt!r}")
    pieces += [_digits(block.col, len(str(int(block.col.max())))), colon,
               _digits(vals, len(str(int(vals.max())))), last[:, None]]
    tok = np.concatenate(pieces, axis=1).ravel()
    return tok[tok != 0].tobytes()


def write_text(path: str, data: Dict, seed: int, total_rows: int,
               fmt: str, threads: int = 1, render=render_text
               ) -> Tuple[int, np.ndarray]:
    """Write the stream's first ``total_rows`` rows as text; returns the
    bytes written and every row's count of tokens. The file is replaced in
    place (one file per cell, whatever the seed), so a checkout's disk use
    stays bounded. ``render`` is for the tests that alter the file."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    sizes = block_sizes(total_rows)

    def one(b):
        block = make_block(data, seed, b, sizes[b])
        return block.lens, render(block, fmt)

    n, lens = 0, []
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        for block_lens, text in _ordered_map(one, len(sizes), threads):
            lens.append(block_lens)
            n += f.write(text)
    os.replace(tmp, path)
    return n, np.concatenate(lens)
