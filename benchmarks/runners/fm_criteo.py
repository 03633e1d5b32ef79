"""Runner of the factorisation-machine cells that read the Criteo click logs
in their own form: the session, loop and window of ``runners/fm.py`` over a
file of 40 tab-separated cells a line (``harness/datagen_criteo.py``), read
by the program's ``criteo`` format with the configuration's ``hash_bits`` as
a URI argument, and one exact number more that a hashing lane has to show:
``epoch_nnz_gap``.

The configuration's guarantees and what holds each (``configs/<name>.json``):
every row once an epoch, by ``epoch_rows_gap``; every present cell once and
no empty one, by ``epoch_nnz_gap``; the ids by the rule, by the comparison
with the plain reference, whose rows are the generator's cells in memory
hashed by ``reference/criteo.py``.
"""

from __future__ import annotations

import os
import time
from typing import Dict

import jax.numpy as jnp
import numpy as np

from dmlc_core_tpu.io import parser_formats_doc
from harness import cells, check, datagen, datagen_criteo
from runners import fm
from runners.fm import drive, end_to_end, traced_drive  # noqa: F401


class Session(fm.Session):
    """``fm.Session`` over a click-log file: its own ``write_data`` and
    reference rows, and the count of every batch's delivered entries held
    against the generator's."""

    def __init__(self, cell: Dict, seed: int, chips: int):
        super().__init__(cell, seed, chips)
        self.nnz_gap = 0
        # a test may break it, as it may ``fm.Session``'s
        self.render_text = lambda block, fmt: datagen_criteo.render_text(
            self.cfg["data"], block)

    def write_data(self, threads: int) -> None:
        fmt = self.traffic["format"]
        if (fmt != self.cfg["format"]
                or self.traffic.get("store", "text") != "text"
                or self.traffic.get("cache", "never") != "never"):
            raise ValueError("only cache=never text cells of the "
                             "configuration's format are written yet")
        if f"format `{fmt}`" not in parser_formats_doc():
            # a program from before the format fails here, at once, and
            # not after a file of text is written
            raise RuntimeError(f"the program's native registry has no "
                               f"text format {fmt!r}")
        text = os.path.join(cells.cache_dir(self.cell["name"]), "train.tsv")
        t0 = time.perf_counter()
        n, lens = datagen.write_text(text, self.cfg["data"], self.seed,
                                     self.file_rows, fmt, threads,
                                     self.render_text)
        self.nnz_per_batch = lens.reshape(-1, self.batch_rows).sum(axis=1)
        self.notes["text_bytes"] = n
        self.notes["write_text_s"] = time.perf_counter() - t0
        self.uri = f"{text}?hash_bits={int(self.cfg['hash_bits'])}"
        self.fmt = fmt

    def dispatch(self, batch):
        # before the step counts the batch: its place in the epoch
        want = int(self.nnz_per_batch[self._batches_this_epoch])
        self.nnz_gap = max(self.nnz_gap, abs(batch.total_nnz - want))
        return super().dispatch(batch)

    def exact_numbers(self) -> Dict[str, int]:
        return dict(super().exact_numbers(), epoch_nnz_gap=self.nnz_gap)

    def reference_readings(self, dtype: str = "float32") -> check.Readings:
        """``fm.Session.reference_readings`` with the columns hashed from
        the generator's cells by the plain statement of the format."""
        ref = cells.load_module("reference", self.cfg["reference"])
        rule = cells.load_module("reference", "criteo")
        cfg = self.cfg
        rows = self.check_steps * self.batch_rows
        block = datagen.first_rows(cfg["data"], self.seed, rows,
                                   self.file_rows)
        c = datagen_criteo.cells(cfg["data"], block)
        ids = rule.cell_ids(c.column, c.text, c.lens, int(cfg["hash_bits"]))
        if int(ids.max()) >= int(cfg["num_features"]):
            raise ValueError("hash_bits and num_features disagree")
        uniq, inv = np.unique(ids.astype(np.int64), return_inverse=True)
        # one shape for every seed (see fm.Session.reference_readings)
        width = datagen_criteo.COLUMNS
        uniq = np.concatenate([uniq, np.zeros(rows * width - uniq.size,
                                              uniq.dtype)])
        v0 = ref.initial_factors(self.init_seed, int(cfg["num_features"]),
                                 int(cfg["fm_rank"]),
                                 float(cfg["init_scale"]), uniq)
        off = np.concatenate([[0], np.cumsum(block.lens)])
        stacked = []
        for i in range(self.check_steps):
            r0, r1 = i * self.batch_rows, (i + 1) * self.batch_rows
            lo, hi = int(off[r0]), int(off[r1])
            col, val = ref.pad_rows(block.lens[r0:r1], inv[lo:hi],
                                    block.val[lo:hi], width)
            stacked.append((block.label[r0:r1], col, val))
        batches = ref.Batch(*(jnp.asarray(np.stack(leaf))
                              for leaf in zip(*stacked)))
        out = ref.readings(v0, batches, float(cfg["learning_rate"]), dtype)
        return check.Readings(*([float(x) for x in out[k]] for k in
                                ("losses", "grad_norms", "change_norms")))
