"""Runner of the factorisation-machine cells that read the click logs where
they are kept: one worker's ``InputSplit`` part of a directory of day
objects, over S3. The session, loop and window of ``runners/fm_criteo.py``
with, in front of them, an object store in a process tree of its own
(``scripts/loadrig.py origin`` over ``tests/mock_s3.py``) that holds the
cell's file under the deployment's keys, and the iterator built as a worker
builds it:

    DeviceRowBlockIter("s3://criteo/?format=criteo&hash_bits=25",
                       part=rank, npart=workers, ...)

The configuration's guarantees and what holds each (``configs/<name>.json``):
the part exactly, by ``part_rows_gap`` and ``epoch_rows_gap`` against the
plain statement of the part rule (``reference/split.py``); every present
cell once, by ``epoch_nnz_gap`` batch by batch along the part's rows; the
ids by the rule, by the comparison with the plain reference on the part's
own first rows; one shape, by the harness's compile and shape counts with
``window_lacks``: a window that never saw the epoch's short last batch, or
an object crossing, where the part has them, proved nothing about them.
"""

from __future__ import annotations

import atexit
import os
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.io import parser_formats_doc
from dmlc_core_tpu.models import FMLearner
from dmlc_core_tpu.tpu import DeviceRowBlockIter, data_mesh
from harness import cells, check, datagen, datagen_criteo
from runners import fm, fm_criteo
from runners.fm import end_to_end  # noqa: F401
from scripts import loadrig
from tests import mock_origin

BUCKET = "criteo"
# the native core reads the S3 environment once, at its first use: every
# origin of a process that runs several sessions listens where the first did
_port = 0


class Session(fm_criteo.Session):
    """``fm_criteo.Session`` whose file lies in an object store under the
    deployment's keys and whose iterator reads one part of them."""

    def __init__(self, cell: Dict, seed: int, chips: int):
        super().__init__(cell, seed, chips)
        if "split_open_us" not in telemetry.METRIC_HELP:
            # a program from before the split's instruments fails here, at
            # once, before a file is written or an origin started
            raise RuntimeError("the program has no histogram "
                               "'split_open_us' (the split over objects)")
        if self.traffic.get("store") != "s3":
            raise ValueError("this runner reads over S3 only")
        dep = self.cfg["deployment"]
        self.rank, self.workers = int(dep["rank"]), int(dep["workers"])
        # the listing; a test may take an object away (the reference keeps
        # the deployment's count)
        self.keys = [f"day_{i:02d}" for i in range(int(dep["objects"]))]
        self.origin = None
        self.part = None              # reference/split.py's, in write_data
        self.part_batches = 0
        self._crossings_at = np.zeros(0, np.int64)
        self._in_window = False
        self.short_in_window = 0
        self.crossings_in_window = 0

    # -- data: the file, the part by the reference, the store ------------------
    def write_data(self, threads: int) -> None:
        """One object's text from the seed, as ``fm_criteo`` writes its
        file; the part's rows by the plain statement of the rule, from the
        object's size and line ends as written (never from what the program
        read); then the store, holding that text under every key."""
        fmt = self.traffic["format"]
        if (fmt != self.cfg["format"]
                or self.traffic.get("cache", "never") != "never"):
            raise ValueError("only cache=never cells of the configuration's "
                             "format are written yet")
        if f"format `{fmt}`" not in parser_formats_doc():
            raise RuntimeError(f"the program's native registry has no "
                               f"text format {fmt!r}")
        text = os.path.join(cells.cache_dir(self.cell["name"]), "train.tsv")
        t0 = time.perf_counter()
        size, lens = datagen.write_text(text, self.cfg["data"], self.seed,
                                        self.file_rows, fmt, threads,
                                        self.render_text)
        self.notes["text_bytes"] = size
        self.notes["write_text_s"] = time.perf_counter() - t0
        rule = cells.load_module("reference", "split")
        ends = rule.line_ends_of_text(np.fromfile(text, np.uint8))
        objects = int(self.cfg["deployment"]["objects"])
        self.part = rule.part_of([size] * objects, [ends] * objects,
                                 self.rank, self.workers)
        self.part_lines = rule.row_sequence(self.part)[:, 1]
        starts = np.arange(0, self.part.rows, self.batch_rows)
        self.part_batches = starts.size
        # a few batches past the end read 0: a program that delivers more
        # shows it in the gaps, not in an IndexError
        self.nnz_per_batch = np.concatenate([
            np.add.reduceat(lens[self.part_lines], starts), np.zeros(8, int)])
        self._crossings_at = np.cumsum(
            [i1 - i0 for _, i0, i1 in self.part.spans])[:-1]
        self.notes["part"] = {
            "rows": self.part.rows, "batches": self.part_batches,
            "short_last_batch_rows": self.part.rows % self.batch_rows,
            "first": list(self.part.first), "last": list(self.part.last),
            "bytes": self.part.end - self.part.begin}
        self.origin_text = text
        self._start_origin(text)
        self.uri = (f"s3://{BUCKET}/?format={fmt}"
                    f"&hash_bits={int(self.cfg['hash_bits'])}")
        self.fmt = "auto"

    def _start_origin(self, text: str) -> None:
        global _port
        store = self.cfg["deployment"]["store"]
        config = mock_origin.OriginConfig(
            first_byte_ms=int(store["first_byte_ms"]),
            latency_ms=int(store["body_block_ms"]),
            latency_block=int(store["body_block_bytes"]),
            workers=int(store["origin_workers"]))
        t0 = time.perf_counter()
        self.origin = loadrig.spawn_origin(
            store["backend"], [f"{BUCKET}/{k}=@{text}" for k in self.keys],
            config, ttl_s=900.0, port=_port)
        atexit.register(self.close)
        _port = self.origin.port
        os.environ.update(self.origin.env())
        self.notes["origin_start_s"] = time.perf_counter() - t0

    def close(self) -> None:
        """Stop the origin (idempotent; also at exit, whatever happened)."""
        if self.origin is not None:
            self.origin.close()
            self.origin = None

    # -- the object: ``fm.Session.build`` with the worker's part ---------------
    def build(self) -> None:
        cfg = self.cfg
        if cfg.get("l2", 0.0) != 0.0:
            raise ValueError("the compact reference holds for l2 = 0 only")
        mesh = data_mesh(self.chips)
        self.learner = FMLearner(
            num_features=int(cfg["num_features"]), k=int(cfg["fm_rank"]),
            mesh=mesh, objective=cfg["objective"],
            learning_rate=float(cfg["learning_rate"]), l2=0.0,
            init_scale=float(cfg["init_scale"]))
        self.params = self.learner.init(self.init_seed)
        jax.block_until_ready(self.params)
        self.it = DeviceRowBlockIter(
            self.uri, part=self.rank, npart=self.workers, mesh=mesh,
            batch_rows=self.batch_rows, fmt=self.fmt,
            prefetch=int(self.traffic.get("prefetch", 2)),
            nthread=int(self.traffic.get("nthread", 0)))
        self._stream = iter(self.it)

    def dispatch(self, batch):
        before = self._rows_this_epoch
        out = super().dispatch(batch)
        if self._in_window:
            self.short_in_window += batch.total_rows < self.batch_rows
            self.crossings_in_window += int(np.count_nonzero(
                (self._crossings_at > before)
                & (self._crossings_at <= self._rows_this_epoch)))
        return out

    def exact_numbers(self) -> Dict[str, int]:
        part_rows = self.part.rows
        lacks = 0
        if part_rows % self.batch_rows and not self.short_in_window:
            lacks += 1
        if self._crossings_at.size and not self.crossings_in_window:
            lacks += 1
        rows_gap = max([abs(r - part_rows) for r in self.epoch_rows] + [0])
        return {
            "part_rows_gap": rows_gap,
            "epoch_rows_gap": max([rows_gap] + [
                abs(b - self.part_batches) for b in self.epoch_batches]),
            "epoch_nnz_gap": self.nnz_gap,
            "window_lacks": lacks,
            "short_batches_in_window": self.short_in_window,
            "crossings_in_window": self.crossings_in_window}

    def free(self) -> None:
        super().free()
        self.close()

    # -- the plain reference, on the part's own first rows ---------------------
    def _check_rows(self, rows: int) -> datagen.RowBlock:
        """The generator's rows at the part's first ``rows`` lines (blocks
        are made at the file's own sizes, then cut)."""
        sizes = datagen.block_sizes(self.file_rows)
        first = np.concatenate([[0], np.cumsum(sizes)])
        made, pieces = {}, []
        lines = self.part_lines[:rows]
        # runs of consecutive lines (a part's first rows may cross an object)
        cuts = np.flatnonzero(np.diff(lines) != 1) + 1
        for run in np.split(lines, cuts):
            lo, hi = int(run[0]), int(run[-1]) + 1
            b0 = int(np.searchsorted(first, lo, side="right")) - 1
            b1 = int(np.searchsorted(first, hi, side="left"))
            for b in range(b0, b1):
                if b not in made:
                    made[b] = datagen.make_block(self.cfg["data"], self.seed,
                                                 b, sizes[b])
            joined = datagen.concat_blocks([made[b] for b in range(b0, b1)])
            pieces.append(joined.slice_rows(lo - int(first[b0]),
                                            hi - int(first[b0])))
        return datagen.concat_blocks(pieces)

    def reference_readings(self, dtype: str = "float32") -> check.Readings:
        """``fm_criteo.Session.reference_readings`` on the rows the part
        begins with."""
        ref = cells.load_module("reference", self.cfg["reference"])
        rule = cells.load_module("reference", "criteo")
        cfg = self.cfg
        rows = self.check_steps * self.batch_rows
        if self.part.rows < rows:
            raise RuntimeError("the part is shorter than the check")
        block = self._check_rows(rows)
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


# -- the timed window: ``fm``'s, with the session told that it is open -----------

def drive(s: Session, seconds: float) -> fm.Section:
    s._in_window = True
    return fm.drive(s, seconds)


def traced_drive(s: Session, seconds: float, trace_dir: str) -> fm.Section:
    s._in_window = True
    return fm.traced_drive(s, seconds, trace_dir)
