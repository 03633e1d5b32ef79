"""Runner of the factorisation-machine cells: the loop of
``examples/train.py`` as library calls,

    DeviceRowBlockIter(uri, mesh=data_mesh(), batch_rows=...)
        -> for batch in it: FMLearner.step(params, batch) -> float(loss)

epoch after epoch over the cell's file. One object (iterator, learner with its
compiled step, parameters) is built in set-up, driven through its first steps
for the comparison with the plain reference, and handed to the timed window.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dmlc_core_tpu.io.convert import rows_to_csr_recordio
from dmlc_core_tpu.models import FMLearner
from dmlc_core_tpu.tpu import DeviceRowBlockIter, data_mesh
from harness import cells, check, datagen

LEAVES = ("b", "w", "v")


class Session:
    """What set-up builds and the window drives."""

    def __init__(self, cell: Dict, seed: int, chips: int):
        self.cell = cell
        self.cfg = cell["config_file"]
        self.traffic = cell["traffic_file"]
        self.seed = int(seed)
        self.init_seed = self.seed % (2 ** 31)
        self.chips = chips
        self.batch_rows = int(self.cfg["batch_rows"]) * chips
        self.file_rows = int(self.traffic["epoch_batches"]) * self.batch_rows
        self.check_steps = int(self.traffic.get("check_steps", 3))
        self.learner = None
        self.params = None
        self.it = None
        self.epoch_rows: List[int] = []     # rows of each finished epoch
        self.epoch_batches: List[int] = []
        self._rows_this_epoch = 0
        self._batches_this_epoch = 0
        self.nnz_per_batch: Optional[np.ndarray] = None
        self.bytes_per_batch = 0
        self.notes: Dict = {}
        self.render_text = datagen.render_text  # a test may break it

    # -- data ---------------------------------------------------------------
    def write_data(self, threads: int) -> None:
        """The cell's file from the seed: text by the vectorised writer,
        then, for a ``crec`` store, converted once by the program's own
        converter. One file per cell, replaced in place."""
        fmt = self.traffic["format"]
        work = cells.cache_dir(self.cell["name"])
        text = os.path.join(work, "train." + fmt)
        t0 = time.perf_counter()
        n, lens = datagen.write_text(text, self.cfg["data"], self.seed,
                                     self.file_rows, fmt, threads,
                                     self.render_text)
        self.nnz_per_batch = lens.reshape(-1, self.batch_rows).sum(axis=1)
        self.notes["text_bytes"] = n
        self.notes["write_text_s"] = time.perf_counter() - t0
        self.uri, self.fmt = text, fmt
        if self.traffic.get("store", "text") == "crec":
            t1 = time.perf_counter()
            crec = os.path.join(work, "train.crec")
            got = rows_to_csr_recordio(text, crec, fmt=fmt)
            if got != self.file_rows:
                raise RuntimeError(f"the converter wrote {got} rows of "
                                   f"{self.file_rows}")
            os.remove(text)
            self.notes["convert_s"] = time.perf_counter() - t1
            self.notes["crec_bytes"] = os.path.getsize(crec)
            self.uri, self.fmt = crec, "crec"
        elif self.traffic.get("cache", "never") != "never":
            raise ValueError("only cache=never text cells are written yet")

    # -- the object ------------------------------------------------------------
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
        kw = {}
        if self.fmt != "crec":
            kw["nthread"] = int(self.traffic.get("nthread", 0))
        self.it = DeviceRowBlockIter(
            self.uri, mesh=mesh, batch_rows=self.batch_rows, fmt=self.fmt,
            prefetch=int(self.traffic.get("prefetch", 2)), **kw)
        self._stream = iter(self.it)

    def next_batch(self):
        """The next batch of the endless stream of epochs, or None at an
        epoch's end (the turnover is the caller's to time and name)."""
        batch = next(self._stream, None)
        if batch is None:
            self.epoch_rows.append(self._rows_this_epoch)
            self.epoch_batches.append(self._batches_this_epoch)
            self._rows_this_epoch = self._batches_this_epoch = 0
        return batch

    def turnover(self) -> None:
        self.it.before_first()
        self._stream = iter(self.it)

    def dispatch(self, batch):
        """One step of the learner, not waited for; returns the loss array
        and the batch's count of real nonzeros."""
        self.params, loss = self.learner.step(self.params, batch)
        nnz = int(self.nnz_per_batch[self._batches_this_epoch])
        self._rows_this_epoch += batch.total_rows
        self._batches_this_epoch += 1
        if not self.bytes_per_batch:
            self.bytes_per_batch = sum(int(v.nbytes)
                                       for v in batch.tree().values())
        return loss, nnz

    def step(self, batch) -> float:
        return float(self.dispatch(batch)[0])

    # -- the first steps, compared with the reference ---------------------------
    def first_steps(self) -> check.Readings:
        """Drive the object through its first steps by the window's own call
        and feed. The first gradient is worked out from the state after one
        step; the change after the last from the state the next step gets.
        ``p0`` is not kept across the steps but made again by the program's
        own ``init``: for a moment it stands beside the state, which sets the
        process's peak about one table above what the steps need (a fused
        norm-of-difference avoids that but took 9 to 13 s on the chip and
        read the norm 1.6e-4 off); ``step_peak_bytes`` notes the peak before
        it."""
        lr = float(self.cfg["learning_rate"])

        @jax.jit
        def diff_norms(a, b):
            return [jnp.sqrt(jnp.sum(jnp.square(getattr(a, k)
                                                - getattr(b, k))))
                    for k in LEAVES]

        losses = []
        grad_norms = None
        p_before = self.params
        for i in range(self.check_steps):
            batch = self.next_batch()
            if batch is None:
                raise RuntimeError("the file is shorter than the check")
            losses.append(self.step(batch))
            if i == 0:
                grad_norms = [float(x) / lr
                              for x in diff_norms(p_before, self.params)]
            p_before = None
        self.notes["step_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()[:self.chips])
        p0 = self.learner.init(self.init_seed)
        change = [float(x) for x in diff_norms(self.params, p0)]
        del p0
        return check.Readings(losses, grad_norms, change)

    def exact_numbers(self) -> Dict[str, int]:
        """Read and split: every finished epoch delivered the file's rows
        exactly once, in the file's count of batches."""
        batches = int(self.traffic["epoch_batches"])
        return {"epoch_rows_gap": max(
            [abs(r - self.file_rows) for r in self.epoch_rows] +
            [abs(b - batches) for b in self.epoch_batches] + [0])}

    def free(self) -> None:
        """Drop the program's state from the device before the reference."""
        if self.it is not None:
            self.it.close()
        self.it = self._stream = self.params = self.learner = None

    # -- the plain reference -------------------------------------------------
    def reference_readings(self, dtype: str = "float32") -> check.Readings:
        """The reference over the generator's first rows (never what the
        program parsed); ``dtype`` below float32 gives the control."""
        ref = cells.load_module("reference", self.cfg["reference"])
        cfg = self.cfg
        rows = self.check_steps * self.batch_rows
        block = datagen.first_rows(cfg["data"], self.seed, rows,
                                   self.file_rows)
        uniq, inv = np.unique(block.col, return_inverse=True)
        # one shape for every seed, so the run after a checkout's first
        # finds the reference's programs in the compile cache: the table has
        # a row for every token the check could hold; the spare rows are
        # never referred to and stay as they are
        bound = rows * len(cfg["data"]["fields"])
        uniq = np.concatenate([uniq, np.zeros(bound - uniq.size, uniq.dtype)])
        v0 = ref.initial_factors(self.init_seed, int(cfg["num_features"]),
                                 int(cfg["fm_rank"]),
                                 float(cfg["init_scale"]), uniq)
        off = np.concatenate([[0], np.cumsum(block.lens)])
        width = len(cfg["data"]["fields"])
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


# -- the timed window -----------------------------------------------------------

class Section:
    """One stretch of the step loop and what it did."""

    def __init__(self):
        self.t0 = self.t1 = 0.0
        self.step_end: List[float] = []
        self.rows = 0
        self.nnz = 0
        self.bad_losses = 0
        self.turnover_s: List[float] = []  # before_first() alone
        self.turnover_at: List[int] = []   # steps done when each came
        self.phases: List[tuple] = []      # per step: wait, dispatch, sync s

    @property
    def steps(self) -> int:
        return len(self.step_end)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def end_to_end(sec: Section) -> Dict[str, float]:
    """All rows stepped in the window over the whole window."""
    return {"rows_per_s": sec.rows / sec.seconds}


def drive(s: Session, seconds: float) -> Section:
    """Step for ``seconds`` and to the end of the step that passes it. Every
    host phase is a ``TraceAnnotation`` on the profiler's clock (free when no
    profile runs), so idle gaps of the device can be named."""
    ann = jax.profiler.TraceAnnotation
    sec = Section()
    sec.t0 = time.perf_counter()
    deadline = sec.t0 + seconds
    while True:
        ta = time.perf_counter()
        with ann("bench.next_batch"):
            batch = s.next_batch()
        if batch is None:
            t = time.perf_counter()
            with ann("bench.epoch_turnover"):
                s.turnover()
            sec.turnover_s.append(time.perf_counter() - t)
            sec.turnover_at.append(sec.steps)
            continue
        rows = batch.total_rows
        tb = time.perf_counter()
        with ann("bench.step_dispatch"):
            loss, nnz = s.dispatch(batch)
        del batch
        tc = time.perf_counter()
        with ann("bench.loss_sync"):
            value = float(loss)
        now = time.perf_counter()
        sec.phases.append((tb - ta, tc - tb, now - tc))
        sec.step_end.append(now)
        sec.rows += rows
        sec.nnz += nnz
        if not math.isfinite(value):
            sec.bad_losses += 1
        if now >= deadline:
            break
    sec.t1 = sec.step_end[-1]
    return sec


def traced_drive(s: Session, seconds: float, trace_dir: str) -> Section:
    """The same loop under the profiler, inside one ``bench.trace_window``
    annotation: that annotation is the traced window."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            sec = drive(s, seconds)
    finally:
        jax.profiler.stop_trace()
    return sec
