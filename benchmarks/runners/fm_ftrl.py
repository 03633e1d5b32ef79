"""Runner of the click-log cells trained with the logs' trainers' update rule
(``FMLearner(updater="ftrl")``: FTRL-proximal on ``b`` and ``w``, AdaGrad on
``v``, a state for every row): the session of ``runners/fm_criteo.py`` (its
file, its reference rows, ``epoch_nnz_gap``) with the configuration's rule
handed to the learner, the rule's own readings and reference, the count of
each step's distinct columns that ``harness/work_ftrl.py`` needs, and one
reading after the window: ``w_zero_share``.

The configuration's guarantees and what holds each (``configs/<name>.json``):
those of ``criteo1tb-fm``, by the same checks; "an update applied exactly once
per distinct column per step", by the comparison with the plain reference
(``reference/ftrl.py``), whose rule touches each of a batch's columns once:
a column updated twice, or not at all, moves its ``z``, ``n`` and ``G`` away
from the reference's.

The readings compared are the losses, and for every leaf of the state (``b``,
``w``, ``v``, ``zb``, ``nb``, ``z``, ``n``, ``g``) the norm of its change
after the first step (in ``check.Readings``' place of the first gradient,
read by ``grad_norm_gap``: FTRL's change is not ``lr * g``) and after the
last (``change_norm_gap``).
"""

from __future__ import annotations

import inspect
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from dmlc_core_tpu.models import FMLearner
from dmlc_core_tpu.tpu import DeviceRowBlockIter, data_mesh
from harness import cells, check, datagen, datagen_criteo
from runners import fm, fm_criteo
from runners.fm import end_to_end  # noqa: F401

RULE = ("alpha", "beta", "l1", "l2", "v_lr", "v_beta")


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x - y)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


@jax.jit
def _zero_counts(w, n):
    touched = n > 0
    return jnp.sum(touched & (w == 0)), jnp.sum(touched)


class Session(fm_criteo.Session):
    """``fm_criteo.Session`` with the configuration's update rule."""

    def __init__(self, cell: Dict, seed: int, chips: int):
        super().__init__(cell, seed, chips)
        self.distinct: List[int] = []   # each dispatched batch's own count

    def write_data(self, threads: int) -> None:
        if "updater" not in inspect.signature(FMLearner).parameters:
            # a program from before the rule fails here, at once, and not
            # after a file of text is written
            raise RuntimeError(
                f"the program's FMLearner has no updater: it cannot train "
                f"with {self.cfg['updater']!r}")
        super().write_data(threads)

    def build(self) -> None:
        cfg = self.cfg
        mesh = data_mesh(self.chips)
        self.learner = FMLearner(
            num_features=int(cfg["num_features"]), k=int(cfg["fm_rank"]),
            mesh=mesh, objective=cfg["objective"],
            init_scale=float(cfg["init_scale"]), updater=cfg["updater"],
            **{k: float(cfg[k]) for k in RULE})
        self.params = self.learner.init(self.init_seed)
        jax.block_until_ready(self.params)
        self.it = DeviceRowBlockIter(
            self.uri, mesh=mesh, batch_rows=self.batch_rows, fmt=self.fmt,
            prefetch=int(self.traffic.get("prefetch", 2)),
            nthread=int(self.traffic.get("nthread", 0)))
        self._stream = iter(self.it)

    def dispatch(self, batch):
        self.distinct.append(int(batch.total_distinct))
        return super().dispatch(batch)

    def first_steps(self) -> check.Readings:
        """``fm.Session.first_steps`` over every leaf of the state, with the
        first step's change in the first gradient's place. ``init()``'s
        state survives the first step (it is copied in); the state before
        the comparison after the last is ``init`` made again, whose slots
        are zeros."""
        losses, first = [], None
        p_before = self.params
        for i in range(self.check_steps):
            batch = self.next_batch()
            if batch is None:
                raise RuntimeError("the file is shorter than the check")
            losses.append(self.step(batch))
            if i == 0:
                first = [float(x) for x in _diff_norms(p_before, self.params)]
            p_before = None
        self.notes["step_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()[:self.chips])
        p0 = self.learner.init(self.init_seed)
        change = [float(x) for x in _diff_norms(self.params, p0)]
        del p0
        return check.Readings(losses, first, change)

    def free(self) -> None:
        # after the window and the peak's reading, before the state goes:
        # the share of the rows ever moved whose w the L1 term holds at 0
        if self.params is not None:
            zero, touched = (int(x) for x in _zero_counts(
                self.params.params.w, self.params.slots.n))
            self.notes["w_zero_share"] = zero / max(touched, 1)
            self.notes["rows_moved"] = touched
        super().free()

    def reference_readings(self, dtype: str = "float32") -> check.Readings:
        """``fm_criteo.Session.reference_readings`` with the rule's
        reference: the same rows, hashed by the plain statement of the
        format, the same initial factors."""
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
        out = ref.readings(v0, batches, tuple(float(cfg[k]) for k in RULE),
                           dtype)
        return check.Readings(*([float(x) for x in out[k]] for k in
                                ("losses", "first_change_norms",
                                 "change_norms")))


def _counted(s: Session, run, *args) -> fm.Section:
    """``run``'s section with the distinct columns of its steps summed
    (``sec.distinct``: what ``harness/work_ftrl.py`` counts by)."""
    at = len(s.distinct)
    sec = run(s, *args)
    sec.distinct = sum(s.distinct[at:])
    return sec


def drive(s: Session, seconds: float) -> fm.Section:
    return _counted(s, fm.drive, seconds)


def traced_drive(s: Session, seconds: float, trace_dir: str) -> fm.Section:
    return _counted(s, fm.traced_drive, seconds, trace_dir)
