"""Device-resident row-block iteration: the heart of the TPU-native design.

The reference pipeline ends at host CSR views (RowBlockIter, data.h:267);
consumers then copy into their own matrices. Here the pipeline *ends in HBM*:

  native parse threads → PaddedBatch (static shapes, numpy, pinned layout)
        → background staging thread (double buffer)
        → jax.device_put under a NamedSharding  → sharded jax.Array batch

Static-shape strategy (XLA compiles one program per shape — SURVEY §7 hard
part 1 "ragged → device"):
- rows per batch is fixed (`batch_rows`); the final partial batch is padded
  with zero-weight rows, so row count never varies.
- nnz is bucketed on an eighth-of-an-octave ladder (`nnz_bucket`: the
  fullest shard's true nnz rounded up to a sixteenth of its next power of
  two, floor `min_nnz_bucket`), so padding stays under 12.5% and the number
  of distinct compiled shapes is O(log max_nnz).
- CSR offsets become per-nonzero `row` segment ids (int32, TPU-friendly);
  padding nonzeros point at row == rows_per_shard, a sacrificial segment
  sliced off by the ops in dmlc_core_tpu.ops.sparse.

Sharding strategy: arrays carry a leading device axis [D, ...] sharded over
the mesh "data" axis; shard d holds rows [d*R, (d+1)*R) of the batch with
*local* row ids — so segment ops never cross shard boundaries and DP
gradients reduce with one psum (SURVEY §2.5).

The double buffer is the ThreadedIter contract (threadediter.h:77-279)
carried across the GIL: ctypes releases the GIL during native parsing, so the
staging thread overlaps parse+pad with XLA compute on the main thread.
"""

from __future__ import annotations

import contextlib
import os
import queue
import re
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Any, Dict, Iterator, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.base import DMLCError
from dmlc_core_tpu.io.native import (NativeBatcher, NativeCsrRecBatcher,
                                     NativeDenseRecBatcher, NativeParser,
                                     _bf16_dtype)
from dmlc_core_tpu.tpu.runtime import install_compile_monitor
from dmlc_core_tpu.tpu.sharding import batch_sharding
from dmlc_core_tpu.tracker.wire import TrackerAbortedError, env_int

# device-lane metric objects resolved ONCE (the registry contract:
# resolve, keep the pointer — per-batch re-resolution would take the
# registry lock on the staging/transfer threads); lazy so importing this
# module registers nothing
_lane_metrics = None
_NO_SPAN = contextlib.nullcontext()


def _get_lane_metrics():
    global _lane_metrics
    if _lane_metrics is None:
        _lane_metrics = {
            "transfer_us": telemetry.histogram("device_transfer_us"),
            "submit_us": telemetry.histogram("device_put_submit_us"),
            "block_us": telemetry.histogram("device_put_block_us"),
            "stage_us": telemetry.histogram("device_stage_us"),
            "wait_us": telemetry.histogram("device_wait_us"),
            "first_wait_us": telemetry.histogram(
                "device_first_batch_wait_us"),
            "turnover_us": telemetry.histogram("device_turnover_us"),
            "batches": telemetry.counter("device_batches_total"),
            "nnz_sent": telemetry.counter("device_nnz_sent_total"),
            "nnz_real": telemetry.counter("device_nnz_real_total"),
            "cols_distinct": telemetry.counter("device_cols_distinct_total"),
            "owner_max": telemetry.counter("device_cols_owner_max_total"),
            "stretch_sent": telemetry.counter("device_stretch_sent_total"),
            "stretch_real": telemetry.counter("device_stretch_real_total"),
            "tail_batches": telemetry.counter("device_tail_batches_total"),
            "bytes": telemetry.counter("device_transfer_bytes_total"),
            "failures": telemetry.counter("device_put_failures_total"),
            "host_q": telemetry.gauge("device_host_q_depth"),
            "ready_q": telemetry.gauge("device_ready_q_depth"),
            "shapes": telemetry.gauge("device_distinct_shapes"),
            "zc_batches": telemetry.counter("device_zero_copy_batches_total"),
            "recycle_skip": telemetry.gauge("device_recycle_skipped"),
        }
    return _lane_metrics


# -- compile-churn telemetry -------------------------------------------------
# Process-wide shape census: the jit cache is keyed by the batch tree's
# structure + leaf shapes/dtypes, so the FIRST sight of a key here is the
# batch that makes every jitted consumer re-trace. Bucket-policy
# regressions (min_nnz_bucket too small, a layout flip mid-run) surface
# as a growing device_compile_events_total{shape=} trail instead of
# silent re-tracing.
_shape_lock = threading.Lock()
_shapes_seen: set = set()


def _batch_shape_key(batch) -> str:
    """Deterministic census key for one host/device batch: every leaf's
    name + shape (+ the dense dtype, which changes the compiled program).
    Matches jit-cache granularity for the batch input."""
    parts = [f"{k}{tuple(v.shape)}" for k, v in sorted(batch.tree().items())]
    if isinstance(batch, DenseBatch):
        parts.append(f"x:{np.dtype(batch.x.dtype).name}")
    return ",".join(parts)


# the labeled compile-event trail stops growing the registry past this
# many distinct shapes (further firsts fold into shape="other"): the
# pathological churn this metric exists to DETECT would otherwise mint a
# full leaf-names+shapes label per batch forever, bloating every
# snapshot, rank_export frame, and /metrics scrape. The
# device_distinct_shapes gauge stays exact regardless.
_SHAPE_LABEL_CAP = 64


def _note_shape(batch) -> None:
    key = _batch_shape_key(batch)
    with _shape_lock:
        new = key not in _shapes_seen
        if new:
            _shapes_seen.add(key)
        n = len(_shapes_seen)
    if new:
        label = key if n <= _SHAPE_LABEL_CAP else "other"
        telemetry.counter("device_compile_events_total",
                          {"shape": label}).inc()
        telemetry.emit_event("device-shape", shape=label, distinct=n)
    _get_lane_metrics()["shapes"].set(n)


def _reset_shape_census() -> None:
    """Forget every seen shape (tests; the real census is process-wide
    like the jit cache it mirrors)."""
    with _shape_lock:
        _shapes_seen.clear()


@contextlib.contextmanager
def jax_profiler_capture():
    """Optional XLA-timeline capture: with ``DMLC_JAX_PROFILE=<dir>`` set,
    wraps the body in ``jax.profiler.start_trace/stop_trace``. Every
    ``telemetry.span`` opened inside lands in the capture's ``/host:CPU``
    plane as ``dmlc.<name>``, on the profiler's clock beside the device
    planes (doc/observability.md "Device lane"). Yields True when a
    capture is running, False when the env is unset. A profiler that was
    asked for and will not start (or stop) raises: the caller wanted the
    trace, and a run without it is not the run they asked for."""
    out_dir = os.environ.get("DMLC_JAX_PROFILE")
    if not out_dir:
        yield False
        return
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()
        telemetry.emit_event("jax-profile", dir=out_dir, started=True)


def _dense_dtype_of(d) -> np.dtype:
    """Normalize the dense x dtype: float32 or bfloat16 (the MXU dtypes the
    native FillDense can emit; batcher.h x_dtype)."""
    if isinstance(d, str) and d in ("bf16", "bfloat16"):
        return _bf16_dtype()
    dt = np.dtype(d)
    if dt != np.dtype(np.float32) and dt != _bf16_dtype():
        raise DMLCError(
            f"dense_dtype must be float32 or bfloat16, got {dt}")
    return dt

__all__ = ["PaddedBatch", "DenseBatch", "DeviceRowBlockIter", "HostBatcher",
           "NativeHostBatcher", "DenseRecHostBatcher", "CsrRecHostBatcher",
           "unpack_tree", "unpack_shard", "match_placement_rules",
           "jax_profiler_capture", "nnz_bucket", "tail_rung", "col_slots",
           "owner_counts"]


@dataclass
class PaddedBatch:
    """Static-shape CSR batch; named arrays lead with the device axis D.

    row/col/val: [D, NNZ]  per-nonzero segment id (local), column, value
    cols: [D, U] int32     each shard's DISTINCT columns, ascending, padded
                           to the ladder rung U of the fullest shard's count
                           by an id beyond any table (``col_slots``); where
                           the columns have key-range owners, owner-major:
                           ``[D, owners * C]``, a padded stretch an owner
    slot: [D, NNZ] int32   per-nonzero position of its column in ``cols``:
                           ``col == cols[slot]``; 0 on padding nonzeros
    label/weight: [D, R]   weight 0 marks padding rows
    nrows: [D]             true row count per shard
    qid: [D, R] int32      optional query/group ids (ranking); -1 on padding
                           rows and rows from qid-less blocks (sentinel —
                           cannot collide with a real qid:0)
    field: [D, NNZ] int32  optional per-nonzero field ids (FM/FFM), 0 on pad

    qid/field continue the reference RowBlock's optional columns
    (data.h:174-236) into the device layout.

    A feature that recurs in a shard recurs in ``col`` and once in ``cols``:
    a consumer that gathers and scatters parameter rows does so at ``cols``
    and expands by ``slot`` (models/fm.py), one read and one update a feature
    a batch. Every assembler emits both, always; ``col`` is then neither
    kept nor sent (the field stays None): unpack_shard/unpack_tree give
    ``col = cols[slot]`` in-jit to whoever still reads it, so there is one
    truth. A hand-built batch of named leaves still carries ``col``.

    Packed transfer layout (native batchers): `big` [D, Kb, NNZ] int32
    stacks row/slot/val(f32 bits)[/field] per shard, `cols` [D, U] int32
    travels beside it, and `aux` [D, K, R] int32 stacks label(f32 bits)/
    weight(f32 bits)[/qid]/nrows-plane per shard, so a batch crosses
    host->HBM in THREE transfers instead of one RPC per leaf — on
    high-latency links the per-transfer dispatch, not bandwidth, bounds
    the binary formats. The packs and `cols` are SHARD-MAJOR (device axis
    leads): under a NamedSharding every shard's
    bytes are one contiguous leading-axis slice of the host buffer, which
    is what lets the zero-copy device_put path hand each device its slab
    without a host gather. With ``csr_val_dtype="bf16"`` values travel as
    a separate bfloat16 ``val16`` leaf [D, NNZ] (half the value bytes;
    the int32 pack drops its val plane). Host-side the named fields are
    zero-copy views into the packs; device-side batches carry only the
    packs and consumers unpack INSIDE jit (unpack_shard/unpack_tree)."""
    row: Any = None
    col: Any = None
    val: Any = None
    label: Any = None
    weight: Any = None
    nrows: Any = None
    # host-side true row count (not part of the device tree; avoids a
    # device->host sync when consumers just need progress accounting)
    total_rows: int = 0
    # host-side true nonzero count, all shards, as the fill counted it:
    # against D * nnz_bucket it is the batch's fill share
    total_nnz: int = 0
    # host-side count of distinct columns, summed over the shards, as the
    # dedupe counted it: against total_nnz, the share of the gathers and
    # scatters that is left
    total_distinct: int = 0
    # host-side: a short last batch that was sent at the rungs of the batch
    # before it, its own being lower (``tail_rung``)
    tail_lifted: bool = False
    # host-side: the key-range owners ``cols`` is laid out by (1: the plain
    # list) and, with several, the fullest owner's count of the batch's
    # distinct columns, all shards' stretches together
    owners: int = 1
    owner_max: int = 0
    qid: Any = None
    field: Any = None
    big: Any = None  # [D, Kb, NNZ] packed row/slot[/val][/field]
    aux: Any = None  # [D, K, R] packed label/weight[/qid]/nrows
    val16: Any = None  # [D, NNZ] bfloat16 values (csr_val_dtype="bf16")
    slot: Any = None
    cols: Any = None

    @property
    def rows_per_shard(self) -> int:
        return self.aux.shape[2] if self.label is None else \
            self.label.shape[1]

    @property
    def nnz_bucket(self) -> int:
        return self.big.shape[2] if self.row is None else self.row.shape[1]

    def tree(self) -> Dict[str, Any]:
        """The batch as a flat dict pytree (the device_put / jit input):
        the packed leaves when packed, the named leaves otherwise."""
        if self.aux is not None:
            t = {"big": self.big, "aux": self.aux}
            if self.cols is not None:  # always, from an assembler
                t["cols"] = self.cols
            if self.val16 is not None:
                t["val"] = self.val16
            return t
        t = {"row": self.row, "col": self.col, "val": self.val,
             "label": self.label, "weight": self.weight,
             "nrows": self.nrows}
        if self.qid is not None:
            t["qid"] = self.qid
        if self.field is not None:
            t["field"] = self.field
        return t


def _expand_cols(cols, slot):
    """``col`` from the distinct list and the slot plane, along the last
    axis (one shard or all of them; host numpy or in-jit)."""
    if isinstance(cols, np.ndarray):
        return np.take_along_axis(cols, slot, axis=-1)
    return jnp.take_along_axis(cols, slot, axis=-1,
                               mode="promise_in_bounds")


@dataclass
class DenseBatch:
    """Dense device layout for low-dimensional data (auto-chosen when
    max_index is small): x is [D, R, F] — downstream matmuls tile straight
    onto the MXU, and host->HBM transfer drops from 12 B/nnz (CSR triple) to
    4 B/value (or 2 with bfloat16). Missing entries are 0 (the reference's
    CSR semantics for absent features in a linear model).

    `aux` packs label/weight[/qid]/nrows as in PaddedBatch: a batch is TWO
    host->HBM transfers (x + aux) instead of 4-5."""
    x: Any = None
    label: Any = None
    weight: Any = None
    nrows: Any = None
    total_rows: int = 0
    qid: Any = None  # [D, R] int32 group ids (field has no dense layout)
    aux: Any = None  # [D, K, R] packed label/weight[/qid]/nrows

    @property
    def rows_per_shard(self) -> int:
        return self.aux.shape[2] if self.label is None else \
            self.label.shape[1]

    @property
    def num_features(self) -> int:
        return self.x.shape[2]

    def tree(self) -> Dict[str, Any]:
        """The batch as a flat dict pytree (the device_put / jit input):
        the two packed leaves when packed, the named leaves otherwise."""
        if self.aux is not None:
            return {"x": self.x, "aux": self.aux}
        t = {"x": self.x, "label": self.label, "weight": self.weight,
             "nrows": self.nrows}
        if self.qid is not None:
            t["qid"] = self.qid
        return t


# -- packed-batch helpers ----------------------------------------------------
# Shard-major packs (device axis LEADS): aux [D, K, R], big [D, Kb, NNZ].
# Per-shard plane order, aux: 0=label (f32 bits), 1=weight (f32 bits),
# [2=qid], last=nrows plane (entry [d, -1, 0] holds shard d's true row
# count). big: 0=row, 1=slot, [2=val (f32 bits) unless a separate bf16
# `val` leaf travels], [last=field]; `cols` [D, U] beside it holds each
# shard's distinct columns (col == cols[slot]). Both packs are int32
# containers; float planes travel as raw bits and are bitcast back on device (a
# dtype-preserving reinterpretation, not a cast). Shard-major means shard
# d's bytes are the contiguous slice pack[d] — the layout the zero-copy
# sharded device_put path requires.

def _aligned_empty(shape, dtype, align: int = 64) -> np.ndarray:
    """C-contiguous uninitialised array whose base address is `align`-byte
    aligned. np.empty only guarantees 16; XLA:CPU aliases (rather than
    copies) a host buffer on device_put only at 64-byte alignment, so
    every staging buffer the zero-copy path may hand to device_put is
    allocated through here."""
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    raw = np.empty(nbytes + align, np.uint8)
    off = (-raw.ctypes.data) % align
    return raw[off:off + nbytes].view(dtype).reshape(shape)


def _view_aux(aux: np.ndarray):
    """Named [D, R] views over a shard-major [D, K, R] aux pack (the
    native fills write the pack directly; these are zero-copy strided
    float32/int32 reinterpretations for host-side consumers)."""
    D, K, R = aux.shape
    label = aux[:, 0].view(np.float32)
    weight = aux[:, 1].view(np.float32)
    qid = aux[:, 2] if K == 4 else None
    return aux, label, weight, qid


def _view_big(big: np.ndarray, has_val: bool = True):
    """Named [D, NNZ] row/slot[/val][/field] views over a shard-major
    [D, Kb, NNZ] pack (val viewed float32; pass has_val=False when values
    travel as a separate bf16 leaf and the pack carries no val plane)."""
    D, Kb, bucket = big.shape
    row = big[:, 0]
    slot = big[:, 1]
    if has_val:
        val = big[:, 2].view(np.float32)
        field = big[:, 3] if Kb == 4 else None
    else:
        val = None
        field = big[:, 2] if Kb == 3 else None
    return row, slot, val, field


def _finish_aux(aux, nrows) -> None:
    """Mirror the [D] nrows vector into the aux nrows plane ([d, -1, 0])."""
    aux[:, -1] = 0
    aux[:, -1, 0] = nrows


def _pack_aux(label, weight, qid, nrows, D: int, R: int, emit_qid: bool,
              aux=None):
    """Assemble an aux pack from already-built flat row arrays (the
    python-batcher path; the native batchers fill their aux views
    in-place instead). Reuses `aux` when its shape fits. Returns
    (aux, label_view, weight_view, qid_view) with views shaped [D, R]."""
    K = 4 if emit_qid else 3
    if aux is None or aux.shape != (D, K, R):
        aux = _aligned_empty((D, K, R), np.int32)
    _, label_v, weight_v, qid_v = _view_aux(aux)
    label_v[:] = np.asarray(label).reshape(D, R)
    weight_v[:] = np.asarray(weight).reshape(D, R)
    if qid_v is not None:
        qid_v[:] = np.asarray(qid).reshape(D, R)
    _finish_aux(aux, nrows)
    return aux, label_v, weight_v, qid_v


def _unpack(tree: Dict[str, Any], sel, nrows_of) -> Dict[str, Any]:
    """Shared aux/big plane decoding; `sel(pack, i)` slices plane i of a
    shard-major pack ([:, i] on full trees, [i] inside a shard_map body)
    and `nrows_of` extracts the nrows vector from the last aux plane."""
    if "aux" not in tree:
        return tree
    aux = tree["aux"]
    out = {}
    if "x" in tree:
        out["x"] = tree["x"]
    if "big" in tree:
        big = tree["big"]
        kb = big.shape[-2]
        if "cols" not in tree:
            raise DMLCError(
                "a packed CSR batch holds each entry's slot where its column "
                f"stood and needs the 'cols' leaf beside {sorted(tree)} "
                "(col_slots makes both from a col plane)")
        out["row"] = sel(big, 0)
        out["slot"] = sel(big, 1)
        out["cols"] = tree["cols"]
        # for whoever still reads columns entry by entry (the linear
        # learner, predict); a step that never asks compiles it away
        out["col"] = _expand_cols(out["cols"], out["slot"])
        if "val" in tree:  # separate bf16 value leaf; pack has no val plane
            out["val"] = tree["val"]
            if kb == 3:
                out["field"] = sel(big, 2)
        else:
            out["val"] = _bitcast_f32(sel(big, 2))
            if kb == 4:
                out["field"] = sel(big, 3)
    out["label"] = _bitcast_f32(sel(aux, 0))
    out["weight"] = _bitcast_f32(sel(aux, 1))
    if aux.shape[-2] == 4:
        out["qid"] = sel(aux, 2)
    out["nrows"] = nrows_of(sel(aux, aux.shape[-2] - 1))
    return out


def unpack_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Named leaves from a packed batch tree (device-axis-ful shapes:
    label/weight/qid [D, R], row/col/val/field [D, NNZ], nrows [D]).
    Identity for already-named trees. Usable under jit (bitcasts and
    slices only) and on host numpy."""
    return _unpack(tree, lambda a, i: a[:, i], lambda plane: plane[:, 0])


def unpack_shard(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Named leaves from one shard of a packed tree (device axis already
    dropped: aux [K, R], big [Kb, NNZ], x [R, F]; nrows becomes a 0-d
    scalar — the SAME rank the named-tree lane yields after its v[0]
    device-axis slice, so _shard_loss implementations see one shape
    regardless of how the batch arrived). Identity for already-named
    trees. For use inside shard_map bodies."""
    return _unpack(tree, lambda a, i: a[i], lambda plane: plane[0])


def _bitcast_f32(a):
    if isinstance(a, np.ndarray):
        return a.view(np.float32)
    return jax.lax.bitcast_convert_type(a, jnp.float32)


def nnz_bucket(n: int, floor: int) -> int:
    """The nnz capacity of a CSR batch whose fullest shard holds ``n``
    entries: the one rule that chooses it (stated once more natively,
    cpp/src/nnz_bucket.h; tests/test_nnz_bucket.py holds the two equal).

    At or under ``floor`` (``min_nnz_bucket``) the capacity is the floor.
    Above it, with ``p`` the smallest power of two >= ``n``, it is ``n``
    rounded up to a multiple of ``p / 16``: eight rungs an octave, so
    padding stays under 12.5% of ``n`` where the next power of two left up
    to 100%, a power of two maps to itself, and the count of distinct
    compiled shapes stays O(log max_nnz). The granule never falls under
    ``min(floor, 128)`` entries, so small shapes and lane rows stay whole.

    The step's gathers and scatters cost per entry sent, padding included
    (PERF.md section 5), which is why the ladder is fine. The trade: a
    corpus whose batch nnz wanders across rungs compiles up to eight shapes
    an octave where it compiled one; ``device_distinct_shapes`` and
    ``model_step_builds_total`` show it. At thousands of rows a batch the
    count is steady to a fraction of a percent. An epoch's short last batch
    does not land on a rung of its own: ``tail_rung``."""
    floor = max(int(floor), 1)
    n = int(n)
    if n <= floor:
        return floor
    p = 1 << (n - 1).bit_length()
    g = max(p >> 4, min(floor, 128))
    return -(-n // g) * g


def tail_rung(own: int, before: int, take: int, batch_rows: int) -> int:
    """The rung a batch of ``take`` real rows is sent at, ``own`` being the
    rung of its own count and ``before`` the rung the batch before it in the
    same epoch was sent at (0: there was none). Stated once more natively
    (cpp/src/nnz_bucket.h TailRung; tests/test_nnz_bucket.py holds the two
    equal), and applied by every assembler to the nnz capacity and to the
    distinct-column list alike.

    A byte-range part of a data set (``part``/``npart``) never holds a whole
    number of batches, so every epoch ends in a batch with fewer real rows
    than ``batch_rows``. Its rows are padded, but its own counts would land
    on lower rungs: a second compiled shape, for one batch an epoch. So a
    short batch takes no rung below the batch before it; the fill is the
    padding the ladder already uses (entries on the sacrificial row, ``cols``
    padded by 2**31 - 1), counted in ``device_nnz_sent_total`` and, a batch,
    in ``device_tail_batches_total``. A full batch keeps its own rung, and
    so does a short batch with none before it (a part of under one batch)."""
    return before if take < batch_rows and before > own else own


def col_slots(col: np.ndarray, n, floor: int, owners: int = 1,
              owner_rows: int = 0):
    """The distinct columns of each CSR shard and every entry's slot among
    them: the dedupe every assembler runs, stated here by ``np.unique`` as
    the oracle and once natively (cpp/src/col_slots.h, whose header has the
    why; tests/test_col_slots.py holds the two equal).

    ``col`` [D, NNZ] int32 holds ``n[d]`` real entries in shard d and
    becomes the slot plane in place (padded entries: slot 0). Returns
    ``(cols, distinct)``: ``cols`` [D, U] int32, each shard's distinct
    columns ascending, ``U = nnz_bucket(fullest shard's count, floor)``,
    the tail padded by 2**31 - 1 repeated (beyond any table: a filling
    gather reads zeros there, a scatter drops them, no slot names them,
    and the list stays sorted to its end); a shard without entries lists
    column 0 once, so slot 0 always names a real row. ``distinct`` is the
    batch's count of distinct columns, summed over the shards.

    With several ``owners`` of ``owner_rows`` ids each (tables sharded by
    key range: models/_dp.py) the list is owner-major, ``[owners, C]``
    flattened: stretch ``o`` holds the shard's columns in
    ``[o * owner_rows, (o + 1) * owner_rows)``, one contiguous stretch of
    the ascending list, padded to ``C`` as the tail is, ``C =
    nnz_bucket(fullest (shard, owner) stretch, floor)``, ``U = owners * C``,
    and a slot names a position in the flattened list. One owner: the list
    above to the byte."""
    lists = []
    for d, nd in enumerate(n):
        uniq, inv = np.unique(col[d, :nd], return_inverse=True)
        col[d, :nd] = inv
        col[d, nd:] = 0
        lists.append(uniq if nd else np.zeros(1, np.int32))
    top = max(int(u[-1]) for u in lists)
    if owners > 1 and top >= owners * owner_rows:
        raise DMLCError(f"column {top} lies beyond the {owners} owners' "
                        f"ranges of {owner_rows} ids")
    # where each owner's stretch of a shard's list starts, and its end
    edges = np.arange(owners) * owner_rows
    first = [np.append(np.searchsorted(u, edges), len(u)) for u in lists]
    C = nnz_bucket(max(int(np.diff(f).max()) for f in first), floor)
    cols = _aligned_empty((len(lists), owners * C), np.int32)
    cols[:] = np.iinfo(np.int32).max
    for d, (uniq, f) in enumerate(zip(lists, first)):
        for o in range(owners):
            cols[d, o * C:o * C + f[o + 1] - f[o]] = uniq[f[o]:f[o + 1]]
        if owners > 1:  # a slot moves with its column's stretch
            nd = n[d]
            o = np.searchsorted(f[1:], col[d, :nd], side="right")
            col[d, :nd] += (o * C - f[o]).astype(np.int32)
    return cols, sum(len(u) for u, nd in zip(lists, n) if nd)


def owner_counts(cols: np.ndarray, n, owners: int) -> np.ndarray:
    """``[owners]``: how many distinct columns of a batch each key-range
    owner is sent, all shards' stretches of the owner-major ``cols``
    together (a shard without entries sends its stand-in and counts
    nothing). What ``device_cols_owner_max_total`` takes the largest of."""
    D = cols.shape[0]
    real = cols.reshape(D, owners, -1) != np.iinfo(np.int32).max
    return (real & (np.asarray(n) > 0)[:, None, None]).sum(axis=(0, 2))


# -- spec-driven placement ---------------------------------------------------
# Leaf-name -> PartitionSpec rules, first match wins (the
# match_partition_rules idiom from t5x-style partitioning): the transfer
# thread derives every leaf's NamedSharding from this table instead of
# hard-coding per-leaf cases. Every batch leaf today is shard-major
# (device axis leads), so one catch-all leading-axis rule suffices; the
# table is the extension point for future non-leading layouts (add the
# specific rule ABOVE the catch-all).
_PLACEMENT_RULES = (
    (r".*", lambda axis: jax.sharding.PartitionSpec(axis)),
)


def match_placement_rules(mesh, keys, axis_name: str = "data"):
    """Per-leaf NamedSharding dict for batch-tree `keys`: each key takes
    the spec of the first _PLACEMENT_RULES regex that fully matches it."""
    out = {}
    for k in keys:
        for pat, spec_fn in _PLACEMENT_RULES:
            if re.fullmatch(pat, k):
                out[k] = jax.sharding.NamedSharding(mesh, spec_fn(axis_name))
                break
    return out


class _ZeroCopyIneligible(Exception):
    """A batch (or backend state) the zero-copy device_put path cannot
    serve; carries the fallback-counter reason label."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def _tree_aliases_host(host_tree: Dict[str, Any],
                       dev_tree: Dict[str, Any]) -> bool:
    """Whether any device leaf's buffer lives inside its host leaf's
    memory span — i.e. device_put aliased instead of copied, so recycling
    the host buffer would corrupt live device data. Probes the actual
    buffer addresses (unsafe_buffer_pointer) instead of trusting backend
    names. A backend that will not give an address is treated as
    aliasing (recycling is an optimization, correctness must not depend
    on it); ``device_alias_probe_total{verdict=}`` says which of the
    three answers — ``no_alias``, ``aliases``, ``unprobeable`` — this
    iterator's one probe got."""
    verdict = "no_alias"
    try:
        for k, h in host_tree.items():
            if not isinstance(h, np.ndarray):
                continue
            lo = h.ctypes.data
            hi = lo + h.nbytes
            d = dev_tree.get(k)
            for s in getattr(d, "addressable_shards", ()):
                p = s.data.unsafe_buffer_pointer()
                if lo <= p < hi:
                    verdict = "aliases"
    except Exception as e:  # the backend's own error type is unknowable
        verdict = "unprobeable"
        telemetry.emit_event("device-alias-probe", verdict=verdict,
                             error=f"{type(e).__name__}: {e}"[:200])
    telemetry.counter("device_alias_probe_total",
                      {"verdict": verdict}).inc()
    return verdict != "no_alias"


class _HostBufferPool:
    """Shape-keyed free-list of host batch buffers shared by the batcher
    implementations: avoids per-batch allocate + page-fault churn on the
    staging thread. Buffers enter via put() only after the host->device
    copy has completed and only when device arrays cannot alias host
    memory (DeviceRowBlockIter's transfer-thread contract); bounded per
    key so idle memory stays small."""

    CAP = 4  # per shape key; covers the prefetch depth

    def __init__(self):
        self._pool: Dict[Any, list] = {}
        self._lock = threading.Lock()

    def pop(self, key):
        with self._lock:
            lst = self._pool.get(key)
            return lst.pop() if lst else None

    def put(self, key, arrs) -> None:
        with self._lock:
            lst = self._pool.setdefault(key, [])
            if len(lst) < self.CAP:
                lst.append(arrs)


class HostBatcher:
    """Accumulates native RowBlocks into fixed-row-count numpy batches.

    Splitting/merging is needed because native blocks have arbitrary sizes
    (one per parser worker per chunk) while the device wants `batch_rows`
    exactly."""

    def __init__(self, parser: NativeParser, batch_rows: int,
                 num_shards: int, min_nnz_bucket: int = 4096,
                 index64: bool = False, layout: str = "auto",
                 dense_max_features: int = 512, dense_dtype=np.float32,
                 col_owners: Tuple[int, int] = (1, 0)):
        if batch_rows % num_shards != 0:
            raise DMLCError(
                f"batch_rows={batch_rows} must divide by shards={num_shards}")
        if layout not in ("auto", "csr", "dense"):
            raise DMLCError(f"unknown layout {layout!r}")
        self.parser = parser
        self.col_owners = col_owners
        self.batch_rows = batch_rows
        self.num_shards = num_shards
        self.min_nnz_bucket = min_nnz_bucket
        self.layout = layout
        self.dense_max_features = dense_max_features
        self.dense_dtype = _dense_dtype_of(dense_dtype)
        self._num_features: Optional[int] = None  # fixed once dense chosen
        # leftover rows from the previous native block (numpy copies)
        self._pending: list = []  # (label, weight, lens, col, val, qid, fld)
        self._pending_rows = 0
        self._done = False
        self._has_qid = False    # sticky, like the layout choice
        self._has_field = False
        # plane presence pins on the first batch (static pytree structure
        # for jitted consumers; same contract as NativeHostBatcher)
        self._emit_qid: Optional[bool] = None
        self._emit_field: Optional[bool] = None
        # recycled big/aux packs (see _HostBufferPool contract)
        self._pool = _HostBufferPool()
        # (nnz rung, distinct rung) of the CSR batch before, this epoch,
        # for tail_rung
        self._rungs_before = (0, 0)

    def recycle(self, batch) -> None:
        """Return a consumed host batch's packed buffers for reuse (same
        contract as NativeHostBatcher.recycle: only after the host->device
        copy has finished and only when device arrays cannot alias host
        memory). Every plane is fully rewritten on reuse, so dirty packs
        are safe."""
        if not isinstance(getattr(batch, "aux", None), np.ndarray):
            return
        if isinstance(batch, DenseBatch):
            if batch.x.dtype != self.dense_dtype:
                return
            self._pool.put(("dense", batch.x.shape[-1]),
                           (batch.x.reshape(self.batch_rows, -1),
                            batch.aux))
        else:
            self._pool.put(("csr", batch.big.shape[-1]),
                           (batch.big, batch.aux))

    def _block_to_parts(self, b) -> tuple:
        lens = np.diff(b.offset).astype(np.int32)
        # the device layout is int32; a feature id >= 2^31 would wrap
        # negative in the astype below and scatter to a wrong column —
        # refuse loudly instead (same contract as qid below; the native
        # batcher enforces this in PaddedBatcher::Accumulate). Reference
        # data.h:26-32 makes index width a first-class contract.
        if b.nnz:
            mx = int(getattr(b, "max_index", 0)) or int(b.index.max())
            if mx > np.iinfo(np.int32).max:
                raise DMLCError(
                    f"feature index {mx} exceeds the int32 device layout "
                    f"(max {np.iinfo(np.int32).max}); remap feature ids "
                    f"below 2^31 for the TPU batch layout")
        col = b.index.astype(np.int32, copy=True)
        val = (b.value.astype(np.float32, copy=True) if b.value is not None
               else np.ones(b.nnz, dtype=np.float32))
        label = b.label.astype(np.float32, copy=True)
        weight = (b.weight.astype(np.float32, copy=True)
                  if b.weight is not None
                  else np.ones(b.num_rows, dtype=np.float32))
        # qid/field stay None for blocks without them (no sentinel traffic
        # on the common qid/field-free path); sentinels materialize at batch
        # assembly only when the stream carries the column somewhere
        qid = fld = None
        if b.qid is not None:
            self._has_qid = True
            if b.qid.max(initial=0) > np.iinfo(np.int32).max:
                raise DMLCError(
                    f"qid {int(b.qid.max())} exceeds the int32 device "
                    f"layout")  # native path enforces the same (batcher.cc)
            qid = b.qid.astype(np.int32)
        if b.field is not None:
            self._has_field = True
            fld = b.field.astype(np.int32)
        return label, weight, lens, col, val, qid, fld

    def next_batch(self) -> Optional[PaddedBatch]:
        """Produce the next PaddedBatch of numpy arrays (None at end)."""
        while self._pending_rows < self.batch_rows and not self._done:
            b = self.parser.next_block()
            if b is None:
                self._done = True
                break
            self._pending.append(self._block_to_parts(b))
            self._pending_rows += len(self._pending[-1][0])
        if self._pending_rows == 0:
            return None

        if self._emit_qid is None:
            self._emit_qid, self._emit_field = self._has_qid, self._has_field
        elif (self._has_qid and not self._emit_qid) or (
                self._has_field and not self._emit_field):
            raise DMLCError(
                "qid/field column appeared mid-stream after the batch "
                "structure was pinned without it; order the inputs so the "
                "first batch carries the column")

        take = min(self.batch_rows, self._pending_rows)
        parts = []  # per-piece tuples, same layout as _pending entries
        got = 0

        def sl(arr, stop=None, start=None):
            if arr is None:
                return None
            return arr[start:] if start is not None else arr[:stop]

        while got < take:
            label, weight, lens, col, val, qid, fld = self._pending[0]
            n = len(label)
            if got + n <= take:
                self._pending.pop(0)
                parts.append((label, weight, lens, col, val, qid, fld))
                got += n
            else:
                keep = take - got
                nnz_keep = int(lens[:keep].sum())
                parts.append((label[:keep], weight[:keep], lens[:keep],
                              col[:nnz_keep], val[:nnz_keep], sl(qid, keep),
                              sl(fld, nnz_keep)))
                self._pending[0] = (label[keep:], weight[keep:], lens[keep:],
                                    col[nnz_keep:], val[nnz_keep:],
                                    sl(qid, start=keep),
                                    sl(fld, start=nnz_keep))
                got = take
        self._pending_rows -= take

        label, weight, lens, col, val = (
            np.concatenate([p[i] for p in parts]) for i in range(5))
        # sentinel backfill only when the stream carries the column at all
        qid = (np.concatenate(
            [p[5] if p[5] is not None else np.full(len(p[0]), -1, np.int32)
             for p in parts]) if self._emit_qid
            else np.empty(0, np.int32))
        fld = (np.concatenate(
            [p[6] if p[6] is not None else np.zeros(len(p[3]), np.int32)
             for p in parts]) if self._emit_field
            else np.empty(0, np.int32))

        D = self.num_shards
        R = self.batch_rows // D
        # pad rows to full batch (weight 0 ⇒ no gradient contribution)
        if take < self.batch_rows:
            pad = self.batch_rows - take
            label = np.concatenate([label, np.zeros(pad, np.float32)])
            weight = np.concatenate([weight, np.zeros(pad, np.float32)])
            lens = np.concatenate([lens, np.zeros(pad, np.int32)])
            if self._emit_qid:
                qid = np.concatenate([qid, np.full(pad, -1, np.int32)])

        if self.layout == "auto":
            # decide once, on the first batch: dense when the feature space
            # is small (the MXU path); sticky so device shapes stay static.
            # field-aware data always stays CSR (no dense field plane)
            max_idx = int(col.max()) if len(col) else 0
            self.layout = ("dense" if not self._emit_field
                           and max_idx + 1 <= self.dense_max_features
                           else "csr")
        if self.layout == "dense":
            if self._emit_field:
                raise DMLCError(
                    "field ids have no dense layout; pass layout='csr' for "
                    "field-aware (libfm) data")
            return self._emit_dense(take, label, weight, lens, col, val, qid)

        # split nnz by shard; bucket to the max shard nnz
        row_of = np.repeat(np.arange(self.batch_rows, dtype=np.int32), lens)
        shard_starts = np.concatenate(
            [[0], np.cumsum(lens.reshape(D, R).sum(axis=1))]).astype(np.int64)
        shard_nnz = np.diff(shard_starts)
        own = nnz_bucket(int(shard_nnz.max()) if take else 1,
                         self.min_nnz_bucket)
        # a short last batch takes no rung below the batch before it
        nnz_before, cols_before = self._rungs_before
        bucket = tail_rung(own, nnz_before, take, self.batch_rows)

        # assemble straight into the packed layout (the same big/aux
        # contract the native batchers emit, so index64 batches cross
        # host->HBM in as few transfers); pooled packs are fully
        # rewritten below, so reuse needs no clearing beyond the fills
        Kb = 4 if self._emit_field else 3
        big = aux_buf = None
        pooled = self._pool.pop(("csr", bucket))
        if pooled is not None:
            big, aux_buf = pooled
            if big.shape[1] != Kb:
                big = None
        if big is None:
            big = _aligned_empty((D, Kb, bucket), np.int32)
        row, slotp, valp, fldp = _view_big(big)
        row[:] = R  # R = padding segment
        valp[:] = 0.0
        if fldp is not None:
            fldp[:] = 0
        for d in range(D):
            lo, hi = shard_starts[d], shard_starts[d + 1]
            n = hi - lo
            row[d, :n] = row_of[lo:hi] - d * R  # local row ids
            slotp[d, :n] = col[lo:hi]
            valp[d, :n] = val[lo:hi]
            if fldp is not None:
                fldp[d, :n] = fld[lo:hi]
        # the columns become the distinct lists, the plane their slots
        owners = self.col_owners[0]
        cols, distinct = col_slots(slotp, shard_nnz, self.min_nnz_bucket,
                                   *self.col_owners)
        U = tail_rung(cols.shape[1], cols_before, take, self.batch_rows)
        lifted = bucket != own or U != cols.shape[1]
        if U != cols.shape[1]:  # the list's own padding, to the rung before
            c0, c1 = cols.shape[1] // owners, U // owners
            wide = _aligned_empty((D, U), np.int32)
            wide[:] = np.iinfo(np.int32).max
            wide.reshape(D, owners, c1)[:, :, :c0] = cols.reshape(D, owners,
                                                                  c0)
            if owners > 1:  # a slot moves with its stretch
                slotp[:] = slotp // c0 * c1 + slotp % c0
            cols = wide
        self._rungs_before = (bucket, cols.shape[1])
        owner_max = int(owner_counts(cols, shard_nnz, owners).max()) \
            if owners > 1 else 0

        nrows = np.minimum(
            np.maximum(take - np.arange(D) * R, 0), R).astype(np.int32)
        aux, label_v, weight_v, qid_v = _pack_aux(
            label, weight, qid, nrows, D, R, self._emit_qid, aux=aux_buf)
        return PaddedBatch(
            row=row, slot=slotp, cols=cols, val=valp,
            label=label_v, weight=weight_v,
            nrows=nrows, total_rows=int(take),
            total_nnz=int(shard_starts[-1]), total_distinct=distinct,
            tail_lifted=lifted, owners=owners, owner_max=owner_max,
            qid=qid_v, field=fldp, big=big, aux=aux)

    def _emit_dense(self, take, label, weight, lens, col, val, qid):
        D = self.num_shards
        R = self.batch_rows // D
        if self._num_features is None:
            self._num_features = int(col.max()) + 1 if len(col) else 1
        F = self._num_features
        mx = int(col.max()) + 1 if len(col) else 1
        if mx > F:
            raise DMLCError(
                f"dense layout fixed at {F} features but saw index {mx - 1}; "
                f"pass layout='csr' or a larger dense_max_features")
        x = aux_buf = None
        pooled = self._pool.pop(("dense", F))
        if pooled is not None:
            x, aux_buf = pooled
            x.fill(0)  # the scatter below only touches present entries
        if x is None:
            x = _aligned_empty((self.batch_rows, F), self.dense_dtype)
            x.fill(0)
        row_of = np.repeat(np.arange(self.batch_rows, dtype=np.int64), lens)
        x[row_of, col] = val
        nrows = np.minimum(
            np.maximum(take - np.arange(D) * R, 0), R).astype(np.int32)
        aux, label_v, weight_v, qid_v = _pack_aux(
            label, weight, qid, nrows, D, R, self._emit_qid, aux=aux_buf)
        return DenseBatch(
            x=x.reshape(D, R, F),
            label=label_v, weight=weight_v,
            nrows=nrows, total_rows=int(take),
            qid=qid_v, aux=aux)

    def reset(self) -> None:
        """Restart batching from the first row (new epoch)."""
        self.parser.before_first()
        self._pending.clear()
        self._pending_rows = 0
        self._done = False
        self._rungs_before = (0, 0)

    def set_epoch(self, epoch: int) -> bool:
        """Pin the shuffle permutation the next reset() samples (mid-epoch
        resume). False when the underlying split chain does not shuffle."""
        return self.parser.set_epoch(epoch)


def _native_cols(native, pool: _HostBufferPool, D: int, owners: int = 1):
    """The distinct-column lists of the batch a native ``fill_packed`` just
    wrote (its col plane now holds the slots), in a pooled [D, U] buffer;
    returns (cols, distinct count, whether a short batch was lifted to the
    rungs of the batch before it, the fullest owner's count where the
    columns have several owners and 0 otherwise)."""
    U, distinct, lifted = native.cols_meta()
    cols = pool.pop(("cols", U))
    if cols is None:
        cols = _aligned_empty((D, U), np.int32)
    native.fill_cols(cols)
    return (cols, distinct, lifted,
            native.cols_owner_max() if owners > 1 else 0)


class NativeHostBatcher:
    """HostBatcher drop-in backed by the C++ PaddedBatcher (cpp/src/batcher.h).

    The splitting/merging/padding that HostBatcher does with per-block numpy
    concatenation happens natively in one pass per batch: next_meta() stages
    a batch and reports its static shape, Python allocates the numpy arrays,
    and fill_* writes them with the GIL released. On a single host core this
    roughly halves the non-parse overhead of the ingest pipeline."""

    def __init__(self, uri: str, part: int = 0, npart: int = 1,
                 fmt: str = "auto", nthread: int = 0,
                 batch_rows: int = 65536, num_shards: int = 1,
                 min_nnz_bucket: int = 4096, layout: str = "auto",
                 dense_max_features: int = 512, dense_dtype=np.float32,
                 csr_val_dtype: str = "f32",
                 col_owners: Tuple[int, int] = (1, 0)):
        if batch_rows % num_shards != 0:
            raise DMLCError(
                f"batch_rows={batch_rows} must divide by shards={num_shards}")
        if layout not in ("auto", "csr", "dense"):
            raise DMLCError(f"unknown layout {layout!r}")
        if csr_val_dtype not in ("f32", "bf16"):
            raise DMLCError(f"unknown csr_val_dtype {csr_val_dtype!r} "
                            f"(expected 'f32' or 'bf16')")
        self._b = NativeBatcher(uri, part=part, npart=npart, fmt=fmt,
                                nthread=nthread, batch_rows=batch_rows,
                                num_shards=num_shards,
                                min_nnz_bucket=min_nnz_bucket)
        self._owners = col_owners[0]
        if self._owners > 1:
            self._b.set_col_owners(*col_owners)
        self.batch_rows = batch_rows
        self.num_shards = num_shards
        self.layout = layout
        self.dense_max_features = dense_max_features
        self.dense_dtype = _dense_dtype_of(dense_dtype)
        self._num_features: Optional[int] = None
        # bf16 CSR values travel as a separate [D, NNZ] bfloat16 leaf and
        # the int32 pack drops its val plane (the native fill converts
        # f32->bf16 round-to-nearest-even in the same pass — cpp/src/bf16.h)
        self._csr_bf16 = csr_val_dtype == "bf16"
        # plane presence pins on the first batch so the emitted pytree
        # structure (and therefore jitted consumers' traces) stays static
        self._emit_qid: Optional[bool] = None
        self._emit_field: Optional[bool] = None
        # recycled host buffers (see _HostBufferPool contract)
        self._pool = _HostBufferPool()

    def next_batch(self):
        """Produce the next static-shape batch of host numpy arrays (None at
        end); buffers come from the recycle pool when available."""
        meta = self._b.next_meta()
        if meta is None:
            return None
        take, bucket, max_index, has_qid, has_field = meta
        if self._emit_qid is None:
            self._emit_qid, self._emit_field = has_qid, has_field
        elif (has_qid and not self._emit_qid) or (
                has_field and not self._emit_field):
            raise DMLCError(
                "qid/field column appeared mid-stream after the batch "
                "structure was pinned without it; order the inputs so the "
                "first batch carries the column")
        has_qid, has_field = self._emit_qid, self._emit_field
        D = self.num_shards
        R = self.batch_rows // D
        if self.layout == "auto":
            # decide once, on the first batch; sticky so shapes stay static.
            # field ids have no dense representation, so field-aware data
            # always takes the CSR layout (batcher.h contract)
            self.layout = ("dense"
                           if not has_field
                           and max_index + 1 <= self.dense_max_features
                           else "csr")
        elif self.layout == "dense" and has_field:
            raise DMLCError(
                "field ids have no dense layout; pass layout='csr' for "
                "field-aware (libfm) data")
        if self.layout == "dense":
            if self._num_features is None:
                self._num_features = max(int(max_index) + 1, 1)
            F = self._num_features
            pooled = self._pool_pop(("dense", F))
            if pooled is not None:
                x, aux, nrows = pooled
            else:
                # the native fill writes float32 or bf16 storage directly
                # (batcher.h x_dtype) — no astype copy on this thread
                x = _aligned_empty((self.batch_rows, F), self.dense_dtype)
                aux = None
                nrows = np.empty(D, np.int32)
            if aux is None or aux.shape[1] != (4 if has_qid else 3):
                aux = _aligned_empty((D, 4 if has_qid else 3, R), np.int32)
            # one fused native pass writes x AND the aux pack (label/weight
            # [/qid]/nrows planes) — no per-plane fills or _finish_aux here
            self._b.fill_dense_packed(x, aux, nrows)
            _, label, weight, qid = _view_aux(aux)
            return DenseBatch(x=x.reshape(D, R, F),
                              label=label, weight=weight,
                              nrows=nrows, total_rows=int(take),
                              qid=qid, aux=aux)
        sep_val = self._csr_bf16
        Kb = 2 + (0 if sep_val else 1) + (1 if has_field else 0)
        pooled = self._pool_pop(("csr", bucket))
        if pooled is not None:
            big, val16, aux, nrows = pooled
        else:
            big, val16, aux = None, None, None
            nrows = np.empty(D, np.int32)
        if big is None or big.shape[1] != Kb:
            big = _aligned_empty((D, Kb, bucket), np.int32)
        if sep_val and (val16 is None or val16.shape != (D, bucket)):
            val16 = _aligned_empty((D, bucket), _bf16_dtype())
        if aux is None or aux.shape[1] != (4 if has_qid else 3):
            aux = _aligned_empty((D, 4 if has_qid else 3, R), np.int32)
        # one fused native pass assembles the whole shard-major batch
        self._b.fill_packed(big, aux, nrows, val=val16 if sep_val else None)
        cols, distinct, lifted, owner_max = _native_cols(
            self._b, self._pool, D, self._owners)
        row, slot, val, field = _view_big(big, has_val=not sep_val)
        _, label, weight, qid = _view_aux(aux)
        return PaddedBatch(row=row, slot=slot, cols=cols,
                           val=val16 if sep_val else val,
                           label=label, weight=weight,
                           nrows=nrows, total_rows=int(take),
                           total_nnz=self._b.batch_nnz(),
                           total_distinct=distinct, tail_lifted=lifted,
                           owners=self._owners, owner_max=owner_max,
                           qid=qid, field=field, big=big, aux=aux,
                           val16=val16)

    # -- host-buffer recycling ---------------------------------------------
    def _pool_pop(self, key):
        return self._pool.pop(key)

    def recycle(self, batch) -> None:
        """Return a consumed host batch's buffers for reuse.

        Callers must guarantee the host->device copy has finished (e.g.
        block_until_ready on the device arrays) and that the device arrays
        no longer alias host memory. DeviceRowBlockIter enforces the
        latter by probing the first transferred batch's device buffer
        addresses against the host buffers (it no longer assumes which
        backends alias) and, when they overlap, DEFERRING the recycle
        behind weakrefs until the consumer drops the device batch
        (parking-lot overflow drops are counted in
        device_recycle_skipped)."""
        if getattr(batch, "aux", None) is None or \
                not isinstance(batch.aux, np.ndarray):
            return  # foreign/device batch; nothing to pool
        if isinstance(batch, DenseBatch):
            if batch.x.dtype != self.dense_dtype:
                return  # foreign buffer set; drop it
            key = ("dense", batch.x.shape[-1])
            arrs = (batch.x.reshape(self.batch_rows, -1), batch.aux,
                    batch.nrows)
        else:
            key = ("csr", batch.big.shape[-1])
            arrs = (batch.big, batch.val16, batch.aux, batch.nrows)
            self._pool.put(("cols", batch.cols.shape[-1]), batch.cols)
        self._pool.put(key, arrs)

    def reset(self) -> None:
        """Restart batching from the first row (new epoch); the recycle pool
        survives."""
        self._b.before_first()

    def set_epoch(self, epoch: int) -> bool:
        """Pin the shuffle permutation the next reset() samples (mid-epoch
        resume). False when the underlying split chain does not shuffle."""
        return self._b.set_epoch(epoch)

    def bytes_read(self) -> int:
        """Bytes consumed from the underlying source so far."""
        return self._b.bytes_read()

    def close(self) -> None:
        """Free the native batcher handle (idempotent)."""
        self._b.close()


class CsrRecHostBatcher:
    """Host batcher over the zero-rearrangement CSR lane (cpp/src/
    csr_rec.h): records store col/val/row-length planes in device layout,
    so next_batch() is bulk memcpy + row-id expansion straight into the
    packed big/aux buffers. The per-shard nnz bucket is STATIC for the
    epoch (the file's window table bounds it), so every batch compiles to
    one device shape. Emits the same PaddedBatch as the CSR text path."""

    def __init__(self, uri: str, part: int = 0, npart: int = 1,
                 batch_rows: int = 65536, num_shards: int = 1,
                 min_nnz_bucket: int = 4096,
                 col_owners: Tuple[int, int] = (1, 0)):
        if batch_rows % num_shards != 0:
            raise DMLCError(
                f"batch_rows={batch_rows} must divide by shards="
                f"{num_shards}")
        self._b = NativeCsrRecBatcher(uri, part=part, npart=npart,
                                      batch_rows=batch_rows,
                                      num_shards=num_shards,
                                      min_nnz_bucket=min_nnz_bucket)
        self._owners = col_owners[0]
        if self._owners > 1:
            self._b.set_col_owners(*col_owners)
        self.batch_rows = batch_rows
        self.num_shards = num_shards
        self._meta = None
        self._pool = _HostBufferPool()

    def recycle(self, batch) -> None:
        """Return a consumed host batch's buffers for reuse (same contract
        as NativeHostBatcher.recycle)."""
        if not isinstance(batch, PaddedBatch) or \
                not isinstance(getattr(batch, "aux", None), np.ndarray):
            return
        self._pool.put(("crec", batch.big.shape[-1]),
                       (batch.big, batch.aux, batch.nrows))
        self._pool.put(("cols", batch.cols.shape[-1]), batch.cols)

    def next_batch(self) -> Optional[PaddedBatch]:
        """Next static-shape PaddedBatch of host numpy arrays (None at
        end); the fill is one GIL-released native pass."""
        if self._meta is None:
            self._meta = self._b.meta()
        bucket, _, has_qid, has_field = self._meta
        D = self.num_shards
        R = self.batch_rows // D
        pooled = self._pool.pop(("crec", bucket))
        if pooled is not None:
            big, aux, nrows = pooled
        else:
            big = _aligned_empty((D, 4 if has_field else 3, bucket),
                                 np.int32)
            aux = _aligned_empty((D, 4 if has_qid else 3, R), np.int32)
            nrows = np.empty(D, np.int32)
        # one fused native pass writes both shard-major packs
        take = self._b.fill_packed(big, aux, nrows)
        if take == 0:
            return None
        cols, distinct, lifted, owner_max = _native_cols(
            self._b, self._pool, D, self._owners)
        row, slot, val, field = _view_big(big)
        _, label, weight, qid = _view_aux(aux)
        return PaddedBatch(row=row, slot=slot, cols=cols, val=val,
                           label=label, weight=weight,
                           nrows=nrows, total_rows=int(take),
                           total_nnz=self._b.batch_nnz(),
                           total_distinct=distinct, tail_lifted=lifted,
                           owners=self._owners, owner_max=owner_max,
                           qid=qid, field=field, big=big, aux=aux)

    def reset(self) -> None:
        """Restart from the first record (new epoch); the pool survives."""
        self._b.before_first()

    def set_epoch(self, epoch: int) -> bool:
        """Pin the shuffle permutation the next reset() samples."""
        return self._b.set_epoch(epoch)

    def bytes_read(self) -> int:
        """Record bytes consumed from the source so far."""
        return self._b.bytes_read()

    def close(self) -> None:
        """Free the native handle (idempotent)."""
        self._b.close()


class DenseRecHostBatcher:
    """Host batcher over the zero-parse dense lane (cpp/src/dense_rec.h):
    records store [rows, F] matrices in device layout, so next_batch() is
    record framing + bulk memcpy into (pooled) numpy buffers. Emits the
    same DenseBatch the dense text path produces — downstream consumers
    cannot tell the lanes apart."""

    def __init__(self, uri: str, part: int = 0, npart: int = 1,
                 batch_rows: int = 65536, num_shards: int = 1,
                 dense_dtype=np.float32):
        if batch_rows % num_shards != 0:
            raise DMLCError(
                f"batch_rows={batch_rows} must divide by shards="
                f"{num_shards}")
        self._b = NativeDenseRecBatcher(uri, part=part, npart=npart,
                                        batch_rows=batch_rows,
                                        num_shards=num_shards)
        self.batch_rows = batch_rows
        self.num_shards = num_shards
        self.dense_dtype = _dense_dtype_of(dense_dtype)
        self._F: Optional[int] = None
        self._pool = _HostBufferPool()

    def recycle(self, batch) -> None:
        """Return a consumed host batch's buffers for reuse (same contract
        as NativeHostBatcher.recycle: only after the host->device copy has
        finished and only when device arrays cannot alias host memory)."""
        if not isinstance(batch, DenseBatch) or \
                not isinstance(getattr(batch, "aux", None), np.ndarray) or \
                batch.x.dtype != self.dense_dtype:
            return
        self._pool.put(("drec", batch.x.shape[-1]),
                       (batch.x.reshape(self.batch_rows, -1), batch.aux,
                        batch.nrows))

    def next_batch(self) -> Optional[DenseBatch]:
        """Next static-shape DenseBatch of host numpy arrays (None at
        end); the fill is one GIL-released native pass."""
        if self._F is None:
            self._F, _, _ = self._b.meta()
            self._F = max(int(self._F), 1)
        F = self._F
        D = self.num_shards
        R = self.batch_rows // D
        pooled = self._pool.pop(("drec", F))
        if pooled is not None:
            x, aux, nrows = pooled
        else:
            x = _aligned_empty((self.batch_rows, F), self.dense_dtype)
            aux = _aligned_empty((D, 3, R), np.int32)
            nrows = np.empty(D, np.int32)
        # one fused native pass writes x and the aux pack
        take = self._b.fill_packed(x, aux, nrows)
        if take == 0:
            return None
        _, label, weight, _ = _view_aux(aux)
        return DenseBatch(x=x.reshape(D, R, F),
                          label=label, weight=weight,
                          nrows=nrows, total_rows=int(take), aux=aux)

    def reset(self) -> None:
        """Restart from the first record (new epoch); the pool survives."""
        self._b.before_first()

    def set_epoch(self, epoch: int) -> bool:
        """Pin the shuffle permutation the next reset() samples. Always
        False today: the dense-rec split does not shuffle."""
        return self._b.set_epoch(epoch)

    def bytes_read(self) -> int:
        """Record bytes consumed from the source so far."""
        return self._b.bytes_read()

    def close(self) -> None:
        """Free the native handle (idempotent)."""
        self._b.close()


class DeviceRowBlockIter:
    """HBM-resident row-block iterator (the TPU-native RowBlockIter).

    reference RowBlockIter<I,D>::Create (data.h:267) parity surface: iterate
    batches, before_first(), bytes_read(); plus device placement. A staging
    thread runs parse+pad (double buffer, capacity `prefetch`); the consumer
    thread issues device_put — by the time XLA finishes step k, batch k+1 is
    staged or already on device.

    ``prefetch=0`` runs the whole path synchronously on the caller's
    thread — no pipeline threads, no queues. The right mode when there is
    nothing to overlap with (single-core hosts, or a measurement of the
    ingest path by itself): each double-buffer handoff is a
    thread wakeup that buys nothing there and can cost more than the
    fused fill it hands over.
    """

    def __init__(self, uri: str, part: int = 0, npart: int = 1,
                 fmt: str = "auto", batch_rows: int = 65536,
                 mesh=None, min_nnz_bucket: int = 4096,
                 index64: bool = False, nthread: int = 0,
                 prefetch: int = 2, to_device: bool = True,
                 layout: str = "auto", dense_max_features: int = 512,
                 dense_dtype=np.float32, csr_val_dtype: str = "f32",
                 col_owners: Tuple[int, int] = (1, 0)):
        """``col_owners``: ``(owners, ids an owner)`` where the consumer
        keeps its tables sharded by key range (``FMLearner.col_owners``
        gives it): every CSR batch's ``cols`` is then owner-major
        (``col_slots``)."""
        self.mesh = mesh
        self.to_device = to_device
        self.batch_rows = batch_rows
        num_shards = 1 if mesh is None else int(mesh.devices.size)
        path_part = uri.split("?", 1)[0].split("#", 1)[0]
        if fmt == "auto" and path_part.endswith(".drec"):
            fmt = "recd"  # dense row-matrix records are self-identifying
        elif fmt == "auto" and path_part.endswith(".crec"):
            fmt = "crec"  # CSR device-plane records (csr_rec.h)
        elif fmt == "auto" and path_part.endswith(".rec"):
            fmt = "rec"  # mirror the native suffix rule (parser.cc Create)
        # determinism keys for mid-epoch resume: the batch count is only a
        # position within THIS stream slicing (state()/restore()). Stored
        # AFTER suffix resolution so a checkpoint taken under fmt="auto"
        # restores into an iterator built with the explicit format.
        self._identity = {"uri": uri, "part": part, "npart": npart,
                          "fmt": fmt, "batch_rows": batch_rows}
        if csr_val_dtype != "f32" and (fmt in ("recd", "crec") or index64):
            raise DMLCError(
                "csr_val_dtype='bf16' is a native text/rec-lane feature "
                "(the fused fill converts values in-pass); the crec/drec "
                "binary lanes and the index64 python batcher keep f32")
        if fmt == "recd":
            # zero-parse dense lane: records already hold device-layout
            # matrices (dense_rec.h); CSR options don't apply
            self.parser = None
            self.batcher = DenseRecHostBatcher(
                uri, part=part, npart=npart, batch_rows=batch_rows,
                num_shards=num_shards, dense_dtype=dense_dtype)
        elif fmt == "crec":
            # zero-rearrangement CSR lane: records hold device-layout
            # col/val/row-length planes (csr_rec.h)
            self.parser = None
            self.batcher = CsrRecHostBatcher(
                uri, part=part, npart=npart, batch_rows=batch_rows,
                num_shards=num_shards, min_nnz_bucket=min_nnz_bucket,
                col_owners=col_owners)
        elif index64:
            # 64-bit parse width; the int32 device layout is still the hard
            # contract — the numpy batcher raises on any id >= 2^31
            # (_block_to_parts guard) instead of wrapping silently
            self.parser = NativeParser(uri, part=part, npart=npart, fmt=fmt,
                                       nthread=nthread, index64=True)
            self.batcher = HostBatcher(self.parser, batch_rows, num_shards,
                                       min_nnz_bucket, index64, layout=layout,
                                       dense_max_features=dense_max_features,
                                       dense_dtype=dense_dtype,
                                       col_owners=col_owners)
        else:
            self.parser = None
            self.batcher = NativeHostBatcher(
                uri, part=part, npart=npart, fmt=fmt, nthread=nthread,
                batch_rows=batch_rows, num_shards=num_shards,
                min_nnz_bucket=min_nnz_bucket, layout=layout,
                dense_max_features=dense_max_features,
                dense_dtype=dense_dtype, csr_val_dtype=csr_val_dtype,
                col_owners=col_owners)
        # per-leaf sharding derived from _PLACEMENT_RULES (every leaf is
        # shard-major, so all take the leading device axis); materialized
        # lazily from the first batch's tree structure
        self.sharding = None
        self._leading_sharding = (None if mesh is None
                                  else batch_sharding(mesh))
        # zero-copy transfer state: DMLC_DEVICE_ZERO_COPY=0 forces the
        # copying device_put path. _placements caches, per (leaf, shape),
        # each device's contiguous leading-axis slice of the host buffer
        # (None when the derived sharding is not leading-axis slicing).
        # _recycle_aliases latches whether this backend's device arrays
        # alias the staging buffers (probed on the first transfer — see
        # _tree_aliases_host).
        self._zero_copy = to_device and env_int(
            "DMLC_DEVICE_ZERO_COPY", 1) != 0
        self._placements: Dict[Any, Any] = {}
        self._recycle_aliases: Optional[bool] = None
        self._recycle_skipped = 0
        # deferred recycling under aliasing (zero-copy backends): host
        # buffers whose device arrays read them in place are parked here
        # behind weakrefs and recycled once the consumer drops the device
        # batch; overflow drops the oldest entry for real (counted in
        # device_recycle_skipped). Touched only by the transfer thread OR
        # the prefetch=0 sync generator, never both.
        self._deferred: list = []
        self._deferred_cap = max(4, prefetch * 2)
        self._prefetch = prefetch
        # two-stage pipeline: parse+pad thread -> _host_q -> transfer thread
        # -> _queue -> consumer. Parsing of batch k+1 overlaps the host->HBM
        # transfer of batch k, which overlaps XLA compute of batch k-1.
        self._host_q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._queue: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._thread: Optional[threading.Thread] = None
        self._xfer_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # mid-epoch resume position (state()/restore())
        self.batches_consumed = 0
        self._skip_batches = 0
        # the next batch is the first after a (re)start: its wait is the
        # one device_first_batch_wait_us keeps
        self._first_wait = True
        # the consumer's holds between batches (telemetry.HoldWatch), and
        # when the put now in flight began: a put that hangs with a long
        # hold says the path to the device stood still
        self._hold = telemetry.HoldWatch()
        self._put_since_us: Optional[float] = None
        # epoch ordinal: selects the shuffle permutation for shuffled URIs
        # (?shuffle_parts= / ?index=&shuffle=1). The split samples epoch 0's
        # permutation at construction; before_first() advances it. state()
        # records it so restore() can replay the exact visit order — a
        # batch prefix under a different permutation is different data.
        self._epoch = 0
        # compile-churn observability: the jax.monitoring listener, once
        # per process (a no-op when the entry point already installed it)
        install_compile_monitor()

    # -- staging threads -----------------------------------------------------
    # Queue ops are stop-aware: a blocking put/get could otherwise race the
    # close-time drain in _join_threads (the drain can steal the very item
    # that would unblock a peer, leaving it waiting forever on an empty
    # queue — the ThreadedIter shutdown hazard, pipeline.h Shutdown).
    _SHUTDOWN = object()

    def _put_stop(self, q: "queue.Queue", item) -> bool:
        """Put unless the iterator is stopping; False when dropped."""
        while True:
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                if self._stop.is_set():
                    return False

    def _get_stop(self, q: "queue.Queue"):
        """Get, or _SHUTDOWN once the iterator is stopping and the queue
        has drained."""
        while True:
            try:
                return q.get(timeout=0.05)
            except queue.Empty:
                if self._stop.is_set():
                    return self._SHUTDOWN

    def _stage_next(self, m) -> Optional[PaddedBatch]:
        """One ``batcher.next_batch()`` under the ``device.stage`` span
        (the end-of-data pull reads ``rows=0`` and is left out of the
        histogram)."""
        if not telemetry.enabled():
            return self.batcher.next_batch()
        with telemetry.span("device.stage") as sp:
            batch = self.batcher.next_batch()
            if batch is not None:
                m["stage_us"].observe(sp.elapsed_us)
            sp.set_arg("rows", 0 if batch is None else batch.total_rows)
        return batch

    def _parse_loop(self) -> None:
        try:
            # mid-epoch resume: burn the recorded prefix on this thread —
            # parsed and discarded, never transferred (restore())
            skip, self._skip_batches = self._skip_batches, 0
            for i in range(skip):
                if self._stop.is_set():  # close() must not wait out a
                    return               # potentially huge resume prefix
                batch = self.batcher.next_batch()
                if batch is None:
                    raise DMLCError(
                        f"restore: resume point ({skip} batches) is past "
                        f"end-of-data (got {i}); the checkpoint and the "
                        f"data stream disagree")
                if hasattr(self.batcher, "recycle"):
                    # discarded host batches never touched the device, so
                    # immediate recycling is safe on any backend
                    self.batcher.recycle(batch)
            m = _get_lane_metrics()
            while not self._stop.is_set():
                # device.stage: one host batch assembly (parse+pad+bucket
                # +pinned pack) on the staging thread — an OPENED span, so
                # it is a profiler annotation too; gated so
                # DMLC_TELEMETRY=0 costs one branch here
                batch = self._stage_next(m)
                if batch is not None:
                    # compile-churn census: a new shape key here is the
                    # batch that re-traces every jitted consumer
                    _note_shape(batch)
                if not self._put_stop(self._host_q, batch):  # None terminates
                    return
                m["host_q"].set(self._host_q.qsize())
                if batch is None:
                    return
        except BaseException as e:  # propagate through the transfer stage
            self._put_stop(self._host_q, e)

    def _transfer_loop(self) -> None:
        try:
            recycle_ok = self.to_device and hasattr(self.batcher, "recycle")
            m = _get_lane_metrics()
            while not self._stop.is_set():
                item = self._get_stop(self._host_q)
                if item is self._SHUTDOWN:
                    return
                if isinstance(item, BaseException) or item is None:
                    self._put_stop(self._queue, item)
                    return
                host = item
                item = self._device_put(host)
                if not self._put_stop(self._queue, item):
                    return
                # double-buffer occupancy, both stages (scrape-time view
                # of where batches pile up)
                m["ready_q"].set(self._queue.qsize())
                m["host_q"].set(self._host_q.qsize())
                if recycle_ok and item is not host:
                    # _device_put blocked until the DMA landed, so the
                    # host buffers are free the moment the device batch
                    # is queued — UNLESS the device arrays alias the
                    # staging memory (zero-copy device_put, any backend
                    # where host and device share an address space). That
                    # is probed from the actual buffer addresses of the
                    # first transferred batch, not assumed from the
                    # backend name; aliased batches defer recycling
                    # until the consumer drops the device arrays.
                    self._recycle_or_defer(host, item, m)
        except BaseException as e:
            self._put_stop(self._queue, e)

    def _recycle_or_defer(self, host, item, m) -> None:
        """Return `host`'s staging buffers to the batcher pool — directly
        when the device arrays are independent copies, or DEFERRED when
        they alias the staging memory (zero-copy backends): the buffers
        are parked behind weakrefs to the device arrays and recycled on a
        later sweep, once the consumer has dropped the device batch.
        Without this, aliasing backends would allocate fresh staging for
        every batch forever — page-fault and allocator churn that can
        cost more than the fill itself. Overflowing the parking lot
        drops the oldest entry for real, counted in
        device_recycle_skipped."""
        if self._recycle_aliases is None:
            self._recycle_aliases = _tree_aliases_host(
                host.tree(), item.tree())
        if not self._recycle_aliases:
            self.batcher.recycle(host)
            return
        self._sweep_deferred()
        refs = tuple(weakref.ref(v) for v in item.tree().values())
        self._deferred.append((host, refs))
        if len(self._deferred) > self._deferred_cap:
            self._deferred.pop(0)
            self._recycle_skipped += 1
            m["recycle_skip"].set(self._recycle_skipped)

    def _sweep_deferred(self) -> None:
        """Recycle parked host batches whose aliasing device arrays have
        all been dropped by the consumer."""
        keep = []
        for host, refs in self._deferred:
            if all(r() is None for r in refs):
                self.batcher.recycle(host)
            else:
                keep.append((host, refs))
        self._deferred = keep

    def _ensure_started(self) -> None:
        telemetry.pulse_start()
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(target=self._parse_loop,
                                            daemon=True)
            self._xfer_thread = threading.Thread(target=self._transfer_loop,
                                                 daemon=True)
            self._thread.start()
            self._xfer_thread.start()

    def _device_put(self, batch: PaddedBatch) -> PaddedBatch:
        if not self.to_device:
            return batch
        tree = batch.tree()
        m = _get_lane_metrics()
        nbytes = sum(int(v.nbytes) for v in tree.values())
        # host->HBM transfer, measured in its two halves for the unified
        # telemetry plane (doc/observability.md "Device lane"): SUBMIT
        # (the device_put dispatch) then BLOCK (dispatch to arrays
        # ready). Blocking here — not in the consumer — means the queue
        # hands over READY batches, so device.wait cleanly reads
        # "staging/transfer behind" and host-buffer recycling is
        # deterministic; the DMA for batch k still overlaps the
        # consumer's compute of batch k-1 (the double buffer), and
        # back-to-back dispatches bought nothing — transfers serialize
        # on the one host->device stream anyway. Timed spans are gated;
        # the block itself is unconditional (semantics must not depend
        # on DMLC_TELEMETRY).
        tel = telemetry.enabled()
        if tel:
            self._put_since_us = time.perf_counter() * 1e6
        try:
            # the parent span is OPENED (telemetry.span), not emitted
            # post-hoc, so the submit/block children below genuinely
            # parent under its id in the ring — offline consumers of the
            # `parent` field see the nesting, not just Perfetto's
            # timestamp containment
            with telemetry.span("device.put", bytes=nbytes):
                t0 = time.perf_counter() if tel else None
                self._ensure_sharding(tree)
                if self._zero_copy:
                    try:
                        tree = self._zero_copy_put(tree)
                        m["zc_batches"].inc()
                    except _ZeroCopyIneligible as e:
                        telemetry.counter(
                            "device_zero_copy_fallbacks_total",
                            {"reason": e.reason}).inc()
                        tree = self._copy_put(tree)
                else:
                    tree = self._copy_put(tree)
                t1 = time.perf_counter() if tel else None
                jax.block_until_ready(list(tree.values()))
                if t0 is not None:
                    t2 = time.perf_counter()
                    m["transfer_us"].observe((t2 - t0) * 1e6)
                    m["submit_us"].observe((t1 - t0) * 1e6)
                    m["block_us"].observe((t2 - t1) * 1e6)
                    # same measurement, second surface: the span ring
                    # (doc/observability.md "Distributed tracing")
                    telemetry.emit_span("device.put.submit", t0 * 1e6,
                                        (t1 - t0) * 1e6)
                    telemetry.emit_span("device.put.block", t1 * 1e6,
                                        (t2 - t1) * 1e6)
        except BaseException:
            # counted + flight-dumped like host-side aborts (the
            # postmortem carries the span ring that shows which batch,
            # how far through the stream, and on what shape it died)
            m["failures"].inc()
            telemetry.flight_dump("device-put-failure")
            raise
        finally:
            self._put_since_us = None
        m["batches"].inc()
        m["bytes"].inc(nbytes)
        cls = type(batch)
        kwargs = dict(tree)
        if cls is PaddedBatch:
            # the fill share: entries sent against entries that were real,
            # both from what the fill counted (no pass over the batch)
            plane = batch.big[:, 0] if batch.row is None else batch.row
            m["nnz_sent"].inc(plane.size)
            m["nnz_real"].inc(batch.total_nnz)
            m["cols_distinct"].inc(batch.total_distinct)
            if batch.tail_lifted:
                m["tail_batches"].inc()
            if batch.owners > 1:
                # several key-range owners: the fullest one's columns, and
                # the stretches' fill (positions sent against columns real)
                m["owner_max"].inc(batch.owner_max)
                m["stretch_sent"].inc(batch.cols.size)
                m["stretch_real"].inc(batch.total_distinct)
                kwargs["owners"] = batch.owners
                kwargs["owner_max"] = batch.owner_max
            kwargs["total_nnz"] = batch.total_nnz
            kwargs["total_distinct"] = batch.total_distinct
            kwargs["tail_lifted"] = batch.tail_lifted
        if "val" in kwargs and "aux" in kwargs:
            # the packed tree's separate bf16 value leaf rides the val16
            # field so the device batch's tree() re-emits it
            kwargs["val16"] = kwargs.pop("val")
        return cls(total_rows=batch.total_rows, **kwargs)

    # -- zero-copy transfer --------------------------------------------------
    def _ensure_sharding(self, tree) -> None:
        if self._leading_sharding is None:
            return
        if self.sharding is None or set(self.sharding) != set(tree):
            self.sharding = match_placement_rules(self.mesh, tree)

    def _copy_put(self, tree):
        """The plain (copying) transfer: one device_put over the tree."""
        if self.sharding is not None:
            return jax.device_put(tree, self.sharding)
        return jax.device_put(tree)

    def _placement_table(self, key, shape, ns):
        """Per-device (device, lo, hi) leading-axis slices of leaf `key`
        under NamedSharding `ns`, derived from devices_indices_map and
        cached per (key, shape). None when the sharding does not slice
        the leading axis contiguously (zero-copy ineligible)."""
        ck = (key, shape)
        if ck in self._placements:
            return self._placements[ck]
        entries, ok = [], True
        try:
            imap = ns.devices_indices_map(shape)
        except Exception:
            imap, ok = None, False
        if ok:
            for dev, idx in imap.items():
                lead = idx[0] if idx else slice(None)
                rest = idx[1:] if idx else ()
                full_rest = all(
                    s.start in (None, 0) and s.step in (None, 1)
                    and s.stop in (None, shape[j + 1])
                    for j, s in enumerate(rest))
                if not idx or lead.step not in (None, 1) or not full_rest:
                    ok = False
                    break
                lo = 0 if lead.start is None else int(lead.start)
                hi = shape[0] if lead.stop is None else int(lead.stop)
                entries.append((dev, lo, hi))
        table = tuple(entries) if ok else None
        self._placements[ck] = table
        return table

    def _zero_copy_put(self, tree):
        """Transfer the batch without copying host memory: device_put of a
        64-byte-aligned C-contiguous numpy buffer lets the runtime alias
        it (DMA reads the staging memory in place), and under a mesh each
        device gets its own contiguous shard-major slab via the placement
        table + make_array_from_single_device_arrays — no host gather, no
        repack. Raises _ZeroCopyIneligible (counted, then the copying
        path runs) rather than silently degrading."""
        for v in tree.values():
            if not isinstance(v, np.ndarray) or \
                    not v.flags["C_CONTIGUOUS"]:
                raise _ZeroCopyIneligible("non_contiguous_host")
        if self.sharding is None:
            for v in tree.values():
                if v.ctypes.data % 64:
                    raise _ZeroCopyIneligible("unaligned")
            # one dispatch for the whole tree: each aligned leaf is
            # aliased individually; the single call just saves the
            # per-leaf Python round trip
            return jax.device_put(tree)
        out = {}
        for k, v in tree.items():
            ns = self.sharding[k]
            table = self._placement_table(k, v.shape, ns)
            if table is None:
                raise _ZeroCopyIneligible("non_leading_partition")
            shards = []
            for dev, lo, hi in table:
                piece = v[lo:hi]
                if piece.ctypes.data % 64:
                    # shard slab sizes that are not 64-byte multiples
                    # misalign every shard after the first
                    raise _ZeroCopyIneligible("unaligned")
                shards.append(jax.device_put(piece, dev))
            out[k] = jax.make_array_from_single_device_arrays(
                v.shape, ns, shards)
        return out

    def _iter_sync(self) -> Iterator[PaddedBatch]:
        """prefetch=0: parse+fill, device_put, and consumption inline on
        the caller's thread (see the class docstring). Same semantics as
        the threaded path — stage spans, shape census, resume-prefix
        burning, alias-probed recycling — minus the queues."""
        m = _get_lane_metrics()
        recycle_ok = self.to_device and hasattr(self.batcher, "recycle")
        skip, self._skip_batches = self._skip_batches, 0
        for i in range(skip):
            batch = self.batcher.next_batch()
            if batch is None:
                raise DMLCError(
                    f"restore: resume point ({skip} batches) is past "
                    f"end-of-data (got {i}); the checkpoint and the "
                    f"data stream disagree")
            if hasattr(self.batcher, "recycle"):
                self.batcher.recycle(batch)
        telemetry.pulse_start()
        hold = self._hold
        try:
            while True:
                # inline, every batch is waited for in full; only the first
                # after a (re)start is recorded as a device.wait, for
                # device_first_batch_wait_us
                first, self._first_wait = self._first_wait, False
                with (telemetry.span("device.wait", first=1)
                      if first and telemetry.enabled() else _NO_SPAN) as sp:
                    host = self._stage_next(m)
                    if host is None:
                        return
                    _note_shape(host)
                    item = self._device_put(host)
                    if sp is not None:
                        m["first_wait_us"].observe(sp.elapsed_us)
                self.batches_consumed += 1
                if recycle_ok and item is not host:
                    # same alias-probed direct-or-deferred recycling as the
                    # transfer thread: _device_put blocked until the DMA
                    # landed, so the host buffers are refillable unless the
                    # device arrays alias them — in which case they are
                    # parked and reclaimed once the consumer drops `item`
                    self._recycle_or_defer(host, item, m)
                # device.hold: from handing the batch over to being asked
                # for the next (recorded post hoc: an opened span may not
                # straddle a yield)
                hold.begin()
                yield item
                hold.end()
        finally:
            hold.abandon()  # a dropped generator records no hold

    def __iter__(self) -> Iterator[PaddedBatch]:
        if self._prefetch == 0:
            yield from self._iter_sync()
            return
        self._ensure_started()
        m = _get_lane_metrics()
        hold = self._hold
        try:
            while True:
                # device.wait: consumer head-of-line — the time this thread
                # stood idle because staging/transfer had not delivered the
                # next READY batch. The complement of these intervals is
                # the consumer's holds (device.hold below), which is what
                # the overlap ratio (telemetry.device_overlap_ratio)
                # intersects device.put spans against. An OPENED span (a
                # profiler annotation too); the first wait after a
                # (re)start carries first=1 and also feeds
                # device_first_batch_wait_us: how late an epoch's first
                # batch comes.
                if telemetry.enabled():
                    first, self._first_wait = self._first_wait, False
                    with telemetry.span(
                            "device.wait",
                            **({"first": 1} if first else {})) as sp:
                        item = self._queue.get()
                        dur_us = sp.elapsed_us
                    m["wait_us"].observe(dur_us)
                    if first:
                        m["first_wait_us"].observe(dur_us)
                else:
                    item = self._queue.get()
                m["ready_q"].set(self._queue.qsize())
                if item is None:
                    self._thread = None
                    self._xfer_thread = None
                    return
                if isinstance(item, BaseException):
                    self._thread = None
                    self._xfer_thread = None
                    raise item
                self.batches_consumed += 1
                # device.hold: from handing the batch over to being asked
                # for the next: the consumer's step and whatever else it
                # does (recorded post hoc: an opened span may not straddle
                # a yield). With device.wait it is the consumer's whole time
                hold.begin()
                yield item
                hold.end(self._put_since_us)
        finally:
            hold.abandon()  # a dropped generator records no hold

    # -- mid-epoch checkpoint/resume ----------------------------------------
    def state(self) -> Dict[str, Any]:
        """Resume point for mid-epoch checkpointing: the number of batches
        yielded this epoch plus the determinism keys (uri/part/npart/fmt/
        batch_rows) that make the count a position. Save it next to the
        model checkpoint (utils/checkpoint.py) and hand it to restore()
        after a preemption — the TPU-pod recovery story."""
        return dict(self._identity, batches_consumed=self.batches_consumed,
                    epoch=self._epoch)

    def restore(self, state: Dict[str, Any]) -> None:
        """Rewind to the epoch start, then skip `state['batches_consumed']`
        batches HOST-SIDE on the staging thread (parsed/filled and
        discarded — never transferred to the device), so iteration resumes
        exactly where state() was captured. Raises if any recorded
        determinism key (batch_rows/part/npart/uri/fmt) disagrees with
        this iterator — batch k of a different stream slicing is different
        data, and resuming there would silently skip and duplicate rows —
        or, at iteration time, if the resume point lies past end-of-data."""
        for key, ours in self._identity.items():
            theirs = state.get(key, ours)
            if theirs != ours:
                raise DMLCError(
                    f"restore: checkpoint was taken with {key}={theirs!r} "
                    f"but this iterator uses {ours!r}; resuming a batch "
                    f"count across a different stream slicing would read "
                    f"the wrong rows")
        # replay the checkpoint's epoch so shuffled URIs rewind into the
        # SAME permutation the prefix was counted under (split-level
        # SetShuffleEpoch; no-op for unshuffled streams, where ordering is
        # epoch-independent)
        self._epoch = int(state.get("epoch", 0))
        self._reset_stream()
        self._skip_batches = int(state.get("batches_consumed", 0))
        self.batches_consumed = self._skip_batches

    def _join_threads(self) -> None:
        self._stop.set()
        for th, q in ((self._thread, self._host_q),
                      (self._xfer_thread, self._queue)):
            if th is None:
                continue
            while th.is_alive():
                try:  # drain so a blocked put can finish
                    q.get_nowait()
                except queue.Empty:
                    pass
                th.join(timeout=0.02)
        self._thread = None
        self._xfer_thread = None
        for q in (self._host_q, self._queue):
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
        # reclaim what the consumer has released; drop the rest (their
        # device arrays may still alias the staging memory)
        self._sweep_deferred()
        self._deferred = []
        self._stop.clear()

    def before_first(self) -> None:
        """Restart iteration as the next epoch (reference
        DataIter::BeforeFirst; shuffled URIs resample their permutation)."""
        self._epoch += 1
        if not telemetry.enabled():
            self._reset_stream()
            return
        # device.epoch_turnover: thread join + batcher reset, per epoch
        with telemetry.span("device.epoch_turnover", epoch=self._epoch) as sp:
            self._reset_stream()
            _get_lane_metrics()["turnover_us"].observe(sp.elapsed_us)

    def _reset_stream(self) -> None:
        """Rewind to the start of epoch ``self._epoch``."""
        self._join_threads()
        self._first_wait = True
        if hasattr(self.batcher, "set_epoch"):
            # pin the permutation deterministically to the epoch ordinal
            # (instead of the split's own BeforeFirst counter, which a
            # process restart would silently reset to 0)
            self.batcher.set_epoch(self._epoch)
        self.batcher.reset()
        self.batches_consumed = 0
        self._skip_batches = 0

    def bytes_read(self) -> int:
        """Bytes consumed from the underlying source so far."""
        if self.parser is not None:
            return self.parser.bytes_read()
        return self.batcher.bytes_read()

    def close(self) -> None:
        """Stop staging threads and free native resources (idempotent)."""
        self._join_threads()
        if self.parser is not None:
            self.parser.close()
        else:
            self.batcher.close()

    def abort_drain(self, reason: str = "tracker-abort") -> None:
        """Abort-path teardown with a BOUNDED wall clock
        (``DMLC_DEVICE_ABORT_DRAIN_MS``, default 2000 ms), for the
        TrackerAbortedError path (doc/robustness.md "Elastic mesh
        training"): a survivor of a dead mesh peer must drain this
        pipeline and exit promptly, even if a staging/transfer thread is
        parked inside a device_put it cannot finish.

        Differs from the cooperative :meth:`_join_threads` in two ways —
        thread joins give up at the deadline (daemon threads; the
        process is about to exit anyway), and the zero-copy parking lot
        is force-dropped: parked staging buffers whose device arrays are
        still live are LEAKED to the allocator rather than recycled,
        because recycling memory a device array still aliases would
        corrupt whatever the abort handler reads from it. Counted in
        ``device_abort_drains_total``; idempotent, and close() stays
        safe to call after."""
        deadline = time.monotonic() + max(
            1, env_int("DMLC_DEVICE_ABORT_DRAIN_MS", 2000)) / 1000.0
        self._stop.set()
        joined = True
        for th, q in ((self._thread, self._host_q),
                      (self._xfer_thread, self._queue)):
            if th is None:
                continue
            while th.is_alive():
                if time.monotonic() > deadline:
                    joined = False
                    break
                try:  # drain so a blocked put can finish
                    q.get_nowait()
                except queue.Empty:
                    pass
                th.join(timeout=0.02)
        self._thread = None
        self._xfer_thread = None
        for q in (self._host_q, self._queue):
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
        # reclaim what the consumer released; FORCE-DROP the rest — their
        # device arrays may still alias the staging memory, so the
        # buffers leak to the allocator instead of returning to the pool
        self._sweep_deferred()
        dropped = len(self._deferred)
        self._deferred = []
        if joined:
            # only a fully-stopped pipeline may rearm; a straggler thread
            # still sees _stop and exits on its own
            self._stop.clear()
        telemetry.counter("device_abort_drains_total").inc()
        telemetry.flight_dump(
            f"device-abort-drain: {reason} (threads "
            f"{'joined' if joined else 'abandoned at deadline'}, "
            f"{dropped} parked buffer(s) dropped)")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class ElasticDeviceRowBlockIter:
    """Lease data-plane × device pipeline: the elastic-mesh input glue
    (doc/robustness.md "Elastic mesh training").

    Where :class:`~dmlc_core_tpu.data.ElasticRowBlockIter` feeds HOST
    consumers from tracker shard leases, this feeds the DEVICE: each
    granted shard becomes a :class:`DeviceRowBlockIter` over
    ``part=shard, npart=num_shards`` with the PR 16 spec-driven sharded
    placement, so per-mesh-axis data shards flow lease → batcher →
    device with no host gather. Yields ``(shard, device_batch)`` pairs;
    a shard's lease completes only after its last batch was yielded
    (the exactly-once checkout survives a consumer death mid-shard —
    the tracker reclaims and re-grants the shard).

    On TrackerAbortedError — from acquire, or surfaced by the monitor
    mid-shard — the live device pipeline is torn down through
    :meth:`DeviceRowBlockIter.abort_drain` (bounded wall clock, parking
    lot force-dropped) and the error propagates. ``abort_drain`` on this
    iterator is safe from another thread, so it slots directly into a
    :class:`~dmlc_core_tpu.parallel.elastic.StepWatchdog` drain list."""

    def __init__(self, uri: str, num_shards: Optional[int] = None,
                 monitor=None, epoch: int = 0,
                 acquire_timeout: Optional[float] = None,
                 **device_kwargs):
        from dmlc_core_tpu.tracker.client import current_monitor
        self.uri = uri
        self._monitor = monitor if monitor is not None else current_monitor()
        if self._monitor is None:
            raise DMLCError(
                "ElasticDeviceRowBlockIter needs a heartbeat channel "
                "(rendezvous with heartbeat=True under an elastic "
                "tracker) — without leases there is no shard source")
        self.num_shards = num_shards if num_shards is not None \
            else env_int("DMLC_TRACKER_NUM_SHARDS", 0)
        if self.num_shards <= 0:
            raise DMLCError(
                "ElasticDeviceRowBlockIter: num_shards must be > 0 (set "
                "DMLC_TRACKER_NUM_SHARDS or pass num_shards=)")
        self.epoch = epoch
        self._acquire_timeout = acquire_timeout
        self._device_kwargs = device_kwargs
        self._current: Optional[DeviceRowBlockIter] = None
        self._aborting = False

    def __iter__(self):
        while True:
            shard = self._monitor.acquire_lease(
                self.epoch, timeout=self._acquire_timeout)
            if shard is None:
                return  # epoch drained: every shard checked out
            it = DeviceRowBlockIter(self.uri, part=shard,
                                    npart=self.num_shards,
                                    **self._device_kwargs)
            self._current = it
            try:
                for batch in it:
                    yield shard, batch
                self._monitor.complete_lease(self.epoch, shard)
            except TrackerAbortedError:
                it.abort_drain("tracker-abort mid-shard")
                raise
            finally:
                self._current = None
                it.close()

    def abort_drain(self, reason: str = "tracker-abort") -> None:
        """Tear down the in-flight shard's device pipeline (bounded wall
        clock; see DeviceRowBlockIter.abort_drain). Thread-safe enough
        for a watchdog drain: _stop/queue ops are atomic, and a racing
        consumer raises out of its queue wait."""
        self._aborting = True
        it = self._current
        if it is not None:
            it.abort_drain(reason)
