"""Scoring-side model artifacts: load, pre-jitted forward, hot reload.

A serving model is a checkpoint written by
:func:`~dmlc_core_tpu.utils.checkpoint.save_checkpoint` whose ``extra``
metadata names the model kind (``linear`` / ``fm``), feature count, and
objective. Loads and reloads go through the checkpoint layer's
NativeStream reads, so the PR 10 ``fs_fault`` plane and the PR 2 retry
plane apply to the model artifact path for free — exactly what the
degradation tests inject against.

The forward is the same CSR margin math the trainers use
(``models/linear.py`` / ``models/fm.py``), jitted once per padded batch
shape. A process-wide shape census (mirroring the device-lane census in
``tpu/device_iter.py``) counts every distinct ``(kind, rows, nnz)`` the
forward has seen: with bucket padding upstream the set is finite and
``steady_new_shapes`` stays 0 under ragged traffic.
"""

import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.base import DMLCError
from dmlc_core_tpu.models.fm import FMParams, _fm_margin_csr
from dmlc_core_tpu.ops.sparse import csr_matvec
from dmlc_core_tpu.utils.checkpoint import restore_checkpoint, \
    save_checkpoint

#: checkpoint ``extra`` keys a serving model artifact carries
KIND_KEY = "serving_kind"
FEATURES_KEY = "num_features"
OBJECTIVE_KEY = "objective"

_shape_lock = threading.Lock()
_shapes_seen: set = set()


def _note_shape(kind: str, num_rows: int, nnz: int) -> None:
    """Census one forward shape: first sight means a fresh jit trace
    (the serving analogue of device_iter's compile-churn census)."""
    key = (kind, num_rows, nnz)
    with _shape_lock:
        new = key not in _shapes_seen
        if new:
            _shapes_seen.add(key)
        n = len(_shapes_seen)
    if new:
        telemetry.emit_event("serve-shape", kind=kind, rows=num_rows,
                             nnz=nnz, distinct=n)
    telemetry.gauge("serve_distinct_shapes").set(n)


def distinct_shapes() -> int:
    """Number of distinct padded forward shapes seen by this process."""
    with _shape_lock:
        return len(_shapes_seen)


def _reset_shape_census() -> None:
    """Forget every seen shape (tests only; the census is process-wide
    like the jit cache it mirrors)."""
    with _shape_lock:
        _shapes_seen.clear()


def save_model(uri: str, kind: str, params: Dict[str, np.ndarray],
               num_features: int, objective: str = "logistic",
               step: int = 0) -> None:
    """Write a serving model artifact.

    ``params`` is a plain dict — ``{"w", "b"}`` for ``linear``,
    ``{"w", "b", "v"}`` for ``fm`` — written atomically through the
    checkpoint layer with serving metadata in ``extra``.
    """
    if kind not in ("linear", "fm"):
        raise DMLCError(f"unknown serving model kind {kind!r}")
    save_checkpoint(uri, dict(params), step=step,
                    extra={KIND_KEY: kind,
                           FEATURES_KEY: str(int(num_features)),
                           OBJECTIVE_KEY: objective})


def _param_name(keystr: str) -> str:
    """``"['w']"`` (tree_util keystr for a dict leaf) -> ``"w"``."""
    return keystr.strip("[]'\" .")


class ScoringModel:
    """A loaded model plus its pre-jitted CSR forward.

    Thread-compatible rather than thread-safe by design: :meth:`scores`
    and :meth:`reload` are only ever called from the scorer thread, so a
    reload can never race a forward. Failed reloads raise and leave the
    previous (last-good) parameters serving.
    """

    def __init__(self, kind: str, params: Dict[str, np.ndarray],
                 num_features: int, objective: str = "logistic",
                 step: int = 0, uri: str = ""):
        if kind not in ("linear", "fm"):
            raise DMLCError(f"unknown serving model kind {kind!r}")
        need = ("w", "b") if kind == "linear" else ("w", "b", "v")
        missing = [k for k in need if k not in params]
        if missing:
            raise DMLCError(
                f"serving model {kind!r} checkpoint is missing "
                f"parameters {missing}")
        self.kind = kind
        self.num_features = int(num_features)
        self.objective = objective
        self.step = int(step)
        self.uri = uri
        self._params = {k: np.asarray(params[k], dtype=np.float32)
                        for k in need}
        if self._params["w"].shape != (self.num_features,):
            raise DMLCError(
                f"serving model w has shape {self._params['w'].shape}, "
                f"expected ({self.num_features},)")
        if kind == "fm" and (self._params["v"].ndim != 2 or
                             self._params["v"].shape[0] != self.num_features):
            # a checkpoint holds v as learner.saved gives it, [F, K]
            raise DMLCError(
                f"serving model v has shape {self._params['v'].shape}, "
                f"expected ({self.num_features}, K)")
        self._fwd = jax.jit(self._margin, static_argnames="num_rows")

    @classmethod
    def load(cls, uri: str) -> "ScoringModel":
        """Load a serving artifact written by :func:`save_model`.

        Raises :class:`~dmlc_core_tpu.base.DMLCError` (or a checkpoint
        error subclass) on any fault — unreadable stream, bad payload,
        missing metadata — so callers can fall back to last-good."""
        flat, step, extra = restore_checkpoint(uri)
        kind = extra.get(KIND_KEY)
        if kind is None:
            raise DMLCError(
                f"checkpoint {uri} is not a serving model artifact "
                f"(missing extra[{KIND_KEY!r}])")
        try:
            num_features = int(extra.get(FEATURES_KEY, ""))
        except ValueError:
            raise DMLCError(
                f"checkpoint {uri} carries a bad {FEATURES_KEY!r}")
        params = {_param_name(k): v for k, v in flat.items()}
        return cls(kind, params, num_features,
                   objective=extra.get(OBJECTIVE_KEY, "logistic"),
                   step=step, uri=uri)

    def reload(self, uri: Optional[str] = None) -> "ScoringModel":
        """Load a replacement model; raises on failure (caller keeps
        serving ``self`` — the last-good fallback)."""
        return ScoringModel.load(uri or self.uri)

    # -- forward -----------------------------------------------------------

    def _margin(self, params: Dict[str, jnp.ndarray], row, col, val,
                num_rows: int) -> jnp.ndarray:
        if self.kind == "linear":
            return csr_matvec(row, col, val, params["w"],
                              num_rows) + params["b"]
        return _fm_margin_csr(
            FMParams(b=params["b"], w=params["w"], v=params["v"]),
            row, col, val, num_rows)

    def scores(self, row: np.ndarray, col: np.ndarray, val: np.ndarray,
               num_rows: int) -> np.ndarray:
        """Scores for one padded batch: ``sigmoid(margin)`` for the
        logistic objective, raw margin otherwise. ``row`` entries equal
        to ``num_rows`` are padding (the sacrificial segment); feature
        ids outside ``[0, num_features)`` are masked to zero weight
        before the device sees them (a clamped gather would silently
        misattribute them to feature 0)."""
        col = np.asarray(col, dtype=np.int32)
        val = np.asarray(val, dtype=np.float32)
        row = np.asarray(row, dtype=np.int32)
        bad = (col < 0) | (col >= self.num_features)
        if bad.any():
            col = np.where(bad, 0, col)
            val = np.where(bad, np.float32(0), val)
        _note_shape(self.kind, num_rows, len(val))
        margin = self._fwd(self._params, row, col, val,
                           num_rows=num_rows)
        if self.objective == "logistic":
            margin = jax.nn.sigmoid(margin)
        return np.asarray(margin)

    def describe(self) -> Dict[str, object]:
        """Small JSON-able summary for ``/statz`` and reload replies."""
        return {"kind": self.kind, "num_features": self.num_features,
                "objective": self.objective, "step": self.step,
                "uri": self.uri}
