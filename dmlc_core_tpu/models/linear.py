"""Sparse linear learner — the flagship demo consumer of the data path.

The reference ships no models (dmlc-core feeds XGBoost/MXNet); the canonical
downstream workload for its RowBlock CSR batches is a distributed linear
learner (the wormhole/difacto lineage). This module is that consumer,
TPU-native: logistic/linear regression over PaddedBatch shards,
data-parallel under `shard_map` with one psum per step for the gradient
(replacing the Rabit allreduce the reference tracker brokers,
tracker.py:185-252).

bfloat16 note: parameters and math stay f32 — at F features the matvec is
bandwidth-trivial; the win on TPU comes from batching (segment ops) and from
the dense MXU path when F is small (ops/sparse.csr_to_dense).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu.models._dp import DataParallelModel
from dmlc_core_tpu.ops.sparse import csr_matvec
from dmlc_core_tpu.tpu.device_iter import unpack_tree

__all__ = ["LinearParams", "LinearLearner"]


class LinearParams(NamedTuple):
    w: jnp.ndarray  # [F]
    b: jnp.ndarray  # []


def objective_loss(margin: jnp.ndarray, shard: Dict[str, jnp.ndarray],
                   num_rows: int, objective: str
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(weighted loss sum, weight sum) for a shard given its margins —
    the objective zoo shared by every margin-producing model (linear here,
    the factorization machine in models/fm.py)."""
    y = shard["label"]
    wgt = shard["weight"]  # 0 on padding rows
    if objective == "logistic":
        # y in {0,1}; stable log-sigmoid cross-entropy. -|margin| is
        # written as a minimum, whose derivative at a tie is the mean of
        # the two sides: abs' reads 1 at 0 (jax 0.9), and the derivative
        # would step from sigmoid(0) - y to -y at a margin of exactly 0,
        # which a zero start and an FM row of one entry both give
        per_row = jnp.maximum(margin, 0) - margin * y + \
            jnp.log1p(jnp.exp(jnp.minimum(margin, -margin)))
    elif objective == "squared":
        per_row = 0.5 * (margin - y) ** 2
    elif objective == "pairwise":
        # RankNet-style learning-to-rank over qid groups (the reference's
        # qid column exists for exactly this consumer lineage,
        # data.h:174-236); the second return is the summed PAIR weight —
        # the psum'd denominator, mirroring wsum for the pointwise losses
        if "qid" not in shard:
            raise ValueError(
                "objective='pairwise' needs qid-grouped data (libsvm "
                "`qid:` column; carried to the device as the qid plane)")
        # the pair mining is an [R, R] broadcast: R f32 temporaries square
        # in rows-per-shard, so an unchecked default batch (65536 rows)
        # would ask for 17 GB on one device — refuse past a sane ceiling
        if num_rows > 8192:
            raise ValueError(
                f"objective='pairwise' mines pairs in [R, R] space; "
                f"R={num_rows} rows per shard would materialize "
                f"{num_rows * num_rows * 4 / 1e9:.1f} GB temporaries. Use "
                f"batch_rows <= 8192 * num_shards for ranking workloads")
        from dmlc_core_tpu.ops.ranking import pairwise_logistic_loss
        return pairwise_logistic_loss(margin, y, shard["qid"], wgt)
    else:
        raise ValueError(f"unknown objective {objective!r}")
    return jnp.sum(per_row * wgt), jnp.sum(wgt)


def _shard_loss(params: LinearParams, shard: Dict[str, jnp.ndarray],
                num_rows: int, objective: str,
                margin_path: str = "segment"
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(weighted loss sum, weight sum) for one local shard. (L2 is applied
    as decoupled weight decay in the update, not in the loss.)

    margin_path (CSR shards only): "segment" rides the segment-sum matvec;
    "dense" materializes the shard dense-first (ops/sparse.csr_to_dense —
    the MXU on-ramp, whose impl the DCT_CSR_TO_DENSE env can switch to the
    Pallas kernel) and takes one matmul. The materialization depends only
    on batch data, never on params, so autodiff does not differentiate
    through the formatting kernel."""
    with jax.named_scope("linear.margin"):
        if "x" in shard:  # dense layout: one MXU matvec
            margin = shard["x"].astype(jnp.float32) @ params.w + params.b
        elif margin_path == "dense":
            from dmlc_core_tpu.ops.sparse import csr_to_dense
            dense = csr_to_dense(shard["row"], shard["col"], shard["val"],
                                 num_rows, params.w.shape[0])
            margin = dense @ params.w + params.b
        else:
            margin = csr_matvec(shard["row"], shard["col"], shard["val"],
                                params.w, num_rows) + params.b
    return objective_loss(margin, shard, num_rows, objective)


class LinearLearner(DataParallelModel):
    """Distributed sparse linear model.

    Usage::

        learner = LinearLearner(num_features=28, mesh=mesh)
        state = learner.init()
        for batch in device_iter:
            state, loss = learner.step(state, batch)

    ``step`` consumes a state it returned itself and copies one you made
    (``DataParallelModel.step``, models/_dp.py).
    """

    def __init__(self, num_features: int, mesh: Optional[Mesh] = None,
                 objective: str = "logistic", learning_rate: float = 0.1,
                 l2: float = 0.0, axis_name: str = "data",
                 margin_path: str = "segment"):
        self.num_features = num_features
        self.mesh = mesh
        self.objective = objective
        self.learning_rate = learning_rate
        self.l2 = l2
        self.axis_name = axis_name
        # "segment" | "dense": see _shard_loss — "dense" is the MXU
        # on-ramp whose formatting impl DCT_CSR_TO_DENSE can switch to
        # the Pallas kernel (opt-in device-side batch formatting)
        self.margin_path = margin_path
        self._step_fn = None

    def init(self, seed: int = 0) -> LinearParams:
        """Fresh parameter pytree (replicated across the mesh)."""
        del seed  # linear model: zero init is canonical
        params = LinearParams(
            w=jnp.zeros((self.num_features,), jnp.float32),
            b=jnp.zeros((), jnp.float32))
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            params = jax.device_put(params, rep)
        return params

    # -- DataParallelModel hooks (the step harness lives in models/_dp.py) --
    def _shard_loss(self, params, shard, rows_per_shard):
        return _shard_loss(params, shard, rows_per_shard, self.objective,
                           self.margin_path)

    def _apply(self, params, grads, denom):
        lr, l2 = self.learning_rate, self.l2
        return LinearParams(
            w=params.w - lr * (grads.w / denom + l2 * params.w),
            b=params.b - lr * grads.b / denom)

    def predict(self, params: LinearParams, batch) -> jnp.ndarray:
        """Margins [D, R] (apply sigmoid for probabilities)."""
        R = batch.rows_per_shard
        # one jitted fwd per rows-per-shard, cached on the learner — a
        # fresh @jax.jit closure per call would retrace every predict
        if getattr(self, "_fwd_fn", None) is None:
            self._fwd_fn = {}
        fwd = self._fwd_fn.get(R)
        if fwd is None:
            @jax.jit
            @jax.named_scope("linear.predict")
            def fwd(params, tree):
                tree = unpack_tree(tree)  # packed batches: bitcast + slice
                if "x" in tree:
                    return tree["x"].astype(jnp.float32) @ params.w + \
                        params.b
                def one(row, col, val):
                    return csr_matvec(row, col, val, params.w, R) + params.b
                return jax.vmap(one)(tree["row"], tree["col"], tree["val"])
            self._fwd_fn[R] = fwd
        return fwd(params, batch.tree())
