"""Factorization machine — the canonical consumer of the libfm data lane.

The reference ships the libfm parser (src/data/libfm_parser.h) precisely
because its downstream ecosystem trains factorization machines (the
wormhole/difacto lineage) on `label field:feature:value` rows; like the
linear learner it ships no model itself. This module is that consumer,
TPU-native: second-order FM over PaddedBatch CSR shards (or DenseBatch
matrices, where the interaction term becomes two MXU matmuls),
data-parallel under ``shard_map`` with one psum per step.

Margin (Rendle's O(NNZ·K) identity):

    y(x) = b + Σ_i w_i x_i + ½ Σ_f [ (Σ_i V_{i,f} x_i)² − Σ_i V_{i,f}² x_i² ]

CSR shards compute the two inner sums with one gather ``V[col]`` and two
segment-sums over the row ids — the same segment-op layout the sparse ops
use (ops/sparse.py); padding nonzeros (val 0, sacrificial row id) vanish.
Dense batches compute them as ``(x @ V)² − x² @ V²`` — pure MXU work.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu.models._dp import DataParallelModel
from dmlc_core_tpu.models.linear import objective_loss
from dmlc_core_tpu.ops.sparse import csr_matvec
from dmlc_core_tpu.tpu.device_iter import unpack_tree

__all__ = ["FMParams", "FMLearner"]


class FMParams(NamedTuple):
    b: jnp.ndarray   # []
    w: jnp.ndarray   # [F]
    v: jnp.ndarray   # [F, K] interaction factors


def _fm_margin_csr(params: FMParams, row, col, val, num_rows: int
                   ) -> jnp.ndarray:
    seg = functools.partial(jax.ops.segment_sum,
                            num_segments=num_rows + 1,
                            indices_are_sorted=True)
    # named scopes: op_name metadata only; the backward ops read
    # .../transpose(jvp(fm.gather))/..., which is how a trace tells the
    # scatter into the dense gradient from the forward gather
    with jax.named_scope("fm.linear"):
        linear = csr_matvec(row, col, val, params.w, num_rows)
    with jax.named_scope("fm.gather"):
        vx = params.v[col] * val[:, None]          # [NNZ, K]
    with jax.named_scope("fm.interaction"):
        s1 = seg(vx, row)[:num_rows]               # Σ V x   per row  [R, K]
        s2 = seg(vx * vx, row)[:num_rows]          # Σ V²x²  per row  [R, K]
        inter = 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)
    return params.b + linear + inter


def _fm_margin_dense(params: FMParams, x) -> jnp.ndarray:
    with jax.named_scope("fm.dense"):
        xf = x.astype(jnp.float32)
        linear = xf @ params.w
        s1 = xf @ params.v                         # [R, K] (MXU)
        s2 = (xf * xf) @ (params.v * params.v)     # [R, K] (MXU)
        inter = 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)
        return params.b + linear + inter


def _margin(params: FMParams, shard, num_rows: int) -> jnp.ndarray:
    if "x" in shard:
        return _fm_margin_dense(params, shard["x"])
    return _fm_margin_csr(params, shard["row"], shard["col"], shard["val"],
                          num_rows)


def _fm_shard_loss(params: FMParams, shard, num_rows: int, objective: str
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(weighted loss sum, weight sum) — the shared objective zoo
    (models/linear.py objective_loss) over the FM margin."""
    margin = _margin(params, shard, num_rows)
    return objective_loss(margin, shard, num_rows, objective)


class FMLearner(DataParallelModel):
    """Distributed second-order factorization machine.

    Usage::

        learner = FMLearner(num_features=1000, k=8, mesh=mesh)
        state = learner.init()
        for batch in device_iter:          # libfm/libsvm/crec/... lanes
            state, loss = learner.step(state, batch)
    """

    def __init__(self, num_features: int, k: int = 8,
                 mesh: Optional[Mesh] = None, objective: str = "logistic",
                 learning_rate: float = 0.05, l2: float = 0.0,
                 init_scale: float = 0.01, axis_name: str = "data"):
        if k <= 0:
            raise ValueError(f"factor rank k must be positive, got {k}")
        self.num_features = num_features
        self.k = k
        self.mesh = mesh
        self.objective = objective
        self.learning_rate = learning_rate
        self.l2 = l2
        self.init_scale = init_scale
        self.axis_name = axis_name
        self._step_fn = None

    def init(self, seed: int = 0) -> FMParams:
        """Fresh parameters (replicated): zero linear part, small random
        factors — an all-zero V has zero interaction gradient."""
        v = self.init_scale * jax.random.normal(
            jax.random.PRNGKey(seed), (self.num_features, self.k),
            jnp.float32)
        params = FMParams(b=jnp.zeros((), jnp.float32),
                          w=jnp.zeros((self.num_features,), jnp.float32),
                          v=v)
        if self.mesh is not None:
            params = jax.device_put(params,
                                    NamedSharding(self.mesh, P()))
        return params

    # -- DataParallelModel hooks (the step harness lives in models/_dp.py) --
    def _shard_loss(self, params, shard, rows_per_shard):
        return _fm_shard_loss(params, shard, rows_per_shard, self.objective)

    def _apply(self, params, grads, denom):
        lr, l2 = self.learning_rate, self.l2
        return FMParams(
            b=params.b - lr * grads.b / denom,
            w=params.w - lr * (grads.w / denom + l2 * params.w),
            v=params.v - lr * (grads.v / denom + l2 * params.v))

    def predict(self, params: FMParams, batch) -> jnp.ndarray:
        """Margins [D, R] (apply sigmoid for probabilities)."""
        R = batch.rows_per_shard
        # one jitted fwd per rows-per-shard, cached on the learner — a
        # fresh @jax.jit closure per call would retrace every predict
        if getattr(self, "_fwd_fn", None) is None:
            self._fwd_fn = {}
        fwd = self._fwd_fn.get(R)
        if fwd is None:
            @jax.jit
            @jax.named_scope("fm.predict")
            def fwd(params, tree):
                tree = unpack_tree(tree)
                if "x" in tree:
                    return jax.vmap(
                        lambda x: _fm_margin_dense(params, x))(tree["x"])
                return jax.vmap(
                    lambda r, c, v: _fm_margin_csr(params, r, c, v, R))(
                        tree["row"], tree["col"], tree["val"])
            self._fwd_fn[R] = fwd
        return fwd(params, batch.tree())
