"""Factorization machine — the canonical consumer of the libfm data lane.

The reference ships the libfm parser (src/data/libfm_parser.h) precisely
because its downstream ecosystem trains factorization machines (the
wormhole/difacto lineage) on `label field:feature:value` rows; like the
linear learner it ships no model itself. This module is that consumer,
TPU-native: second-order FM over PaddedBatch CSR shards (or DenseBatch
matrices, where the interaction term becomes two MXU matmuls),
data-parallel under ``shard_map`` on a mesh of several devices. A CSR step
keeps the gradient in the rows the batch gathered and scatter-adds it
straight into ``w`` and ``v`` (the row form of models/_dp.py): no ``[F, K]``
gradient table is made, and on a mesh the shards exchange those rows (an
all-gather of every shard's distinct columns and the rows of its gradient)
where a dense step all-reduces a gradient of the tables' shape.
A feature is gathered and scattered once a batch: the rows are those at
the shard's DISTINCT columns (``cols``, which every assembler sends:
tpu/device_iter.py col_slots), expanded to the entries by ``slot``.

Margin (Rendle's O(NNZ·K) identity):

    y(x) = b + Σ_i w_i x_i + ½ Σ_f [ (Σ_i V_{i,f} x_i)² − Σ_i V_{i,f}² x_i² ]

CSR shards index every entry four times a step. The rows ``w[cols]``
(scope ``fm.linear``: that gather alone) and ``V[cols]`` (``fm.gather``)
are expanded to the entries by ``slot`` as one ``[U, K+1]`` array in one
gather (``fm.expand``), and the linear sum and the two inner sums ride in
one segment sum over the row ids as the ``2K+1`` lanes of one array
(``fm.interaction``); the backward is one gather back by row and one
scatter-add by ``slot``, the merge of a feature's repeats. On the chip a
pass over the entries costs by its indices, one lane what K lanes cost
(PERF.md section 6, PR 35), and every [NNZ, n] array between the passes is
padded to 128 lanes and costs its bytes, so the row form's backward is
written by hand (``_fm_margin_rows_bwd``: one pass writes the entries'
cotangent; autodiff's slices, pads and one-lane columns touched such an
array 24 times a step where this touches one 13, PERF.md section 6, PR 37).
Padding nonzeros (val 0, sacrificial row id) vanish.
Dense batches compute them as ``(x @ V)² − x² @ V²`` — pure MXU work.

Two update rules. ``updater="sgd"`` (the default): ``p − lr·g``, a
scatter-add of the gradient's rows. ``updater="ftrl"``: what the click
logs' trainers run (wormhole's ``linear`` and ``difacto``), FTRL-proximal
on ``b`` and ``w`` (McMahan et al., KDD 2013, Algorithm 1) and AdaGrad on
``v`` (DiFacto, Li et al., WSDM 2016), with a state for every row
(``FMState``: the parameters and ``FMSlots``) that is read and written only
at the batch's distinct columns; on one device only.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.base import DMLCError
from dmlc_core_tpu.models._dp import DataParallelModel
from dmlc_core_tpu.models.linear import objective_loss
from dmlc_core_tpu.tpu.device_iter import unpack_tree

__all__ = ["FMParams", "FMSlots", "FMState", "FMLearner"]


class FMParams(NamedTuple):
    b: jnp.ndarray   # []
    w: jnp.ndarray   # [F]
    v: jnp.ndarray   # [F, K] interaction factors


class FMSlots(NamedTuple):
    """What ``updater="ftrl"`` keeps beside the parameters, row for row:
    FTRL-proximal's ``z`` and ``n`` of ``b`` and of ``w``, AdaGrad's sum of
    squared gradients ``g`` (the equations' ``G``) of ``v``. Zeros at
    ``init``."""
    zb: jnp.ndarray  # []
    nb: jnp.ndarray  # []
    z: jnp.ndarray   # [F]
    n: jnp.ndarray   # [F]
    g: jnp.ndarray   # [F, K]


class FMState(NamedTuple):
    """The state of an FM trained by a rule with slots: one pytree, donated
    to the step as the parameters alone are under SGD."""
    params: FMParams
    slots: FMSlots


def _params_of(state) -> FMParams:
    return state.params if isinstance(state, FMState) else state


def _ftrl(w, z, n, g, alpha, beta, l1, l2):
    """FTRL-proximal per coordinate on the batch-mean gradient ``g`` (McMahan
    et al. 2013, Algorithm 1): the new ``(w, z, n)``. Where ``g`` is 0
    nothing moves (``σ`` is 0 and ``w`` is what it was: the closed form of
    the same ``z``, ``n``)."""
    sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / alpha
    z = z + g - sigma * w
    n = n + g * g
    w = jnp.where(jnp.abs(z) <= l1, 0.0,
                  -(z - jnp.sign(z) * l1) / ((beta + jnp.sqrt(n)) / alpha
                                             + l2))
    return w, z, n


def _adagrad_step(big_g, g, lr, beta):
    """AdaGrad per element: the new sum of squares and the step to add to
    ``v``."""
    big_g = big_g + g * g
    return big_g, -lr * g / (beta + jnp.sqrt(big_g))


class FMRows(NamedTuple):
    """What a CSR shard reads of the parameters: the rows at its distinct
    columns ``cols`` (a feature that recurs in the shard is here once)."""
    b: jnp.ndarray   # []
    w: jnp.ndarray   # [U]
    v: jnp.ndarray   # [U, K]


# named scopes: op_name metadata only. In the table form the backward ops
# read .../transpose(jvp(fm.gather))/..., which is how a trace tells the
# scatter into the dense gradient from the forward gather; in the row form
# the gathers are outside the differentiated function and have no backward
def _fm_gather(params: FMParams, cols) -> FMRows:
    """The rows at ``cols``: ascending to the list's end, its padding
    beyond the tables (col_slots), where the gather reads zeros and its
    transpose, the table form's scatter, drops. ``[D, C]`` is stretch
    after stretch (what an owner of a range is asked for, models/_dp.py),
    ascending within a stretch only, and gives ``[D, C, ...]``."""
    def at(table):
        return table.at[cols].get(mode="fill", fill_value=0,
                                  indices_are_sorted=cols.ndim == 1)
    with jax.named_scope("fm.linear"):
        w_rows = at(params.w)
    with jax.named_scope("fm.gather"):
        v_rows = at(params.v)
    return FMRows(params.b, w_rows, v_rows)


def _fm_margin_entries(b, w, v, row, val, num_rows: int) -> jnp.ndarray:
    """The margin from each entry's own row of the parameters ([NNZ],
    [NNZ, K]). The three sums over ``row`` ride in one segment sum as lanes
    of one ``[NNZ, 2K+1]`` array (Σ V x, Σ V²x², and last Σ w x), each lane
    summing the terms it would sum alone; the transpose is one gather back
    by ``row``."""
    k = v.shape[1]
    with jax.named_scope("fm.interaction"):
        vx = v * val[:, None]                      # [NNZ, K]
        sums = jax.ops.segment_sum(
            jnp.concatenate([vx, vx * vx, (val * w)[:, None]], axis=1), row,
            num_segments=num_rows + 1, indices_are_sorted=True)[:num_rows]
        s1, s2, linear = sums[:, :k], sums[:, k:2 * k], sums[:, 2 * k]
        inter = 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)
    return b + linear + inter


def _fm_margin_rows(rows: FMRows, slot, row, val, num_rows: int
                    ) -> jnp.ndarray:
    """The margin from the rows a shard gathered, with its backward written
    by hand (``_fm_margin_rows_bwd``): called outside a gradient (``predict``)
    this is the plain composition, an expansion and ``_fm_margin_entries``."""
    k = rows.v.shape[1]
    wv = _fm_expand(rows, slot)
    return _fm_margin_entries(rows.b, wv[:, k], wv[:, :k], row, val,
                              num_rows)


def _fm_expand(rows: FMRows, slot) -> jnp.ndarray:
    # the expansion reads a [U, K+1] intermediate (v's lanes, then w's),
    # not the tables, in one gather
    with jax.named_scope("fm.expand"):
        return jnp.concatenate([rows.v, rows.w[:, None]], axis=1).at[
            slot].get(mode="promise_in_bounds")


def _fm_margin_rows_fwd(rows: FMRows, slot, row, val, num_rows: int):
    """``_fm_margin_rows`` with the products made over the ``K+1`` lanes at
    once and the segment sum's lanes in the order (V x, w x, V²x²): each
    lane sums what it sums in ``_fm_margin_entries``. Kept for the backward:
    the expanded rows and Σ V x, no [NNZ, n] array that the forward did not
    have to make."""
    k = rows.v.shape[1]
    wv = _fm_expand(rows, slot)
    with jax.named_scope("fm.interaction"):
        t = wv * val[:, None]                      # [NNZ, K+1]: V x, then w x
        sums = jax.ops.segment_sum(
            jnp.concatenate([t, t[:, :k] * t[:, :k]], axis=1), row,
            num_segments=num_rows + 1, indices_are_sorted=True)[:num_rows]
        s1, linear, s2 = sums[:, :k], sums[:, k], sums[:, k + 1:]
        inter = 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)
    # rows.w: for the count of rows the merge lands in
    return rows.b + linear + inter, (wv, s1, slot, row, val, rows.w)


def _fm_margin_rows_bwd(num_rows: int, kept, dm):
    """From the margin's cotangent ``dm`` [R]: one gather back by ``row`` of
    the ``K+1`` lanes (dm·Σ V x, dm); one pass that writes the entries'
    cotangent once, d(V) = x·dm·(Σ V x − V x) and d(w) = x·dm; one
    scatter-add by ``slot`` into [U, K+1], the merge of a feature's
    repeats, w's and v's at once, at the gradient's magnitude. The padding
    entries name row ``num_rows``, a row of zeros (a gather that fills
    costs a pass over its result for the mask)."""
    wv, s1, slot, row, val, w_rows = kept
    k = s1.shape[1]
    with jax.named_scope("fm.interaction"):
        back = jnp.pad(jnp.concatenate([dm[:, None] * s1, dm[:, None]],
                                       axis=1), ((0, 1), (0, 0)))
        g = back.at[row].get(mode="clip", indices_are_sorted=True)
        # every lane at once, dm read from its lane as [NNZ] (a [NNZ, 1]
        # column is padded to 128 lanes like any [NNZ, n] array): V x
        # under v's lanes, 0 under w's
        x = val[:, None]
        v_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, k + 1), 1) < k
        inner = g - g[:, k][:, None] * jnp.where(v_lanes, wv * x, 0)
        d = x * inner                              # [NNZ, K+1]
    with jax.named_scope("fm.expand"):
        merged = jnp.zeros((w_rows.shape[0], k + 1), d.dtype).at[slot].add(
            d, mode="promise_in_bounds")
    # val's cotangent is dead code in a step, which asks for the rows' alone
    return (FMRows(jnp.sum(dm), merged[:, k], merged[:, :k]), None, None,
            jnp.sum(wv * inner, axis=1))


_fm_margin_rows = jax.custom_vjp(_fm_margin_rows, nondiff_argnums=(4,))
_fm_margin_rows.defvjp(_fm_margin_rows_fwd, _fm_margin_rows_bwd)


def _fm_margin_csr(params: FMParams, row, col, val, num_rows: int
                   ) -> jnp.ndarray:
    """The margin from raw columns, every entry reading its own row: for
    batches that carry no distinct list (the scoring server's)."""
    with jax.named_scope("fm.linear"):
        w = jnp.take(params.w, col, axis=0)
    with jax.named_scope("fm.gather"):
        v = params.v[col]
    return _fm_margin_entries(params.b, w, v, row, val, num_rows)


def _fm_margin_dense(params: FMParams, x) -> jnp.ndarray:
    with jax.named_scope("fm.dense"):
        xf = x.astype(jnp.float32)
        linear = xf @ params.w
        s1 = xf @ params.v                         # [R, K] (MXU)
        s2 = (xf * xf) @ (params.v * params.v)     # [R, K] (MXU)
        inter = 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)
        return params.b + linear + inter


def _margin(params, shard, num_rows: int) -> jnp.ndarray:
    """``params``: FMParams, or the FMRows a CSR shard gathered from them."""
    if "x" in shard:
        return _fm_margin_dense(params, shard["x"])
    if "cols" not in shard:
        raise DMLCError(
            "FMLearner reads a CSR batch by its distinct columns, which "
            f"every assembler sends; this one holds {sorted(shard)} and no "
            "'cols' / 'slot' (tpu/device_iter.py col_slots makes them)")
    rows = params if isinstance(params, FMRows) else \
        _fm_gather(params, shard["cols"])
    return _fm_margin_rows(rows, shard["slot"], shard["row"], shard["val"],
                           num_rows)


def _fm_shard_loss(params, shard, num_rows: int, objective: str
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(weighted loss sum, weight sum) — the shared objective zoo
    (models/linear.py objective_loss) over the FM margin."""
    margin = _margin(params, shard, num_rows)
    return objective_loss(margin, shard, num_rows, objective)


class FMLearner(DataParallelModel):
    """Distributed second-order factorization machine, trained by plain SGD
    or (``updater="ftrl"``) by FTRL-proximal on ``b`` and ``w`` with AdaGrad
    on ``v``, whose equations follow below (doc/parameters.md "Model update
    rules").

    Usage::

        learner = FMLearner(num_features=1000, k=8, mesh=mesh)
        state = learner.init()
        for batch in device_iter:          # libfm/libsvm/crec/... lanes
            state, loss = learner.step(state, batch)

    The state you pass back is consumed: ``step`` updates the tables where
    they lie, and the arrays of a state that ``step`` itself returned are
    deleted once it is handed in again (a loop as above holds one table,
    not two). Copy a state you mean to keep beside the training
    (``jax.tree.map(jnp.copy, state)``). A state you made (``init()``, a
    restored checkpoint, your own arrays) is never invalidated: the step
    copies it once on the device, in every layout
    (``DataParallelModel.step``, models/_dp.py).

    The update rule (``updater``). ``"sgd"``: ``p − learning_rate·(g +
    l2·p)`` with ``g`` the batch-mean gradient; the state is ``FMParams``.
    ``"ftrl"``: the state is ``FMState`` (``FMParams`` and ``FMSlots``,
    35 floats a row at rank 16 where SGD keeps 17), and per coordinate::

        w (and b, with l1 = l2 = 0):  σ = (sqrt(n + g²) − sqrt(n)) / alpha
                                      z ← z + g − σ·w
                                      n ← n + g²
                                      w ← 0 if |z| ≤ l1, else
                                          −(z − sign(z)·l1)
                                          / ((beta + sqrt(n)) / alpha + l2)
        v, per element:               G ← G + g_v²
                                      v ← v − v_lr·g_v / (v_beta + sqrt(G))

    ``learning_rate`` is then unused and ``l2`` is FTRL's λ2 on ``w``.
    Only the rows at a CSR batch's distinct columns are read and written
    (scope ``opt.update``: the slots' rows gathered and the new rows
    computed; their write-back under ``dp.apply``); an untouched row does
    not change under either rule. FTRL runs on one device: a mesh of
    several hands the update one gradient row a shard for a column that
    several shards name, and the rule needs their sum first.
    """

    def __init__(self, num_features: int, k: int = 8,
                 mesh: Optional[Mesh] = None, objective: str = "logistic",
                 learning_rate: float = 0.05, l2: float = 0.0,
                 init_scale: float = 0.01, axis_name: str = "data",
                 table_layout: str = "replicated", updater: str = "sgd",
                 alpha: float = 0.1, beta: float = 1.0, l1: float = 0.0,
                 v_lr: float = 0.1, v_beta: float = 1.0):
        """``table_layout``: how a mesh keeps ``w`` and ``v``.
        ``"replicated"``: whole on every device. ``"range_sharded"``: cut
        over the mesh's devices by contiguous ranges of
        ``num_features / devices`` rows, a row on its owner alone (``b``
        stays on every device); the batches then come from an iterator
        built with ``col_owners=learner.col_owners`` (models/_dp.py, the
        third form).

        ``updater``: ``"sgd"`` (``learning_rate``, ``l2`` as weight decay)
        or ``"ftrl"``: FTRL-proximal on ``b`` and ``w`` (``alpha``,
        ``beta``, ``l1``, ``l2``; ``b`` without ``l1``, ``l2``) and AdaGrad
        on ``v`` (``v_lr``, ``v_beta``), on one device (the class's
        docstring has the equations)."""
        if k <= 0:
            raise ValueError(f"factor rank k must be positive, got {k}")
        if table_layout not in ("replicated", "range_sharded"):
            raise ValueError(f"unknown table_layout {table_layout!r} "
                             f"(replicated, range_sharded)")
        if updater not in ("sgd", "ftrl"):
            raise ValueError(f"unknown updater {updater!r} (sgd, ftrl)")
        if updater == "ftrl":
            devices = 1 if mesh is None else int(mesh.devices.size)
            if devices > 1 or table_layout != "replicated":
                raise ValueError(
                    f"updater='ftrl' runs on one device: on a mesh of "
                    f"{devices} ({table_layout} tables) a column several "
                    f"shards name reaches the update as one gradient row a "
                    f"shard, and FTRL's z, n and AdaGrad's G need the "
                    f"column's summed gradient, which the mesh step does "
                    f"not make yet")
            if min(alpha, beta, v_lr, v_beta) <= 0 or min(l1, l2) < 0:
                raise ValueError(
                    f"ftrl wants alpha, beta, v_lr, v_beta > 0 and l1, l2 "
                    f">= 0, got alpha={alpha} beta={beta} l1={l1} l2={l2} "
                    f"v_lr={v_lr} v_beta={v_beta}")
        self.num_features = num_features
        self.k = k
        self.mesh = mesh
        self.objective = objective
        self.learning_rate = learning_rate
        self.l2 = l2
        self.init_scale = init_scale
        self.axis_name = axis_name
        self.table_layout = table_layout
        self.updater = updater
        self.alpha, self.beta, self.l1 = alpha, beta, l1
        self.v_lr, self.v_beta = v_lr, v_beta
        self.col_owners = (1, 0)
        if table_layout == "range_sharded":
            owners = 0 if mesh is None else int(mesh.devices.size)
            if owners < 2 or num_features % owners:
                raise ValueError(
                    f"range-sharded tables are cut in equal ranges over a "
                    f"mesh of several devices: {num_features} rows over "
                    f"{owners} (pad num_features to a multiple)")
            self.col_owners = (owners, num_features // owners)
        self._step_fn = None

    def init(self, seed: int = 0):
        """Fresh parameters: zero linear part, small random factors (an
        all-zero V has zero interaction gradient). On a mesh replicated, or
        with range-sharded tables every range made on its owner, no device
        and not the host holding a whole table: the same draw row for row
        (a row's bits depend on its place in the table, not on who makes
        it; the draw alone is jitted, cut by rows, and scaled outside the
        jit as the replicated one is, so that no compiler folds the two
        factors into one rounding). Under ``updater="ftrl"`` an ``FMState``:
        the same parameters and zero slots, made where they will live.
        Sets the gauge ``model_state_bytes{part=params|slots}``."""
        key = jax.random.PRNGKey(seed)
        shape = (self.num_features, self.k)
        if self.table_layout == "range_sharded":
            at = FMParams(*(NamedSharding(self.mesh, spec)
                            for spec in self._table_specs()))
            draw = jax.jit(
                lambda k: jax.random.normal(k, shape, jnp.float32),
                out_shardings=at.v)(key)
            return self._counted(FMParams(
                b=jnp.zeros((), jnp.float32, device=at.b),
                w=jnp.zeros(shape[:1], jnp.float32, device=at.w),
                v=self.init_scale * draw))
        v = self.init_scale * jax.random.normal(key, shape, jnp.float32)
        params = FMParams(b=jnp.zeros((), jnp.float32),
                          w=jnp.zeros(shape[:1], jnp.float32), v=v)
        at = None
        if self.mesh is not None:
            at = NamedSharding(self.mesh, P())
            params = jax.device_put(params, at)
        if self.updater == "ftrl":
            def zeros(s):
                return jnp.zeros(s, jnp.float32, device=at)
            params = FMState(params, FMSlots(
                zb=zeros(()), nb=zeros(()), z=zeros(shape[:1]),
                n=zeros(shape[:1]), g=zeros(shape)))
        return self._counted(params)

    def _counted(self, state):
        """``state``, its bytes set on ``model_state_bytes`` by part (one
        copy: a replicated mesh holds one on every device)."""
        parts = {"params": _params_of(state)}
        if isinstance(state, FMState):
            parts["slots"] = state.slots
        for part, tree in parts.items():
            telemetry.gauge("model_state_bytes",
                            {"model": type(self).__name__, "part": part}).set(
                sum(leaf.nbytes for leaf in jax.tree.leaves(tree)))
        return state

    def _table_specs(self) -> FMParams:
        rows = P(self.axis_name)
        return FMParams(b=P(), w=rows, v=rows)

    # -- DataParallelModel hooks (the step harness lives in models/_dp.py) --
    def _shard_loss(self, params, shard, rows_per_shard):
        return _fm_shard_loss(_params_of(params), shard, rows_per_shard,
                              self.objective)

    def _apply(self, params, grads, denom):
        if self.updater == "ftrl":
            # the table form: every row, an untouched one with g = 0
            return self._ftrl_update(params, _params_of(grads), denom,
                                     lambda table: table,
                                     lambda table, rows: rows)
        lr, l2 = self.learning_rate, self.l2
        return FMParams(
            b=params.b - lr * grads.b / denom,
            w=params.w - lr * (grads.w / denom + l2 * params.w),
            v=params.v - lr * (grads.v / denom + l2 * params.v))

    def _gather_rows(self, params, shard):
        return _fm_gather(_params_of(params), shard["cols"])

    def _ftrl_update(self, state: FMState, g, denom, rows_of, put):
        """The FTRL / AdaGrad step on the rows ``rows_of`` reads out of a
        table (``g``'s leaves are those rows' gradients), written back by
        ``put(table, new_rows)``."""
        p, s = state
        with jax.named_scope("opt.update"):
            b, zb, nb = _ftrl(p.b, s.zb, s.nb, g.b / denom, self.alpha,
                              self.beta, 0.0, 0.0)
            w, z, n = _ftrl(rows_of(p.w), rows_of(s.z), rows_of(s.n),
                            g.w / denom, self.alpha, self.beta, self.l1,
                            self.l2)
            big_g, dv = _adagrad_step(rows_of(s.g), g.v / denom, self.v_lr,
                                      self.v_beta)
            v = rows_of(p.v) + dv
        return FMState(FMParams(b, put(p.w, w), put(p.v, v)),
                       FMSlots(zb, nb, put(s.z, z), put(s.n, n),
                               put(s.g, big_g)))

    def _apply_rows(self, params, cols, g, denom):
        """``_apply`` with the gradient as FMRows at ``cols``: a row's
        gradient is already the sum of the entries that name it (the
        expansion's transpose), so the scatter-add lands one row a feature,
        ascending and distinct, with one rounding at the parameter's
        magnitude as ``_apply`` has; the list's padding lies beyond the
        tables and is dropped (it repeats one id, so the scatter is told
        its indices are sorted and no more; the hint of uniqueness bought
        nothing on the chip: PERF.md section 6, PR 31). Weight decay still
        reaches every row.

        On a mesh ``cols`` is ``[D, U]`` and the rows ``[D, U, ...]``:
        every shard's list with its gradient's rows, shard after shard.
        The lists go in as one: it ascends within a shard's stretch only,
        so nothing is promised of its order, and a column that several
        shards name is added to once a shard, with a rounding each.

        Under ``updater="ftrl"`` (one device) the rows of every leaf at
        ``cols`` are read, the rule gives their new values and these are
        written back; the padding's reads fill zeros and its writes are
        dropped, as the SGD scatter's are."""
        if self.updater == "ftrl":
            def rows_of(table):
                return table.at[cols].get(mode="fill", fill_value=0,
                                          indices_are_sorted=True)

            def put(table, rows):
                return table.at[cols].set(rows, indices_are_sorted=True)
            return self._ftrl_update(params, g, denom, rows_of, put)
        lr, l2 = self.learning_rate, self.l2

        def update(table, grad):
            table = table if l2 == 0 else (1.0 - lr * l2) * table
            if cols.ndim == 1:
                return table.at[cols].add(-lr * (grad / denom),
                                          indices_are_sorted=True)
            return table.at[cols.reshape(-1)].add(
                -lr * (grad.reshape((-1,) + table.shape[1:]) / denom))
        return FMParams(b=params.b - lr * g.b / denom,
                        w=update(params.w, g.w), v=update(params.v, g.v))

    def predict(self, params, batch) -> jnp.ndarray:
        """Margins [D, R] (apply sigmoid for probabilities); ``params``:
        ``FMParams`` or an ``FMState``, whose parameters it reads."""
        params = _params_of(params)
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
                return jax.vmap(lambda shard: _margin(params, shard, R))(
                    unpack_tree(tree))
            self._fwd_fn[R] = fwd
        return fwd(params, batch.tree())
