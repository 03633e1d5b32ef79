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

The step makes three decisions, each in one place:

- the layout of each table (``FMLearner._layout``, chosen in ``__init__``),
  through which every read and write of it goes: ``_Plain``, a feature a
  row, or ``_Lanes``, ``v`` (and ``G``) lane-dense where the rank divides
  the chip's 128 lanes on replicated tables (PERF.md section 6);
- the order of the list the step scatters at (``FMLearner._list_order``),
  from its shape and ``v``'s layout;
- the update rule (``FMLearner._rule``, chosen in ``__init__``): ``_Sgd``,
  ``p − lr·g``, or ``_Ftrl``, what the click logs' trainers run (wormhole's
  ``linear`` and ``difacto``): FTRL-proximal on ``b`` and ``w`` (McMahan et
  al., KDD 2013, Algorithm 1) and AdaGrad on ``v`` (DiFacto, Li et al., WSDM
  2016), a state for every row (``FMState``), one device only. It owns its
  state and turns the rows a step read and their gradient into writes (a
  row or a delta) that ``_At`` lands at the list's columns or whole tables.
"""

from __future__ import annotations

import math
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
    v: jnp.ndarray   # [F, K] interaction factors, or lane-dense (_Lanes)


class FMSlots(NamedTuple):
    """What ``updater="ftrl"`` keeps beside the parameters, row for row:
    FTRL-proximal's ``z`` and ``n`` of ``b`` and of ``w``, AdaGrad's sum of
    squared gradients ``g`` (the equations' ``G``) of ``v``. Zeros at
    ``init``."""
    zb: jnp.ndarray  # []
    nb: jnp.ndarray  # []
    z: jnp.ndarray   # [F]
    n: jnp.ndarray   # [F]
    g: jnp.ndarray   # [F, K], laid out as v is


class FMState(NamedTuple):
    """The state of an FM trained by a rule with slots: one pytree, donated
    to the step as the parameters alone are under SGD."""
    params: FMParams
    slots: FMSlots


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


LANES = 128   # a row of the chip's (8, 128) tiles
# XLA's scatter into [rows, 128], told that its indices ascend, passes over
# the whole table (on v5e 1.57 ns a row of it) and then costs 9.7 ns an
# update; told nothing, it leaves the other rows alone and costs about 71 ns
# an update (PERF.md section 5). Told where the table holds fewer rows than
# this many an update.
STREAM_ROWS_PER_UPDATE = 39


# -- a table's layout: its shape as held; ``rows`` at a list of columns
# (zeros at the list's padding, an id beyond the tables: col_slots), ``add``
# a delta there, ``put`` new rows there (the padding's writes dropped), each
# told that the list ascends as ``ascending`` and the layout say; ``matrix``,
# the table as ``[F, K]``, and ``packed``, its inverse
class _Plain(NamedTuple):
    """A table held as it is read, a feature a row: ``w`` and FTRL's ``z``
    and ``n`` (``[F]``), and ``v`` and ``G`` (``[F, K]``) where K does not
    divide 128 or the tables are range-sharded (an owner's range is rows).
    Told whenever its list ascends; it never asks for a sort."""
    shape: Tuple[int, ...]
    lane_dense = False

    def rows(self, table, cols, ascending: bool):
        return table.at[cols].get(mode="fill", fill_value=0,
                                  indices_are_sorted=ascending)

    def streams(self, updates: int) -> bool:
        return False

    def add(self, table, cols, delta, ascending: bool):
        return table.at[cols].add(delta, indices_are_sorted=ascending)

    def put(self, table, cols, rows, ascending: bool):
        return table.at[cols].set(rows, indices_are_sorted=ascending)

    def matrix(self, table):
        return table
    packed = matrix


class _Lanes(NamedTuple):
    """A lane-dense factor table (``v``, and ``G``, where K divides 128 on
    replicated tables): ``[F, K]`` row-major held as ``[ceil(F / p),
    128]``, feature ``i`` in row ``i // p``, lanes ``(i % p)·K`` on, ``p =
    128 / K`` (the last row's pad features never named). Rows are read as
    whole 128-lane rows; a delta is scatter-added as one, so a neighbour
    that shares the row is left as it was and two columns of a row are two
    adds (never told unique), told that ``cols`` ascend only where that is
    cheaper (``streams``). No ``put``: no rule writes new rows of one."""
    k: int
    num_features: int
    lane_dense = True

    @property
    def pack(self) -> int:
        return LANES // self.k

    @property
    def shape(self) -> Tuple[int, int]:
        return (-(-self.num_features // self.pack), LANES)

    def _at(self, cols):
        """The row of each column; the list's padding (an id beyond any
        table, col_slots) a row beyond this table's last, even where that
        last row holds pad features."""
        return jnp.where(cols < self.num_features, cols // self.pack,
                         self.shape[0])

    def rows(self, table, cols, ascending: bool):
        """``[U, K]``. The padding reads the last row and is zeroed on its
        K lanes alone, not filled over whole 128-lane rows (PERF.md section
        5: 1–14% less a gather on the chip)."""
        packed = table.at[jnp.minimum(cols // self.pack, self.shape[0] - 1)
                          ].get(mode="promise_in_bounds",
                                indices_are_sorted=ascending)
        lane, k = (cols % self.pack)[:, None], self.k
        out = packed[:, :k]
        for j in range(1, self.pack):
            out = jnp.where(lane == j, packed[:, j * k:(j + 1) * k], out)
        return jnp.where((cols < self.num_features)[:, None], out, 0)

    def streams(self, updates: int) -> bool:
        """Whether a scatter of ``updates`` rows into this table, its list
        ascending, is cheaper told so: the table holds fewer rows than
        ``STREAM_ROWS_PER_UPDATE`` an update."""
        return self.shape[0] < STREAM_ROWS_PER_UPDATE * updates

    def add(self, table, cols, delta, ascending: bool):
        """``delta`` placed at its lanes of a row of ``-0.0``, the identity
        of addition bit for bit."""
        group = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // self.k
        placed = jnp.where(group == (cols % self.pack)[:, None],
                           jnp.tile(delta, (1, self.pack)),
                           jnp.float32(-0.0))
        return table.at[self._at(cols)].add(
            placed, indices_are_sorted=ascending and self.streams(
                cols.shape[0]))

    def matrix(self, table):
        """The ``[F, K]`` view (a reshape and a slice: on the chip a pass
        over the table)."""
        return table.reshape(-1, self.k)[:self.num_features]

    def packed(self, matrix):
        """``[F, K]`` as this table holds it, the pad features zero."""
        pad = self.shape[0] * self.pack - self.num_features
        return jnp.pad(matrix, ((0, pad), (0, 0))).reshape(self.shape)


class _At(NamedTuple):
    """Where a rule reads and writes one table: at the list ``cols`` through
    its ``layout`` (the row form), or on the whole table (``cols`` None)."""
    layout: object
    cols: Optional[jnp.ndarray] = None
    ascending: bool = False

    def rows(self, table):
        return table if self.cols is None else self.layout.rows(
            table, self.cols, self.ascending)

    def put(self, table, rows):
        return rows if self.cols is None else self.layout.put(
            table, self.cols, rows, self.ascending)

    def add(self, table, delta):
        return table + delta if self.cols is None else self.layout.add(
            table, self.cols, delta, self.ascending)


# -- the update rules: the rows a step read and their gradient as writes;
# the state: ``init``, ``parts`` ("params", ...), ``relaid`` factor tables
class _Sgd(NamedTuple):
    """``p − learning_rate·(g + l2·p)``: a delta added to each table, the
    decay a scale of the whole table before it (it reaches every row)."""
    learning_rate: float
    l2: float
    slots = False

    def init(self, params, zeros):
        return params

    def parts(self, params):
        return {"params": params}

    def relaid(self, params, relay):
        return params._replace(v=relay(params.v))

    def update(self, params, g, denom, w_at: _At, v_at: _At):
        lr, l2 = self.learning_rate, self.l2

        def step(at, table, grad):
            table = table if l2 == 0 else (1.0 - lr * l2) * table
            return at.add(table, -lr * (grad / denom))
        return FMParams(b=params.b - lr * g.b / denom,
                        w=step(w_at, params.w, g.w),
                        v=step(v_at, params.v, g.v))


class _Ftrl(NamedTuple):
    """FTRL-proximal on ``b`` and ``w``, AdaGrad on ``v`` (``FMLearner``
    has the equations): new rows of ``w``, ``z``, ``n``, deltas of ``v``
    and ``G``."""
    alpha: float
    beta: float
    l1: float
    l2: float
    v_lr: float
    v_beta: float
    slots = True
    parts = staticmethod(FMState._asdict)

    def init(self, params, zeros):
        return FMState(params, FMSlots(
            zb=zeros(()), nb=zeros(()), z=zeros(params.w.shape),
            n=zeros(params.w.shape), g=zeros(params.v.shape)))

    def relaid(self, state: FMState, relay):
        return FMState(state.params._replace(v=relay(state.params.v)),
                       state.slots._replace(g=relay(state.slots.g)))

    def update(self, state: FMState, g, denom, w_at: _At, v_at: _At):
        p, s = state
        with jax.named_scope("opt.update"):
            b, zb, nb = _ftrl(p.b, s.zb, s.nb, g.b / denom, self.alpha,
                              self.beta, 0.0, 0.0)
            w, z, n = _ftrl(w_at.rows(p.w), w_at.rows(s.z), w_at.rows(s.n),
                            g.w / denom, self.alpha, self.beta, self.l1,
                            self.l2)
            # AdaGrad per element: the square added to G, the step to v
            big_g, gv = v_at.rows(s.g), g.v / denom
            sq = gv * gv
            dv = -self.v_lr * gv / (self.v_beta + jnp.sqrt(big_g + sq))
        return FMState(FMParams(b, w_at.put(p.w, w), v_at.add(p.v, dv)),
                       FMSlots(zb, nb, w_at.put(s.z, z), w_at.put(s.n, n),
                               v_at.add(s.g, sq)))


class FMRows(NamedTuple):
    """What a CSR shard reads of the parameters: the rows at its distinct
    columns ``cols`` (a feature that recurs in the shard is here once)."""
    b: jnp.ndarray   # []
    w: jnp.ndarray   # [U]
    v: jnp.ndarray   # [U, K]


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

    Where K divides 128 (and is less) on replicated tables, ``v`` (and
    ``G``) is held lane-dense (``lane_dense``): read it as ``[F, K]``
    through ``factors``, and save and restore a state through ``saved`` /
    ``from_saved``, whose tables are ``[F, K]`` whatever the layout.
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
                    f"shards name reaches the update as a row a shard, and "
                    f"z, n and G need its summed gradient")
            if min(alpha, beta, v_lr, v_beta) <= 0 or min(l1, l2) < 0:
                raise ValueError(
                    f"ftrl wants alpha, beta, v_lr, v_beta > 0 and l1, l2 "
                    f">= 0, got alpha={alpha} beta={beta} l1={l1} l2={l2} "
                    f"v_lr={v_lr} v_beta={v_beta}")
        self.num_features = num_features
        self.k = k
        self.mesh = mesh
        self.objective = objective
        self.init_scale = init_scale
        self.axis_name = axis_name
        self.table_layout = table_layout
        self.updater = updater
        self.col_owners = (1, 0)
        if table_layout == "range_sharded":
            owners = 0 if mesh is None else int(mesh.devices.size)
            if owners < 2 or num_features % owners:
                raise ValueError(
                    f"range-sharded tables are cut in equal ranges over a "
                    f"mesh of several devices: {num_features} rows over "
                    f"{owners} (pad num_features to a multiple)")
            self.col_owners = (owners, num_features // owners)
        # each table leaf's layout, b read whole (_Plain and _Lanes say which)
        self._layout = FMParams(
            b=None, w=_Plain((num_features,)),
            v=_Lanes(k, num_features) if (
                k < LANES and LANES % k == 0
                and table_layout == "replicated")
            else _Plain((num_features, k)))
        self._rule = (_Ftrl(alpha, beta, l1, l2, v_lr, v_beta)
                      if updater == "ftrl" else _Sgd(learning_rate, l2))
        self._step_fn, self._fwd_fn = None, {}

    @property
    def lane_dense(self) -> bool:
        """Whether ``v`` (and ``G``) is held lane-dense (``_Lanes``)."""
        return self._layout.v.lane_dense

    def init(self, seed: int = 0):
        """Fresh parameters: zero linear part, small random factors (an
        all-zero V has zero interaction gradient). On a mesh replicated, or
        with range-sharded tables every range made on its owner, no device
        and not the host holding a whole table: the same draw row for row
        (the draw alone is jitted, cut by rows, and scaled outside the jit
        as the replicated one is, so that no compiler folds the two factors
        into one rounding). Under ``updater="ftrl"`` an ``FMState``: the
        same parameters and zero slots. A lane-dense ``v`` is drawn at its
        own shape: ``factors`` of it is the ``[F, K]`` draw bit for bit.
        Sets the gauge ``model_state_bytes{part=params|slots}``."""
        key = jax.random.PRNGKey(seed)
        w, v = self._layout.w.shape, self._layout.v.shape
        if self.table_layout == "range_sharded":
            at = FMParams(*(NamedSharding(self.mesh, spec)
                            for spec in self._table_specs()))
            draw = jax.jit(
                lambda k: jax.random.normal(k, v, jnp.float32),
                out_shardings=at.v)(key)
            return self._counted(FMParams(
                b=jnp.zeros((), jnp.float32, device=at.b),
                w=jnp.zeros(w, jnp.float32, device=at.w),
                v=self.init_scale * draw))
        params = FMParams(
            b=jnp.zeros((), jnp.float32), w=jnp.zeros(w, jnp.float32),
            v=self.init_scale * jax.random.normal(key, v, jnp.float32))
        at = None
        if self.mesh is not None:
            at = NamedSharding(self.mesh, P())
            params = jax.device_put(params, at)
        return self._counted(self._rule.init(
            params, lambda s: jnp.zeros(s, jnp.float32, device=at)))

    def factors(self, params) -> jnp.ndarray:
        """``v`` of this learner's state ``params`` (``FMParams`` or, under
        ``updater="ftrl"``, an ``FMState``) as ``[F, K]``, whatever its
        layout."""
        return self._layout.v.matrix(self._params(params).v)

    def saved(self, state):
        """``state`` as a checkpoint holds it: every factor table ``[F,
        K]`` (so a checkpoint reads the same under either layout, and the
        scoring server loads it)."""
        return self._rule.relaid(state, self._layout.v.matrix)

    def from_saved(self, state):
        """The inverse of ``saved``: a state with ``[F, K]`` tables as this
        learner holds it."""
        return self._rule.relaid(state, self._layout.v.packed)

    def _counted(self, state):
        """``state``, its bytes set on ``model_state_bytes`` by part (one
        copy: a replicated mesh holds one on every device)."""
        for part, tree in self._rule.parts(state).items():
            telemetry.gauge("model_state_bytes",
                            {"model": type(self).__name__, "part": part}).set(
                sum(leaf.nbytes for leaf in jax.tree.leaves(tree)))
        return state

    def _params(self, state) -> FMParams:
        return self._rule.parts(state)["params"]

    def _table_specs(self) -> FMParams:
        rows = P(self.axis_name)
        return FMParams(b=P(), w=rows, v=rows)

    def _list_order(self, shape) -> str:
        """How the step orders a list of columns of ``shape``: ``"ascends"``,
        one shard's (``[U]``; a batch's ``[1, U]``) as col_slots sends it;
        ``"sorts"``, the shards' ``[D, U]`` where ``v``'s layout streams
        ``D·U`` updates (``_ordered`` sorts them into one); ``"flat"``, any
        other ``[D, U]`` or an owner's stretches ``[D, C]``."""
        if len(shape) == 1 or shape[0] == 1:
            return "ascends"
        return "sorts" if self._layout.v.streams(math.prod(shape)) else "flat"

    def _ordered(self, cols, g: FMRows):
        """The one list the step scatters at, ``g``'s rows in its order, and
        whether it ascends. The sort is stable: a column several shards name
        is added to once a shard, in the mesh's order, sorted or not."""
        order = self._list_order(cols.shape)
        if order == "sorts":
            # the padding lies beyond the tables and sorts to the end; every
            # replica sorts the same gathered list, so they stay identical.
            # w's rows ride in the sort (a gather of single floats costs
            # more), v's are gathered by the order
            cols, perm, w_rows = jax.lax.sort(
                (cols.reshape(-1), jnp.arange(cols.size, dtype=jnp.int32),
                 g.w.reshape(-1)), num_keys=1, is_stable=True)
            return cols, g._replace(w=w_rows, v=g.v.reshape(-1, self.k).at[
                perm].get(mode="promise_in_bounds", unique_indices=True)), True
        # on one shard's list these reshapes are no operation
        return (cols.reshape(-1), g._replace(w=g.w.reshape(-1),
                                             v=g.v.reshape(-1, self.k)),
                order == "ascends")

    # -- DataParallelModel hooks (the step harness lives in models/_dp.py) --
    def _count_step(self, tree):
        # beside model_step_row_updates_total: steps on lane-dense tables,
        # on a mesh list sorted first, of a rule with a state of its own
        model = {"model": type(self).__name__}
        if self.lane_dense:
            telemetry.counter("model_step_lane_dense_updates_total",
                              model).inc()
        if self._takes_row_form(tree) and self._list_order(
                tree["cols"].shape) == "sorts":
            telemetry.counter("model_step_sorted_lists_total", model).inc()
        if self._rule.slots:
            telemetry.counter("model_step_rule_updates_total",
                              {**model, "rule": self.updater}).inc()

    def _margin(self, params, shard, num_rows: int) -> jnp.ndarray:
        """``params``: this learner's state (a dense batch reads ``v`` as
        ``[F, K]``, a CSR one gathers its rows), or the FMRows a CSR shard
        gathered."""
        if "x" in shard:
            params = self._params(params)
            return _fm_margin_dense(params._replace(
                v=self._layout.v.matrix(params.v)), shard["x"])
        if "cols" not in shard:
            raise DMLCError(
                "FMLearner reads a CSR batch by its distinct columns, which "
                f"every assembler sends; this one holds {sorted(shard)} and "
                "no 'cols' / 'slot' (tpu/device_iter.py col_slots makes "
                "them)")
        rows = params if isinstance(params, FMRows) else \
            self._gather_rows(params, shard)
        return _fm_margin_rows(rows, shard["slot"], shard["row"],
                               shard["val"], num_rows)

    def _shard_loss(self, params, shard, rows_per_shard):
        """(weighted loss sum, weight sum) — the shared objective zoo
        (models/linear.py objective_loss) over the FM margin."""
        margin = self._margin(params, shard, rows_per_shard)
        return objective_loss(margin, shard, rows_per_shard, self.objective)

    def _gather_rows(self, params, shard):
        """The rows at the shard's list (``[D, C]``, an owner's stretches,
        models/_dp.py, gives ``[D, C, ...]``). In the table form a trace
        tells the scatter into the dense gradient from this gather by the
        scope (.../transpose(jvp(fm.gather))/...)."""
        params, cols = self._params(params), shard["cols"]
        ascending = self._list_order(cols.shape) == "ascends"
        with jax.named_scope("fm.linear"):
            w = self._layout.w.rows(params.w, cols, ascending)
        with jax.named_scope("fm.gather"):
            v = self._layout.v.rows(params.v, cols, ascending)
        return FMRows(params.b, w, v)

    def _apply(self, params, grads, denom):
        # every row, an untouched one with g = 0; a lane-dense v's gradient
        # (the [F, K] view's transpose) is zero at the pad features
        return self._rule.update(params, self._params(grads), denom,
                                 _At(self._layout.w), _At(self._layout.v))

    def _apply_rows(self, params, cols, g, denom):
        """``_apply`` with the gradient as FMRows at ``cols`` (on a mesh
        every shard's ``[D, U]``): a write lands once a feature a shard,
        with one rounding at the parameter's magnitude as ``_apply`` has.
        The padding's reads fill zeros and its writes are dropped (it
        repeats one id: a scatter is told at most that its indices are
        sorted, uniqueness bought nothing on the chip, PERF.md section 6,
        PR 31)."""
        cols, g, ascending = self._ordered(cols, g)
        return self._rule.update(params, g, denom,
                                 _At(self._layout.w, cols, ascending),
                                 _At(self._layout.v, cols, ascending))

    def predict(self, params, batch) -> jnp.ndarray:
        """Margins [D, R] (apply sigmoid for probabilities) of this
        learner's state ``params``."""
        R = batch.rows_per_shard
        # one jitted fwd per rows-per-shard, cached on the learner — a
        # fresh @jax.jit closure per call would retrace every predict
        fwd = self._fwd_fn.get(R)
        if fwd is None:
            @jax.jit
            @jax.named_scope("fm.predict")
            def fwd(params, tree):
                return jax.vmap(lambda shard: self._margin(
                    params, shard, R))(unpack_tree(tree))
            self._fwd_fn[R] = fwd
        return fwd(params, batch.tree())
