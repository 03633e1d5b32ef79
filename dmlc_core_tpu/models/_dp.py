"""Shared data-parallel step harness for the margin models.

LinearLearner and FMLearner differ only in their parameter pytrees, margin
computation, and SGD update; everything about running a step over a device
batch is identical — unpack the packed batch per shard, take
value_and_grad of the shard loss, apply the update, and jit-cache per batch
shape. That harness lives here once.

The gradient has one of three forms. The first two are chosen when the step
is built from what the batch and the model show (``_takes_row_form``); the
third by how the model was told to keep its tables (``table_layout``):

- table form (every dense batch; every model without the row hooks):
  value_and_grad with respect to the parameters, so the gradient is a
  pytree of the parameters' shapes; on a mesh the (loss, weight, grad)
  triple is psummed once over ICI (the Rabit allreduce equivalent, SURVEY
  §2.5) and ``_apply`` writes the new tables from the old ones and it.
- row form (a CSR batch, for a model that has the two row hooks): the model
  gathers the rows its shard reads, value_and_grad is taken of the same
  ``_shard_loss`` with respect to those rows, and ``_apply_rows``
  scatter-adds the gradient's rows into the tables at the shard's distinct
  columns ``cols``: the gradient as (indices, rows), one row a feature
  however often the shard names it. No table of the parameters' shape is
  made beside the parameters in and out. On one device nothing is
  exchanged: there is one shard. On a mesh of several every device does
  the same for its own shard, then the shards' lists and the rows of their
  gradients are all-gathered (``[D, U]``, ``[D, U, ...]``: what the shards
  touched, not a table; loss, weight and what every shard reads whole are
  psummed beside them) and every replica scatter-adds all of them, in the
  mesh's order, so the replicas stay bit-identical. A column several
  shards name is added to once a shard. (Cell kdd2012-fm-dp4.libfm runs
  it; the all-reduce of the table it replaced took 65 of the step's
  115 ms there, PERF.md section 6, PR 33.)
- row form on range-sharded tables (``table_layout="range_sharded"``: a
  CSR batch whose ``cols`` is owner-major, tpu/device_iter.py col_slots):
  no device holds a whole table. The leaves ``_table_specs`` names are
  sharded over the mesh by contiguous ranges of rows, a row's owner the
  device of its range: what ps-lite's servers are to wormhole's workers,
  server d and worker d on device d. A shard's list is ascending, so the
  columns one owner holds are one stretch of it, at a static capacity
  ``C``. The step pulls (scope ``dp.pull``: an all-to-all of the id
  stretches ``[D, C]``, every owner gathers the ``D * C`` rows asked of
  its range, ids less the range's first row, padding reads zeros, and an
  all-to-all takes them back), differentiates exactly as the row form
  does, pushes (``dp.push``: an all-to-all of the gradient's rows to their
  owners; loss, weight and what every shard reads whole are psummed) and
  every owner scatter-adds the ``D * C`` rows it got into its own range,
  in the mesh's order (``dp.apply``). A column several shards name is
  added to once a shard, as on replicated tables; what every shard reads
  whole (a scalar) stays replicated and bit-identical. (Cell
  criteo1tb-fm-ps4.tsv runs it: 2^27 rows of 17 floats, which one chip
  cannot hold twice; PERF.md section 6, PR 39.)

The phases of the jitted step carry ``jax.named_scope``s (``dp.unpack``,
``dp.loss_grad``, ``dp.allreduce``, ``dp.apply``; the models add their own
inside ``dp.loss_grad``), which change the operations' ``op_name`` metadata
only: a device trace then names its time by phase whatever the compiler
numbers its fusions (doc/observability.md "Device lane").

Every form is compiled with its state donated (``donate_argnums=(0,)``):
the new tables are written into the buffers of the old, so a step holds one
table where it held two and no pass copies a table before the scatter (that
copy was 11 to 33% of the step, PERF.md section 6, PR 40). ``step`` hands the
compiled step a state as it is only when it is the one the learner's own
last step returned; any other is the caller's and is copied first
(``_own_state``).

Subclasses implement:
  _shard_loss(params, shard, rows_per_shard) -> (loss_sum, weight_sum)
  _apply(params, grads, denom) -> new params
and may implement, for CSR shards (both or neither):
  _gather_rows(params, shard) -> rows, a pytree that ``_shard_loss`` takes
      in the place of ``params``: leaves ``[U, ...]``, a row a column of
      the shard's list, and scalars the shard reads whole
  _apply_rows(params, cols, row_grads, denom) -> new params; ``cols`` is
      one shard's list ``[U]`` with ``row_grads`` as the rows were, or on a
      mesh every shard's ``[D, U]`` with leaves ``[D, U, ...]`` and the
      scalars summed
and for range-sharded tables, with the row hooks (``_gather_rows`` then
also takes lists ``[D, C]``, stretch after stretch, and gives leaves
``[D, C, ...]``):
  table_layout = "range_sharded", col_owners = (owners, rows an owner)
  _table_specs() -> the parameters' PartitionSpecs, a leaf each: ``P(axis)``
      on the row axis of a table, ``P()`` for what every shard reads whole
"""

from __future__ import annotations

import functools
import weakref
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.base import DMLCError
from dmlc_core_tpu.parallel.varying import (gather_unvarying,
                                            mark_varying)
from dmlc_core_tpu.tpu.device_iter import unpack_shard

__all__ = ["DataParallelModel"]


class DataParallelModel:
    """Mixin: the data-parallel step (``shard_map`` on a mesh, its exchange
    under ``dp.allreduce``) over packed or named batch trees."""

    mesh: Optional[Mesh]
    axis_name: str
    table_layout: str = "replicated"

    def _shard_loss(self, params, shard, rows_per_shard: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def _apply(self, params, grads, denom):
        raise NotImplementedError

    # the row hooks; None: the model has the table form only
    _gather_rows = None
    _apply_rows = None

    def _takes_row_form(self, keys) -> bool:
        """Whether a batch tree of these leaves steps in the row form: the
        model has the hooks and the batch is CSR (the distinct columns
        ``cols`` travel with it, and no dense ``x``), on any mesh."""
        return (self._gather_rows is not None
                and "cols" in keys and "x" not in keys)

    def _build_step(self, rows_per_shard: int, keys: tuple):
        axis = self.axis_name
        # every batch leaf is shard-major (device axis leads) since the
        # device_iter packing migration — packed and named alike
        tree_keys = [(k, P(axis)) for k in keys]

        def shard_view(tree):
            """Drop the device axis and unpack aux/big into named arrays
            (a bitcast+slice — free inside the jitted step)."""
            with jax.named_scope("dp.unpack"):
                local = {k: v[0] for k, v in tree.items()}
                return unpack_shard(local)

        def local_grads(params, shard):
            """``params``: the parameters, or the rows the shard gathered
            from them."""
            def loss_fn(p):
                return self._shard_loss(p, shard, rows_per_shard)
            with jax.named_scope("dp.loss_grad"):
                (loss_sum, wsum), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
            return loss_sum, wsum, grads

        def apply(update, loss_sum, wsum):
            with jax.named_scope("dp.apply"):
                denom = jnp.maximum(wsum, 1.0)
                return update(denom), loss_sum / denom

        row_form = self._takes_row_form(keys)
        if self.table_layout == "range_sharded":
            return self._build_owner_step(tree_keys, shard_view, local_grads,
                                          apply)
        if row_form and (self.mesh is None or self.mesh.devices.size == 1):
            # the benchmark finds the step's module by this name
            # (tests/test_benchmark_names.py holds it, here and below)
            def sharded_step(params, tree):
                shard = shard_view(tree)
                with jax.named_scope("dp.loss_grad"):
                    rows = self._gather_rows(params, shard)
                loss_sum, wsum, row_grads = local_grads(rows, shard)
                return apply(lambda denom: self._apply_rows(
                    params, shard["cols"], row_grads, denom), loss_sum, wsum)
            return jax.jit(sharded_step, donate_argnums=(0,))

        if self.mesh is None:
            def step(params, tree):
                loss_sum, wsum, grads = local_grads(params, shard_view(tree))
                return apply(lambda denom: self._apply(params, grads, denom),
                             loss_sum, wsum)
            return jax.jit(step, donate_argnums=(0,))

        # the benchmark finds the step's module by this name
        # (tests/test_benchmark_names.py)
        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=(P(), dict(tree_keys)),
                           out_specs=(P(), P()))
        def sharded_step(params, tree):
            shard = shard_view(tree)  # drop device axis + unpack
            # the replicated params are differentiated as device-varying:
            # typed unvarying, autodiff's transpose psums their cotangent
            # by itself and the explicit psum below would count the
            # gradient once per device
            local = mark_varying(params, (axis,))
            if row_form:
                with jax.named_scope("dp.loss_grad"):
                    local = self._gather_rows(local, shard)
            loss_sum, wsum, grads = local_grads(local, shard)
            if row_form:
                # the rows go into the exchange as the leaves they are:
                # where a model's backward makes one as a slice of a wider
                # array (the FM's w out of its merged [U, K+1] gradient),
                # the compiler would sink the reshape behind the all-gather
                # and move [D*U, 1] padded to 128 lanes (PERF.md section 6,
                # PR 35). Outside dp.allreduce: the copy it may cost is no
                # part of the exchange
                grads = jax.lax.optimization_barrier(grads)
            # ONE exchange per step over ICI — the Rabit allreduce
            # equivalent (SURVEY §2.5)
            with jax.named_scope("dp.allreduce"):
                loss_sum = jax.lax.psum(loss_sum, axis)
                wsum = jax.lax.psum(wsum, axis)
                if row_form:
                    # every shard's list and the rows of its gradient, in
                    # the mesh's order and the same on every device; what
                    # the shards read whole (a scalar) is summed
                    cols = gather_unvarying(shard["cols"], axis)
                    grads = jax.tree.map(
                        lambda g: gather_unvarying(g, axis) if g.ndim
                        else jax.lax.psum(g, axis), grads)

                    def update(denom):
                        return self._apply_rows(params, cols, grads, denom)
                else:
                    grads = jax.tree.map(lambda g: jax.lax.psum(g, axis),
                                         grads)

                    def update(denom):
                        return self._apply(params, grads, denom)
            return apply(update, loss_sum, wsum)

        return jax.jit(sharded_step, donate_argnums=(0,))

    def _build_owner_step(self, tree_keys, shard_view, local_grads, apply):
        """The row form on range-sharded tables (the module docstring's
        third form): pull, the row form's gradient, push, and the update by
        each row's owner alone."""
        axis = self.axis_name
        n_dev = int(self.mesh.devices.size)
        owner_rows = self.col_owners[1]
        specs = self._table_specs()

        def exchange(leaf):
            """``[D, C, ...]``, stretch d for device d, against the
            stretches the devices hold for this one, in the mesh's order."""
            return jax.lax.all_to_all(leaf, axis, 0, 0, tiled=True)

        # the benchmark finds the step's module by this name
        # (tests/test_benchmark_names.py)
        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=(specs, dict(tree_keys)),
                           out_specs=(specs, P()))
        def sharded_step(params, tree):
            shard = shard_view(tree)
            with jax.named_scope("dp.pull"):
                # row d: the columns shard d reads of this owner's range,
                # as rows of the range (the padding stays beyond it)
                asked = exchange(shard["cols"].reshape(n_dev, -1)) \
                    - jax.lax.axis_index(axis) * owner_rows
                # what every shard reads whole is differentiated as
                # device-varying, as the replicated step's parameters are
                local = type(params)(*(
                    mark_varying(p, (axis,)) if spec == P() else p
                    for p, spec in zip(params, specs)))
                rows = jax.tree.map(
                    lambda r: exchange(r).reshape((-1,) + r.shape[2:])
                    if r.ndim else r,
                    self._gather_rows(local, {"cols": asked}))
            loss_sum, wsum, grads = local_grads(rows, shard)
            # the rows go into the exchange as the leaves they are (the
            # replicated row form's reason, PERF.md section 6, PR 35)
            grads = jax.lax.optimization_barrier(grads)
            with jax.named_scope("dp.push"):
                loss_sum = jax.lax.psum(loss_sum, axis)
                wsum = jax.lax.psum(wsum, axis)
                grads = jax.tree.map(
                    lambda g: exchange(g.reshape(asked.shape + g.shape[1:]))
                    if g.ndim else jax.lax.psum(g, axis), grads)
            return apply(lambda denom: self._apply_rows(params, asked, grads,
                                                        denom),
                         loss_sum, wsum)

        return jax.jit(sharded_step, donate_argnums=(0,))

    def _exchange_bytes(self, params, tree, n_dev: int) -> int:
        """What a step on ``n_dev`` devices hands to its collectives, from
        the shapes: loss sum and weight sum, and then a gradient of the
        parameters' shapes (table form) or every shard's list with the
        rows of its gradient, what the shards read whole counted once (row
        form; on range-sharded tables the rows twice, pulled and pushed).
        Nothing on one device."""
        if n_dev == 1:
            return 0
        if not self._takes_row_form(tree):
            return 8 + sum(p.size * p.dtype.itemsize
                           for p in jax.tree.leaves(params))
        shard = jax.eval_shape(
            lambda t: unpack_shard({k: v[0] for k, v in t.items()}), tree)
        rows = jax.tree.leaves(jax.eval_shape(self._gather_rows, params,
                                              shard))
        trips = 2 if self.table_layout == "range_sharded" else 1
        cols = shard["cols"]
        return 8 + n_dev * cols.size * cols.dtype.itemsize + sum(
            r.dtype.itemsize * (trips * n_dev * r.size if r.ndim else 1)
            for r in rows)

    def _own_state(self, params):
        """``params`` as the donating step may consume it: itself where
        every leaf *is* (the object, not an equal one) the leaf this
        learner's last step returned; else (the first call's, one from
        ``init``, a restored checkpoint, another learner's, a tree with a
        leaf replaced) a copy made on the device, leaf by leaf with its
        sharding, so that nothing of the caller's is deleted."""
        leaves = jax.tree.leaves(params)
        # weak references (``_run``): they keep nothing on the device alive
        last = getattr(self, "_returned", ())
        if len(last) == len(leaves) and all(
                ref() is leaf for ref, leaf in zip(last, leaves)):
            return params
        # beside model_step_dispatch_us' count: the share of steps that ran
        # in place (doc/observability.md "Device lane")
        telemetry.counter("model_step_state_copies_total",
                          {"model": type(self).__name__}).inc()
        return jax.tree.map(jnp.copy, params)

    def _run(self, fn, params, tree):
        """The compiled step on a state it may consume; what it returns is
        remembered as the learner's own."""
        out = fn(self._own_state(params), tree)
        self._returned = tuple(weakref.ref(leaf)
                               for leaf in jax.tree.leaves(out[0]))
        return out

    def step(self, params, batch):
        """One jitted training step on a device batch; returns
        (params, loss). The state is updated where it lies: one this
        learner's last ``step`` returned is consumed (its arrays are
        deleted), any other is copied first and survives.

        So ``params, loss = learner.step(params, batch)`` holds one table
        where a step that kept its input held two, and reading a consumed
        state, or handing it in again, raises jax's error for a deleted
        array: copy a returned state you mean to keep (``jnp.copy`` a
        leaf). A state the learner did not itself return (``init()``, a
        restored checkpoint, arrays of your own) is copied once on the
        device and the copy is consumed (``model_step_state_copies_total``
        counts those)."""
        if getattr(self, "_step_fn", None) is None:
            self._step_fn, self._step_bytes = {}, {}
        tree = batch.tree()
        D = (tree["aux"].shape[0] if "aux" in tree
             else tree["label"].shape[0])
        n_dev = 1 if self.mesh is None else int(self.mesh.devices.size)
        if D != n_dev:
            # the step reads shard block[0] only — a mismatch would
            # silently train on 1/D of the rows
            raise ValueError(
                f"batch device axis D={D} != mesh size {n_dev}; "
                f"build the batch with num_shards={n_dev}")
        owners = getattr(batch, "owners", 1)
        if self.table_layout == "range_sharded" and owners != n_dev:
            # a plain list cut in equal parts would send ids to owners that
            # do not hold them
            raise DMLCError(
                f"range-sharded tables read a CSR batch whose distinct "
                f"columns are laid out by their {n_dev} owners; this one "
                f"has {owners}: build the iterator with "
                f"col_owners=learner.col_owners")
        sig = tuple((k, tuple(v.shape)) for k, v in sorted(tree.items()))
        fn = self._step_fn.get(sig)
        built = fn is None
        if built:
            fn = self._step_fn[sig] = self._build_step(
                batch.rows_per_shard, tuple(sorted(tree.keys())))
            telemetry.counter("model_step_builds_total",
                              {"model": type(self).__name__}).inc()
            self._step_bytes[sig] = self._exchange_bytes(params, tree, n_dev)
        if not telemetry.enabled():
            return self._run(fn, params, tree)
        # model.step: the host's hand-over of one step to the runtime (the
        # call that built the function also traces and compiles inside it,
        # and a state that is not the learner's own is copied inside it)
        with telemetry.span("model.step", built=int(built)) as sp:
            out = self._run(fn, params, tree)
            telemetry.histogram("model_step_dispatch_us").observe(
                sp.elapsed_us)
        # counted beside the histogram: the two counts give the share of
        # steps that took the row form
        if self._takes_row_form(tree):
            telemetry.counter("model_step_row_updates_total",
                              {"model": type(self).__name__}).inc()
        # beside it, the steps of a rule with a state of its own
        rule = getattr(self, "updater", "sgd")
        if rule != "sgd":
            telemetry.counter("model_step_rule_updates_total",
                              {"model": type(self).__name__,
                               "rule": rule}).inc()
        # with the device time under scope dp.allreduce, the exchange's rate
        if self._step_bytes[sig]:
            telemetry.counter("model_step_allreduce_bytes_total",
                              {"model": type(self).__name__}).inc(
                                  self._step_bytes[sig])
        return out
