#!/usr/bin/env python3
"""The factor table's scatter and gather alone, plain ``f32[F, K]`` (which
the compiler lays out feature-minor) against lane-dense ``f32[ceil(F/p),
128]`` (p = 128/K features a row, a feature's K floats one run of a row), at
the shapes of three one-chip cells and of the four-chip cell's apply on one
replica. The lists are a batch's sorted distinct columns, 96% real and the
rest the list's padding (an id beyond the table), uniform or skewed.

    python3 benchmarks/tests/layout_probe.py [--iters 40] [--tiny]

Prints one JSON line a case: device ms an operation over ``--iters`` donated
iterations, ns a row of the scatters, and whether the rows read back agree
across the forms (``--tiny``: the same cases at a size for the CPU).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

PAD = np.iinfo(np.int32).max
LANES = 128


def cols_of(F, U, skew, rng):
    """``[U]`` ascending distinct ids below ``F``, 96% of them, then the
    padding; ``skew``: drawn as ``F·u³``, so low ids recur."""
    real = int(U * 0.96)
    if skew:
        got = np.unique((F * rng.random(real * 3) ** 3).astype(np.int64))
        while got.size < real:
            got = np.unique(np.concatenate(
                [got, (F * rng.random(real) ** 3).astype(np.int64)]))
        got = np.sort(rng.choice(got, real, replace=False))
    else:
        got = np.sort(rng.choice(F, real, replace=False))
    out = np.full(U, PAD, np.int32)
    out[:real] = got
    return out


def place(rows, cols, K):
    """``[U, 128]``: each row of ``rows`` at its feature's K lanes."""
    group = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // K
    return jnp.where(group == (cols % (LANES // K))[:, None],
                     jnp.tile(rows, (1, LANES // K)), 0)


def packed_at(cols, F, K, nrows):
    """The lane-dense row of each column; the padding beyond the table."""
    return jnp.where(cols < F, cols // (LANES // K), nrows)


def pick(packed, cols, K):
    """``[U, K]``: each column's K lanes out of its whole row."""
    lane, out = (cols % (LANES // K))[:, None], packed[:, :K]
    for j in range(1, LANES // K):
        out = jnp.where(lane == j, packed[:, j * K:(j + 1) * K], out)
    return out


def timed(fn, iters, state, *args, donate=True):
    """Device ms a call, warm, over ``iters`` calls; the last result."""
    out = fn(state, *args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(out if donate else state, *args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / iters * 1e3, out


def one_list(name, F, K, U, skew, rng, iters):
    p, nrows = LANES // K, -(-F // (LANES // K))
    c_np = cols_of(F, U, skew, rng)
    cols = jnp.asarray(c_np)
    upd = jnp.asarray(rng.normal(size=(U, K)).astype(np.float32) * 1e-3)
    real = c_np < F
    rec = {"case": name, "F": F, "K": K, "U": U, "skew": skew,
           "rows_shared": int(real.sum() - np.unique(c_np[real] // p).size)}

    plain_scatter = jax.jit(lambda t, c, u: t.at[c].add(
        u, indices_are_sorted=True), donate_argnums=0)
    lane_scatter = jax.jit(lambda t, c, u: t.at[packed_at(c, F, K, nrows)]
                           .add(place(u, c, K), indices_are_sorted=True),
                           donate_argnums=0)
    plain_gather = jax.jit(lambda t, c: t.at[c].get(
        mode="fill", fill_value=0, indices_are_sorted=True))
    # fill the padding's whole rows, then pick K lanes (models/fm.py)
    lane_gather_fill = jax.jit(lambda t, c: pick(
        t.at[packed_at(c, F, K, nrows)].get(
            mode="fill", fill_value=0, indices_are_sorted=True), c, K))
    # clamp the row, pick K lanes, then zero the padding's K lanes

    def clip(t, c):
        packed = t.at[jnp.minimum(c // p, nrows - 1)].get(
            mode="promise_in_bounds", indices_are_sorted=True)
        return jnp.where((c < F)[:, None], pick(packed, c, K), 0)
    lane_gather_clip = jax.jit(clip)

    key = jax.random.PRNGKey(3)
    t = jax.random.normal(key, (F, K), jnp.float32)
    rec["plain_scatter_ms"], t = timed(plain_scatter, iters, t, cols, upd)
    rec["plain_gather_ms"], g_plain = timed(plain_gather, iters, t, cols,
                                            donate=False)
    g_plain = np.asarray(g_plain)
    del t
    t = jax.random.normal(key, (nrows, LANES), jnp.float32)
    rec["lane_scatter_ms"], t = timed(lane_scatter, iters, t, cols, upd)
    rec["lane_gather_fill_ms"], g_fill = timed(lane_gather_fill, iters, t,
                                               cols, donate=False)
    rec["lane_gather_clip_ms"], g_clip = timed(lane_gather_clip, iters, t,
                                               cols, donate=False)
    g_fill, g_clip = np.asarray(g_fill), np.asarray(g_clip)
    del t
    # one flat draw and the same adds in the same order: equal to the bit
    rec["rows_equal"] = bool((g_plain[real] == g_fill[real]).all()
                             and (g_clip == g_fill).all())
    rec["plain_ns_per_row"] = rec["plain_scatter_ms"] * 1e6 / U
    rec["lane_ns_per_row"] = rec["lane_scatter_ms"] * 1e6 / U
    return rec


def shard_lists(F, K, U, D, rng, iters):
    """A replica's apply on the mesh: D shards' sorted lists, which share
    columns, into one table."""
    nrows = -(-F // (LANES // K))
    c_np = np.stack([cols_of(F, U, True, rng) for _ in range(D)])
    cols = jnp.asarray(c_np)
    upd = jnp.asarray(rng.normal(size=(D, U, K)).astype(np.float32) * 1e-3)
    seen = jnp.asarray(np.unique(c_np[c_np < F]))

    def plain_one_list(t, c, u):      # the [F, K] form's: one unsorted list
        return t.at[c.reshape(-1)].add(u.reshape(-1, K))

    def lane_one_list(t, c, u):
        c = c.reshape(-1)
        return t.at[packed_at(c, F, K, nrows)].add(
            place(u.reshape(-1, K), c, K))

    def lane_by_shard(t, c, u):       # models/fm.py on a mesh
        for d in range(D):
            t = t.at[packed_at(c[d], F, K, nrows)].add(
                place(u[d], c[d], K), indices_are_sorted=True)
        return t

    rec = {"case": "mesh_apply", "F": F, "K": K, "U": U, "D": D,
           "cols_shared": int(D * U - np.unique(c_np).size)}
    key, rows = jax.random.PRNGKey(3), {}
    for name, fn, shape in (
            ("plain_one_list", plain_one_list, (F, K)),
            ("lane_one_list", lane_one_list, (nrows, LANES)),
            ("lane_by_shard", lane_by_shard, (nrows, LANES))):
        t = jax.random.normal(key, shape, jnp.float32)
        rec[name + "_ms"], t = timed(jax.jit(fn, donate_argnums=0), iters,
                                     t, cols, upd)
        rows[name] = np.asarray(t[seen] if shape == (F, K) else pick(
            t[packed_at(seen, F, K, nrows)], seen, K))
        del t
    # the adds to a shared row come in another order: equal to rounding
    rec["max_abs_gap"] = float(max(
        np.abs(r - rows["plain_one_list"]).max() for r in rows.values()))
    return rec


def rows_sweep(F, K, U, rng, iters):
    """The lane-dense scatter alone at one table and list size."""
    nrows = -(-F // (LANES // K))
    cols = jnp.asarray(cols_of(F, U, True, rng))
    upd = jnp.asarray(rng.normal(size=(U, K)).astype(np.float32) * 1e-3)
    scatter = jax.jit(lambda t, c, u: t.at[packed_at(c, F, K, nrows)].add(
        place(u, c, K), indices_are_sorted=True), donate_argnums=0)
    t = jax.random.normal(jax.random.PRNGKey(3), (nrows, LANES), jnp.float32)
    ms, t = timed(scatter, iters, t, cols, upd)
    del t
    return {"case": "lane_scatter", "F": F, "K": K, "U": U,
            "table_rows": nrows, "lane_scatter_ms": ms,
            "lane_ns_per_row": ms * 1e6 / U}


def scatter_forms(F, K, U, rng, iters):
    """The lane-dense scatter written five ways, at one table and list
    size: whether any of them leaves the table's untouched rows alone."""
    nrows = -(-F // (LANES // K))
    c_np = cols_of(F, U, True, rng)
    cols = jnp.asarray(c_np)
    upd = jnp.asarray(rng.normal(size=(U, K)).astype(np.float32) * 1e-3)
    GS = jax.lax.GatherScatterMode

    def drop(t, c, u):                 # models/fm.py
        return t.at[packed_at(c, F, K, nrows)].add(
            place(u, c, K), indices_are_sorted=True)

    def clamped(t, c, u):              # the padding adds -0.0 to the last row
        at = jnp.minimum(c // (LANES // K), nrows - 1)
        placed = jnp.where((c < F)[:, None], place(u, c, K), -0.0)
        return t.at[at].add(placed, indices_are_sorted=True,
                            mode=GS.PROMISE_IN_BOUNDS)

    def tiles(t, c, u):                # [rows/8, 8, 128]: a sublane a row
        at = packed_at(c, F, K, nrows)
        t3 = t.reshape(-1, 8, LANES)
        return t3.at[at // 8, at % 8].add(
            place(u, c, K), indices_are_sorted=True).reshape(t.shape)

    def unsorted(t, c, u):
        return t.at[packed_at(c, F, K, nrows)].add(place(u, c, K))

    def unique(t, c, u):               # timing only: the list shares rows
        return t.at[packed_at(c, F, K, nrows)].add(
            place(u, c, K), indices_are_sorted=True, unique_indices=True)

    rec = {"case": "scatter_forms", "F": F, "K": K, "U": U,
           "table_rows": nrows}
    rows8 = -(-nrows // 8) * 8
    for name, fn in (("drop", drop), ("clamped", clamped), ("tiles", tiles),
                     ("unsorted", unsorted), ("unique", unique)):
        t = jax.random.normal(jax.random.PRNGKey(3),
                              (rows8 if name == "tiles" else nrows, LANES),
                              jnp.float32)
        rec[name + "_ms"], t = timed(jax.jit(fn, donate_argnums=0), iters,
                                     t, cols, upd)
        del t
    return rec


def pallas_scatter_add(table, at, placed, block=1024, interpret=False):
    """``table`` ``[N, 128]`` with the rows of ``placed`` added at ``at``
    (ascending; an id outside the table is skipped), by hand: a block of
    the list at a time, each distinct row read by one DMA, the run of
    updates that names it summed into it, and written back by one DMA."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    U, N = at.shape[0], table.shape[0]
    Up = -(-U // block) * block
    at = jnp.pad(at, (0, Up - U), constant_values=-1)
    placed = jnp.pad(placed, ((0, Up - U), (0, 0)))

    def kernel(at_ref, upd_ref, _, out_ref, buf, sem):
        def valid(r):
            return (at_ref[r] >= 0) & (at_ref[r] < N)

        def head(r):
            return valid(r) & ((r == 0) | (
                at_ref[r] != at_ref[jnp.maximum(r - 1, 0)]))

        def row_dma(r, to_table, i):
            table_row = out_ref.at[pl.ds(at_ref[r], 1)]
            buf_row = buf.at[pl.ds(r, 1)]
            return pltpu.make_async_copy(
                *((buf_row, table_row) if to_table else (table_row, buf_row)),
                sem.at[i])

        def each(fn):
            def body(r, carry):
                @pl.when(head(r))
                def _():
                    fn(r)
                return carry
            jax.lax.fori_loop(0, block, body, 0)

        each(lambda r: row_dma(r, False, 0).start())
        each(lambda r: row_dma(r, False, 0).wait())

        def acc(r, h):
            h = jnp.where(head(r), r, h)

            @pl.when(valid(r))
            def _():
                buf[pl.ds(h, 1), :] = (buf[pl.ds(h, 1), :]
                                       + upd_ref[pl.ds(r, 1), :])
            return h
        jax.lax.fori_loop(0, block, acc, 0)
        each(lambda r: row_dma(r, True, 1).start())
        each(lambda r: row_dma(r, True, 1).wait())

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        grid=(Up // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((block, LANES), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(at, placed, table)


def pallas_form(F, K, U, rng, iters, interpret=False):
    """The hand-written scatter against XLA's, at one size: ms and whether
    the tables agree."""
    nrows = -(-F // (LANES // K))
    cols = jnp.asarray(cols_of(F, U, True, rng))
    upd = jnp.asarray(rng.normal(size=(U, K)).astype(np.float32) * 1e-3)
    xla = jax.jit(lambda t, c, u: t.at[packed_at(c, F, K, nrows)].add(
        place(u, c, K), indices_are_sorted=True), donate_argnums=0)
    hand = jax.jit(lambda t, c, u: pallas_scatter_add(
        t, packed_at(c, F, K, nrows), place(u, c, K), interpret=interpret),
        donate_argnums=0)
    rec = {"case": "pallas_scatter", "F": F, "K": K, "U": U,
           "table_rows": nrows}
    seen = jnp.asarray(np.unique(np.asarray(packed_at(cols, F, K, nrows))
                                 [:int(U * 0.96)]))
    rows = {}
    for name, fn in (("xla", xla), ("pallas", hand)):
        t = jax.random.normal(jax.random.PRNGKey(3), (nrows, LANES),
                              jnp.float32)
        rec[name + "_ms"], t = timed(fn, iters, t, cols, upd)
        rows[name] = np.asarray(t[seen])
        rec[name + "_last_row"] = float(np.asarray(t[nrows - 1]).sum())
        del t
    rec["rows_equal"] = bool((rows["xla"] == rows["pallas"]).all())
    rec["max_abs_gap"] = float(np.abs(rows["xla"] - rows["pallas"]).max())
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--sweep", default="",
                    help="F:K:U,... : the lane-dense scatter alone at these "
                    "sizes, in place of the cells' cases")
    ap.add_argument("--forms", default="",
                    help="F:K:U,... : the lane-dense scatter written five "
                    "ways at these sizes")
    ap.add_argument("--pallas", default="",
                    help="F:K:U,... : a hand-written scatter against XLA's "
                    "(with --tiny: in the interpreter)")
    args = ap.parse_args(argv)
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    rng = np.random.default_rng(5)
    # (cell, F, K, U): kdd2012-fm, criteo1tb-fm, kdd2010b-fm
    cases = (("kdd2012-fm", 54686456, 16, 106496),
             ("criteo1tb-fm", 2 ** 25, 16, 212992),
             ("kdd2010b-fm", 29890096, 32, 262144))
    mesh = (54686456, 16, 106496, 4)          # kdd2012-fm-dp4
    if args.tiny:
        cases = (("tiny16", 10003, 16, 512), ("tiny32", 10001, 32, 512))
        mesh = (10003, 16, 512, 4)
    if args.pallas:
        for spec in args.pallas.split(","):
            F, K, U = (int(x) for x in spec.split(":"))
            print(json.dumps(pallas_form(F, K, U, rng, args.iters,
                                         interpret=args.tiny)), flush=True)
        return
    if args.forms:
        for spec in args.forms.split(","):
            F, K, U = (int(x) for x in spec.split(":"))
            print(json.dumps(scatter_forms(F, K, U, rng, args.iters)),
                  flush=True)
        return
    if args.sweep:
        for spec in args.sweep.split(","):
            F, K, U = (int(x) for x in spec.split(":"))
            print(json.dumps(rows_sweep(F, K, U, rng, args.iters)),
                  flush=True)
        return
    for name, F, K, U in cases:
        for skew in (True, False):
            print(json.dumps(one_list(name, F, K, U, skew, rng, args.iters)),
                  flush=True)
    print(json.dumps(shard_lists(*mesh, rng, args.iters)), flush=True)


if __name__ == "__main__":
    main()
