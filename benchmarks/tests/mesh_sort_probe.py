#!/usr/bin/env python3
"""A replica's apply on the four-chip mesh, timed alone: the D shards'
sorted distinct lists laid end to end (the all-gathered list), scatter-added
into lane-dense ``v`` and into ``w`` as one unsorted list (what the step
does until the list is sorted), against the same list sorted once, stably,
on the device, the gradient's rows permuted by the sort, and both scatters
told that their list ascends (XLA's told scatter into ``[rows, 128]``
passes over the whole table once, then costs little an update).

    python3 benchmarks/tests/mesh_sort_probe.py [--iters 40] [--tiny]

Prints one JSON line: device ms a call of each form over ``--iters``
donated calls (the sorted forms with their sort and permutation, and the
told scatters alone on a list sorted beforehand), the largest gap of each
form's rows to the plain ``[F, K]`` form's, and whether the sorted whole
apply is at most 0.7 of the unsorted one (``gate``). ``--tiny``: the same
at a size for the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from layout_probe import cols_of, packed_at, pick, place, timed  # noqa: E402

LANES = 128


def sort_list(c):
    """``[D, U]`` to one ascending ``[D·U]`` list and the permutation that
    sorts it: stable, so a column that several shards name keeps the
    shards' order."""
    flat = c.reshape(-1)
    return jax.lax.sort((flat, jnp.arange(flat.shape[0], dtype=jnp.int32)),
                        num_keys=1, is_stable=True)


def mesh_sort(F, K, U, D, rng, iters):
    nrows = -(-F // (LANES // K))
    c_np = np.stack([cols_of(F, U, True, rng) for _ in range(D)])
    cols = jnp.asarray(c_np)
    upd = jnp.asarray(rng.normal(size=(D, U, K)).astype(np.float32) * 1e-3)
    wupd = jnp.asarray(rng.normal(size=(D, U)).astype(np.float32) * 1e-3)
    seen_np = np.unique(c_np[c_np < F])
    seen = jnp.asarray(seen_np)
    s_cols, s_perm = sort_list(cols)

    def v_unsorted(t, c, u):          # the step's one list, told nothing
        c = c.reshape(-1)
        return t.at[packed_at(c, F, K, nrows)].add(
            place(u.reshape(-1, K), c, K))

    def v_sorted(t, c, u):            # the sort and the permutation inside
        s, perm = sort_list(c)
        return t.at[packed_at(s, F, K, nrows)].add(
            place(u.reshape(-1, K)[perm], s, K), indices_are_sorted=True)

    def v_told(t, s, u):              # the told scatter alone
        return t.at[packed_at(s, F, K, nrows)].add(
            place(u, s, K), indices_are_sorted=True)

    def w_unsorted(w, c, g):          # the compiler sorts this list itself
        return w.at[c.reshape(-1)].add(g.reshape(-1))

    def w_sorted(w, c, g):
        s, perm = sort_list(c)
        return w.at[s].add(g.reshape(-1)[perm], indices_are_sorted=True)

    def w_told(w, s, g):
        return w.at[s].add(g, indices_are_sorted=True)

    def apply_unsorted(wv, c, ug):    # the step's dp.apply: w and v
        w, v = wv
        u, g = ug
        return w_unsorted(w, c, g), v_unsorted(v, c, u)

    def apply_sorted(wv, c, ug):      # one sort for both scatters
        w, v = wv
        u, g = ug
        s, perm = sort_list(c)
        return (w.at[s].add(g.reshape(-1)[perm], indices_are_sorted=True),
                v.at[packed_at(s, F, K, nrows)].add(
                    place(u.reshape(-1, K)[perm], s, K),
                    indices_are_sorted=True))

    def apply_step(wv, c, ug):        # models/fm.py: w's rows ride in the
        w, v = wv                     # sort, v's are gathered by its order
        u, g = ug
        flat = c.reshape(-1)
        s, perm, g_s = jax.lax.sort(
            (flat, jnp.arange(flat.shape[0], dtype=jnp.int32),
             g.reshape(-1)), num_keys=1, is_stable=True)
        rows = u.reshape(-1, K).at[perm].get(mode="promise_in_bounds",
                                             unique_indices=True)
        return (w.at[s].add(g_s, indices_are_sorted=True),
                v.at[packed_at(s, F, K, nrows)].add(
                    place(rows, s, K), indices_are_sorted=True))

    def plain(t, c, u):               # [F, K], one list: the reference
        return t.at[c.reshape(-1)].add(u.reshape(-1, K))

    def v_rows(t):
        return np.asarray(pick(t[packed_at(seen, F, K, nrows)], seen, K))

    rec = {"case": "mesh_sort", "F": F, "K": K, "U": U, "D": D,
           "table_rows": nrows,
           "cols_shared": int(D * U - np.unique(c_np).size)}
    key = jax.random.PRNGKey(3)
    t = jax.random.normal(key, (F, K), jnp.float32)
    rec["plain_one_list_ms"], t = timed(jax.jit(plain, donate_argnums=0),
                                        iters, t, cols, upd)
    ref = np.asarray(t[seen])
    del t
    rows, gaps = {}, {}
    for name, fn, args in (
            ("v_unsorted", v_unsorted, (cols, upd)),
            ("v_sorted", v_sorted, (cols, upd)),
            ("v_told", v_told, (s_cols, upd.reshape(-1, K)[s_perm]))):
        t = jax.random.normal(key, (nrows, LANES), jnp.float32)
        rec[name + "_ms"], t = timed(jax.jit(fn, donate_argnums=0), iters,
                                     t, *args)
        rows[name] = v_rows(t)
        gaps[name] = float(np.abs(rows[name] - ref).max())
        del t
    w0 = jnp.zeros((F,), jnp.float32)
    ws = {}
    for name, fn, args in (
            ("w_unsorted", w_unsorted, (cols, wupd)),
            ("w_sorted", w_sorted, (cols, wupd)),
            ("w_told", w_told, (s_cols, wupd.reshape(-1)[s_perm]))):
        rec[name + "_ms"], w = timed(jax.jit(fn, donate_argnums=0), iters,
                                     jnp.copy(w0), *args)
        ws[name] = np.asarray(w[seen])
        del w
    gaps["w_sorted"] = float(np.abs(ws["w_sorted"] - ws["w_unsorted"]).max())
    gaps["w_told"] = float(np.abs(ws["w_told"] - ws["w_unsorted"]).max())
    whole = {}
    for name, fn in (("apply_unsorted", apply_unsorted),
                     ("apply_sorted", apply_sorted),
                     ("apply_step", apply_step)):
        state = (jnp.copy(w0), jax.random.normal(key, (nrows, LANES),
                                                 jnp.float32))
        rec[name + "_ms"], state = timed(jax.jit(fn, donate_argnums=0),
                                         iters, state, cols, (upd, wupd))
        whole[name] = (np.asarray(state[0][seen]), v_rows(state[1]))
        del state
    for name in whole:
        gaps[name + "_vs_unsorted"] = float(max(
            np.abs(a - b).max() for a, b in zip(whole[name],
                                                 whole["apply_unsorted"])))
    rec["max_abs_gap"] = gaps
    # the same adds in the same order a lane, where the backend keeps the
    # list's order for a repeated index (the CPU's does)
    rec["sorted_equals_unsorted"] = bool(
        (rows["v_sorted"] == rows["v_unsorted"]).all()
        and (ws["w_sorted"] == ws["w_unsorted"]).all())
    rec["gate"] = rec["apply_sorted_ms"] <= 0.7 * rec["apply_unsorted_ms"]
    rec["v_gate"] = rec["v_sorted_ms"] <= 0.7 * rec["v_unsorted_ms"]
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    rng = np.random.default_rng(7)
    # kdd2012-fm-dp4: the global batch's four lists into one replica's tables
    F, K, U, D = 54686452, 16, 106496, 4
    if args.tiny:
        F, U = 10003, 512
    print(json.dumps(mesh_sort(F, K, U, D, rng, args.iters)), flush=True)


if __name__ == "__main__":
    main()
