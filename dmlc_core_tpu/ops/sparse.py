"""Sparse CSR ops over PaddedBatch shards.

The reference's only compute is Row::SDot (data.h:124-136), a scalar loop —
hostile to TPUs. Here the same math is expressed as XLA-friendly segment
operations over the PaddedBatch layout (per-nonzero row segment ids with a
sacrificial padding segment), and a dense materialization path for the MXU
when features are dense/low-dimensional.

All functions operate on ONE shard (no leading device axis): under
`shard_map` over the mesh "data" axis each device runs them on its local
shard, and segment ids never cross shards by construction
(see dmlc_core_tpu/tpu/device_iter.py).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

__all__ = ["csr_matvec", "csr_matmul_dense", "csr_to_dense", "row_sdot",
           "field_aware_matvec"]


def csr_matvec(row: jnp.ndarray, col: jnp.ndarray, val: jnp.ndarray,
               w: jnp.ndarray, num_rows: int) -> jnp.ndarray:
    """y[r] = Σ_{nz in row r} val * w[col]  (reference Row::SDot batched).

    row: [NNZ] local segment ids (padding entries == num_rows)
    Returns [num_rows]."""
    contrib = val * jnp.take(w, col, axis=0)
    y = jax.ops.segment_sum(contrib, row, num_segments=num_rows + 1,
                            indices_are_sorted=True)
    return y[:num_rows]


def csr_matmul_dense(row: jnp.ndarray, col: jnp.ndarray, val: jnp.ndarray,
                     W: jnp.ndarray, num_rows: int) -> jnp.ndarray:
    """[num_rows, K] = CSR · W for W [F, K] — rides the segment path with a
    gathered [NNZ, K] intermediate; prefer csr_to_dense+matmul when F is
    small (MXU path)."""
    contrib = val[:, None] * jnp.take(W, col, axis=0)  # [NNZ, K]
    y = jax.ops.segment_sum(contrib, row, num_segments=num_rows + 1,
                            indices_are_sorted=True)
    return y[:num_rows]


def csr_to_dense(row: jnp.ndarray, col: jnp.ndarray, val: jnp.ndarray,
                 num_rows: int, num_features: int,
                 impl: "str | None" = None) -> jnp.ndarray:
    """Materialize a dense [num_rows, num_features] shard — the MXU on-ramp
    for dense-ish data (e.g. HIGGS's 28 columns): downstream matmuls tile
    onto the systolic array instead of scatter units.

    impl: "xla" (scatter-add, the default), "pallas" (the scatter-as-
    matmul kernel, ops/pallas_kernels.py, compiled by Mosaic — TPU only),
    or None to read the DCT_CSR_TO_DENSE env var (trace-time; the opt-in
    switch for the device-side batch-formatting path)."""
    if impl is None:
        impl = os.environ.get("DCT_CSR_TO_DENSE", "xla")
    if impl == "pallas":
        # the kernel accumulates in f32 on the MXU: a silent f64/int cast
        # would change results beyond epsilon vs the XLA path, breaking
        # the drop-in-switch contract — refuse instead
        if jnp.asarray(val).dtype != jnp.float32:
            raise ValueError(
                f"csr_to_dense impl='pallas' supports float32 values only "
                f"(got {jnp.asarray(val).dtype}); use impl='xla'")
        from dmlc_core_tpu.ops.pallas_kernels import csr_to_dense_pallas
        return csr_to_dense_pallas(row, col, val, num_rows, num_features)
    if impl != "xla":
        raise ValueError(f"unknown csr_to_dense impl {impl!r} "
                         "(expected 'xla' or 'pallas')")
    dense = jnp.zeros((num_rows + 1, num_features), dtype=val.dtype)
    dense = dense.at[row, col].add(val)
    return dense[:num_rows]


def row_sdot(row: jnp.ndarray, col: jnp.ndarray, val: jnp.ndarray,
             w: jnp.ndarray, num_rows: int) -> jnp.ndarray:
    """Alias with reference naming (Row::SDot, data.h:124-136)."""
    return csr_matvec(row, col, val, w, num_rows)


def field_aware_matvec(row: jnp.ndarray, col: jnp.ndarray,
                       field: jnp.ndarray, val: jnp.ndarray,
                       W: jnp.ndarray, num_rows: int) -> jnp.ndarray:
    """y[r] = Σ_{nz in row r} val · W[field, col] — the field-aware linear
    margin consuming the PaddedBatch `field` plane (the device continuation
    of the reference libfm parser's per-nonzero field ids,
    src/data/libfm_parser.h:69-144).

    row/col/field/val: [NNZ]; W: [num_fields, num_features]. Padding
    nonzeros (val == 0, field == 0) contribute nothing. Returns [num_rows].
    """
    wij = W[field, col]  # [NNZ] gather
    y = jax.ops.segment_sum(val * wij, row, num_segments=num_rows + 1,
                            indices_are_sorted=True)
    return y[:num_rows]
