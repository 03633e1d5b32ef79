"""Pallas TPU kernels for the hot device-side batch transforms.

The one on-device transform SURVEY §7 calls out: CSR -> padded-dense batch
formatting. Scatter is hostile to the TPU's vector/matrix units (no fast
random writes across lanes), so the kernel reformulates it as matmuls —
the TPU-native move:

    col_mix[K, F] = val * onehot(col)        (VPU elementwise build)
    dense[R, F]  += onehot(rows)[R, K] @ col_mix[K, F]   (MXU)

The grid is (row tiles, feature tiles, nonzero chunks). TPU grid steps run
sequentially with the last axis fastest, so each [TILE_R, TILE_F] output
block stays resident while every K-sized chunk of nonzeros accumulates
into it (zero-init at chunk 0). Tiling both output axes bounds what one
step holds in VMEM by constants (``_STEP_VMEM_BYTES``), whatever the
shard's shape. Padding entries carry row == R and val == 0 (the
PaddedBatch layout contract, tpu/device_iter.py), so they fall out of the
one-hots naturally.

The kernel is compiled by Mosaic; ``interpret=True`` is for CPU tests,
which ask for it by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["csr_to_dense_pallas"]

# output tile: rows to a multiple of the f32 sublane (8), features to a
# multiple of the lane width (128)
_TILE_R = 256
_TILE_F = 512
# nonzeros per grid step; also the XLA layout tile of the 1-D int32
# operands, which Mosaic requires 1-D block shapes to align with
_CHUNK = 1024
# what one grid step allocates at the largest tile, counted per element of
# each one-hot side — int32 iota (4) + f32 one-hot (4) + the three bf16
# pieces Precision.HIGHEST splits an f32 MXU operand into (6) — plus the
# double-buffered f32 output block; it has to fit v5e's 16 MiB default
# scoped VMEM limit
_STEP_VMEM_BYTES = (14 * (_TILE_R * _CHUNK + _CHUNK * _TILE_F)
                    + 2 * 4 * _TILE_R * _TILE_F)
if _STEP_VMEM_BYTES > 16 << 20:
    raise ValueError(f"pallas tile constants need {_STEP_VMEM_BYTES} bytes "
                     "of VMEM per grid step, past the 16 MiB scoped limit")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tiling(num_rows: int, num_features: int) -> "tuple[int, int, int, int]":
    """(R_pad, F_pad, tile_r, tile_f): the padded output and its block.
    Rows get +1 for the sacrificial padding row; a dimension smaller
    than one tile is a single block of its own (8/128-rounded) size."""
    tile_r = min(_TILE_R, _round_up(num_rows + 1, 8))
    tile_f = min(_TILE_F, _round_up(num_features, 128))
    return (_round_up(num_rows + 1, tile_r),
            _round_up(num_features, tile_f), tile_r, tile_f)


def _vma_of(*operands) -> frozenset:
    """Union of the operands' varying-manual-axes sets (empty outside
    shard_map)."""
    vma = set()
    for op in operands:
        vma |= set(jax.typeof(op).vma)
    return frozenset(vma)


def _csr_scatter_kernel(row_ref, col_ref, val_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    r = row_ref[:]                      # [chunk] int32
    c = col_ref[:]
    v = val_ref[:]
    tile_r, tile_f = out_ref.shape
    chunk = r.shape[0]

    # scatter-as-matmul: one-hot membership built on the VPU against this
    # block's row/feature window, accumulated through one MXU matmul
    col_ids = (jax.lax.broadcasted_iota(jnp.int32, (chunk, tile_f), 1)
               + pl.program_id(1) * tile_f)
    col_mix = jnp.where(col_ids == c[:, None], v[:, None], 0.0)  # [K, F]
    row_ids = (jax.lax.broadcasted_iota(jnp.int32, (tile_r, chunk), 0)
               + pl.program_id(0) * tile_r)
    row_oh = (row_ids == r[None, :]).astype(jnp.float32)         # [R, K]
    # Precision.HIGHEST: the MXU's default bf16 multiply would round the
    # values on their way through the one-hot (row_oh entries are exact
    # 0/1, but col_mix carries the data)
    out_ref[:] += jnp.dot(row_oh, col_mix,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit,
                   static_argnames=("num_rows", "num_features", "interpret"))
def _csr_to_dense_call(row, col, val, num_rows: int, num_features: int,
                       interpret: bool):
    # nnz pads to whole chunks; pads carry row == num_rows (the
    # sacrificial row, sliced away below) and val == 0
    R_pad, F_pad, tile_r, tile_f = _tiling(num_rows, num_features)
    nnz = row.shape[0]
    nnz_pad = max(_round_up(nnz, _CHUNK), _CHUNK)
    if nnz_pad != nnz:
        pad = nnz_pad - nnz
        row = jnp.pad(row, (0, pad), constant_values=num_rows)
        col = jnp.pad(col, (0, pad))
        val = jnp.pad(val, (0, pad))

    nz_spec = pl.BlockSpec((_CHUNK,), lambda i, j, k: (k,))
    out = pl.pallas_call(
        _csr_scatter_kernel,
        # under shard_map's varying-type discipline the kernel output
        # varies over the same mesh axes its inputs do, and jax requires
        # that declared on the out_shape (empty outside shard_map)
        out_shape=jax.ShapeDtypeStruct((R_pad, F_pad), jnp.float32,
                                       vma=_vma_of(row, col, val)),
        grid=(R_pad // tile_r, F_pad // tile_f, nnz_pad // _CHUNK),
        in_specs=[nz_spec, nz_spec, nz_spec],
        out_specs=pl.BlockSpec((tile_r, tile_f), lambda i, j, k: (i, j)),
        interpret=interpret,
    )(row, col, val)
    return out[:num_rows, :num_features]


def csr_to_dense_pallas(row: jnp.ndarray, col: jnp.ndarray,
                        val: jnp.ndarray, num_rows: int, num_features: int,
                        interpret: bool = False) -> jnp.ndarray:
    """Pallas CSR -> dense [num_rows, num_features] (ops.sparse.csr_to_dense
    semantics: padding rows == num_rows dropped, duplicate (r, c) summed).

    ``interpret=True`` re-traces the kernel body as jax ops so CPU tests
    can run it; it cannot run inside ``shard_map``, whose varying-type
    checker rejects the body's iotas, and says so rather than computing
    something else.
    """
    if interpret and _vma_of(row, col, val):
        raise ValueError(
            "csr_to_dense_pallas(interpret=True) cannot run inside "
            "shard_map: the interpreted kernel body does not type-check "
            "under varying manual axes. Test the kernel outside "
            "shard_map; on a TPU the compiled kernel runs inside it")
    return _csr_to_dense_call(row, col, jnp.asarray(val, jnp.float32),
                              int(num_rows), int(num_features),
                              bool(interpret))
