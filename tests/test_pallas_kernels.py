"""Pallas CSR->dense kernel vs the XLA scatter oracle. These tests ask for
interpret mode by name; the compiled kernel is checked on the chip by
chip_smoke.py."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.ops import pallas_kernels
from dmlc_core_tpu.ops.sparse import csr_to_dense

csr_to_dense_pallas = functools.partial(pallas_kernels.csr_to_dense_pallas,
                                        interpret=True)


@pytest.fixture
def interpreted_switch(monkeypatch):
    """Route the csr_to_dense(impl="pallas") switch through interpret
    mode: there is no Mosaic on the CPU backend."""
    monkeypatch.setattr(pallas_kernels, "csr_to_dense_pallas",
                        csr_to_dense_pallas)


def random_csr(rng, R, F, nnz, pad=0):
    row = np.sort(rng.integers(0, R, nnz)).astype(np.int32)
    col = rng.integers(0, F, nnz).astype(np.int32)
    val = rng.normal(size=nnz).astype(np.float32)
    if pad:
        row = np.concatenate([row, np.full(pad, R, np.int32)])
        col = np.concatenate([col, np.zeros(pad, np.int32)])
        val = np.concatenate([val, np.zeros(pad, np.float32)])
    return jnp.asarray(row), jnp.asarray(col), jnp.asarray(val)


# the last case spans several row tiles, feature tiles and nnz chunks
@pytest.mark.parametrize("R,F,nnz", [(8, 28, 100), (17, 130, 999),
                                     (3, 5, 1), (64, 256, 4096),
                                     (300, 700, 2500)])
def test_matches_xla_scatter(R, F, nnz):
    rng = np.random.default_rng(R * F + nnz)
    row, col, val = random_csr(rng, R, F, nnz)
    got = csr_to_dense_pallas(row, col, val, R, F)
    want = csr_to_dense(row, col, val, R, F)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_padding_rows_dropped():
    # entries with row == num_rows are the PaddedBatch sacrificial slot
    rng = np.random.default_rng(0)
    row, col, val = random_csr(rng, 8, 16, 50, pad=30)
    got = csr_to_dense_pallas(row, col, val, 8, 16)
    want = csr_to_dense(row, col, val, 8, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_duplicate_coordinates_sum():
    row = jnp.asarray([0, 0, 0], jnp.int32)
    col = jnp.asarray([2, 2, 2], jnp.int32)
    val = jnp.asarray([1.0, 2.0, 3.5], jnp.float32)
    got = csr_to_dense_pallas(row, col, val, 2, 4)
    assert float(got[0, 2]) == pytest.approx(6.5)
    assert float(np.abs(np.asarray(got)).sum()) == pytest.approx(6.5)


def test_empty_matrix():
    row = jnp.zeros((0,), jnp.int32)
    col = jnp.zeros((0,), jnp.int32)
    val = jnp.zeros((0,), jnp.float32)
    got = csr_to_dense_pallas(row, col, val, 4, 8)
    assert got.shape == (4, 8)
    assert float(np.abs(np.asarray(got)).sum()) == 0.0


def test_csr_to_dense_impl_switch(monkeypatch, interpreted_switch):
    # the opt-in device-side formatting path: explicit impl= and the
    # DCT_CSR_TO_DENSE env both dispatch to the Pallas kernel
    rng = np.random.default_rng(4)
    row, col, val = random_csr(rng, 16, 24, 200)
    want = np.asarray(csr_to_dense(row, col, val, 16, 24))
    got = csr_to_dense(row, col, val, 16, 24, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    monkeypatch.setenv("DCT_CSR_TO_DENSE", "pallas")
    got_env = csr_to_dense(row, col, val, 16, 24)
    np.testing.assert_allclose(np.asarray(got_env), want, rtol=1e-6,
                               atol=1e-6)
    monkeypatch.setenv("DCT_CSR_TO_DENSE", "bogus")
    with pytest.raises(ValueError, match="csr_to_dense impl"):
        csr_to_dense(row, col, val, 16, 24)


def test_linear_dense_margin_path_matches_segment(tmp_path, monkeypatch,
                                                  interpreted_switch):
    # training through margin_path="dense" with the Pallas formatter must
    # follow the same trajectory as the segment-sum path (the kernel only
    # formats batch data — gradients never flow through it)
    from dmlc_core_tpu.models.linear import LinearLearner
    from dmlc_core_tpu.tpu.device_iter import DeviceRowBlockIter

    p = tmp_path / "m.libsvm"
    rng = np.random.default_rng(9)
    with open(p, "w") as f:
        for i in range(512):
            feats = " ".join(f"{j}:{rng.uniform(-1, 1):.4f}"
                             for j in range(6))
            f.write(f"{i % 2} {feats}\n")

    def train(**kw):
        learner = LinearLearner(6, mesh=None, learning_rate=0.5, **kw)
        params = learner.init()
        with DeviceRowBlockIter(str(p), batch_rows=128, mesh=None,
                                layout="csr", min_nnz_bucket=1024) as it:
            for batch in it:
                params, loss = learner.step(params, batch)
        return float(loss), np.asarray(params.w)

    loss_seg, w_seg = train()
    monkeypatch.setenv("DCT_CSR_TO_DENSE", "pallas")
    loss_dense, w_dense = train(margin_path="dense")
    assert np.isfinite(loss_dense)
    np.testing.assert_allclose(loss_dense, loss_seg, rtol=1e-5)
    np.testing.assert_allclose(w_dense, w_seg, rtol=1e-5, atol=1e-7)


def test_interpret_mode_refuses_shard_map():
    # the interpreted body cannot type-check under varying manual axes;
    # it must say so, not hand the shard to another implementation
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    rng = np.random.default_rng(1)
    row, col, val = (jnp.stack([a, a]) for a in random_csr(rng, 8, 16, 64))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def fmt(r, c, v):
        return csr_to_dense_pallas(r[0], c[0], v[0], 8, 16)[None]

    with pytest.raises(ValueError, match="cannot run inside shard_map"):
        fmt(row, col, val)


def test_tpu_mosaic_lowering_exports():
    # the kernel must survive the real TPU lowering pipeline (Mosaic)
    # even on a host with no chip — block-spec/layout bugs surface here
    from jax import export

    def fmt(r, c, v):
        return pallas_kernels.csr_to_dense_pallas(r, c, v, 64, 28)

    i32 = jax.ShapeDtypeStruct((2048,), jnp.int32)
    exp = export.export(jax.jit(fmt), platforms=["tpu"])(
        i32, i32, jax.ShapeDtypeStruct((2048,), jnp.float32))
    assert len(exp.mlir_module_serialized) > 0
