"""Ring collective and ring-attention tests on the virtual 8-device mesh.

Validates the sequence/context-parallel layer (parallel/ring.py) against
dense single-device oracles (ops/attention.py): ring allreduce == psum,
ring attention == exact softmax attention (full and causal), and the
mesh-level wrapper keeps the sequence sharding.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_core_tpu.ops.attention import blockwise_attention, mha_reference
from dmlc_core_tpu.parallel.ring import (ring_allreduce, ring_attention,
                                         sequence_parallel_attention,
                                         zigzag_permutation)


def mesh1d(n, name):
    return Mesh(np.array(jax.devices()[:n]), (name,))


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("size", [1, 7, 64, 1000])
def test_ring_allreduce_matches_psum(n, size):
    mesh = mesh1d(n, "r")
    rng = np.random.default_rng(size * n)
    x = rng.normal(size=(n, size)).astype(np.float32)

    ring = jax.jit(shard_map(
        functools.partial(ring_allreduce, axis_name="r"), mesh=mesh,
        in_specs=P("r"), out_specs=P("r")))
    # shard_map splits the leading axis: each device sums its row slice
    got = ring(x)
    want = np.broadcast_to(x.sum(axis=0, keepdims=True), x.shape)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_ring_allreduce_nd_payload():
    mesh = mesh1d(8, "r")
    rng = np.random.default_rng(3)
    x = rng.normal(size=(8, 3, 5)).astype(np.float32)
    ring = jax.jit(shard_map(
        functools.partial(ring_allreduce, axis_name="r"), mesh=mesh,
        in_specs=P("r"), out_specs=P("r")))
    got = np.asarray(ring(x))
    want = np.broadcast_to(x.sum(axis=0, keepdims=True), x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("nseq", [2, 4, 8])
def test_ring_attention_matches_dense(causal, nseq):
    B, S, H, D = 2, 32, 2, 8
    rng = np.random.default_rng(nseq + int(causal))
    q = rng.normal(size=(B, S, H, D)).astype(np.float32)
    k = rng.normal(size=(B, S, H, D)).astype(np.float32)
    v = rng.normal(size=(B, S, H, D)).astype(np.float32)

    mesh = mesh1d(nseq, "seq")
    got = sequence_parallel_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), mesh, causal=causal)
    want = mha_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("nseq", [2, 4, 8])
def test_zigzag_ring_attention_matches_dense(nseq):
    """The balanced causal ring (zigzag layout, full-pair-only compute)
    must equal dense causal attention exactly — the liveness proof in
    ring_attention_zigzag's docstring, checked numerically."""
    B, S, H, D = 2, 32, 2, 8
    rng = np.random.default_rng(40 + nseq)
    q = rng.normal(size=(B, S, H, D)).astype(np.float32)
    k = rng.normal(size=(B, S, H, D)).astype(np.float32)
    v = rng.normal(size=(B, S, H, D)).astype(np.float32)
    mesh = mesh1d(nseq, "seq")
    got = sequence_parallel_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), mesh, causal=True,
                                      layout="zigzag")
    want = mha_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_zigzag_permutation_roundtrip():
    perm = np.asarray(zigzag_permutation(32, 4))
    assert sorted(perm.tolist()) == list(range(32))
    # device 0 holds chunks 0 and 7, device 1 chunks 1 and 6, ...
    assert perm[:8].tolist() == [0, 1, 2, 3, 28, 29, 30, 31]
    inv = np.argsort(perm)
    x = np.arange(32)
    assert (x[perm][inv] == x).all()


def test_zigzag_rejects_non_causal():
    mesh = mesh1d(2, "seq")
    x = jnp.zeros((1, 8, 1, 4), jnp.float32)
    with pytest.raises(ValueError, match="CAUSAL"):
        sequence_parallel_attention(x, x, x, mesh, causal=False,
                                    layout="zigzag")


def test_ring_attention_output_stays_sequence_sharded():
    B, S, H, D = 1, 16, 1, 4
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
               for _ in range(3))
    mesh = mesh1d(8, "seq")
    out = sequence_parallel_attention(q, k, v, mesh)
    # compare normalized: older jax drops trailing Nones from the spec
    spec = tuple(out.sharding.spec)
    assert spec[:2] == (None, "seq") and all(s is None for s in spec[2:])


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention_matches_dense(causal):
    B, L, S, H, D = 2, 24, 70, 2, 8  # non-divisible by block_size
    rng = np.random.default_rng(7 + int(causal))
    q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    if causal:
        # causal only makes sense for L == S
        q = q[:, :24]
        k2, v2 = k[:, :24], v[:, :24]
        got = blockwise_attention(q, k2, v2, block_size=16, causal=True)
        want = mha_reference(q, k2, v2, causal=True)
    else:
        got = blockwise_attention(q, k, v, block_size=16)
        want = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_long_sequence_jits_once():
    # the scan-over-ring form must compile with static shapes
    B, S, H, D = 1, 64, 2, 8
    mesh = mesh1d(8, "seq")
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
               for _ in range(3))
    spec = P(None, "seq", None, None)
    fn = jax.jit(shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    out = fn(q, k, v)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
