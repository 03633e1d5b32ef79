"""Sequence-parallel transformer tests on the virtual 8-device 2-D mesh.

Checks the DP x SP training step end-to-end: loss decreases on a learnable
pattern, the sequence-parallel forward matches a single-device oracle, and
parameter replication is preserved across steps.
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from dmlc_core_tpu.models.transformer import TransformerConfig, TransformerLM


def mesh2d(data, seq):
    devs = np.array(jax.devices()[: data * seq]).reshape(data, seq)
    return Mesh(devs, ("data", "seq"))


def batch(rng, B, S, vocab):
    toks = rng.integers(0, vocab, size=(B, S + 1), dtype=np.int64)
    return (toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32))


@pytest.mark.parametrize("shape", [(2, 4), (4, 2), (1, 8), (8, 1)])
def test_step_runs_on_mesh_shapes(shape):
    cfg = TransformerConfig(vocab=31, max_seq=16, embed=16, heads=2,
                            layers=1)
    mesh = mesh2d(*shape)
    model = TransformerLM(cfg, mesh, learning_rate=0.05)
    params = model.init()
    rng = np.random.default_rng(0)
    toks, labels = batch(rng, B=8, S=16, vocab=cfg.vocab)
    params, loss = model.step(params, toks, labels)
    assert np.isfinite(float(loss))


def test_loss_decreases_on_copy_task():
    # predict-next on a periodic stream is learnable by a tiny model
    cfg = TransformerConfig(vocab=8, max_seq=16, embed=32, heads=2, layers=1)
    mesh = mesh2d(2, 4)
    model = TransformerLM(cfg, mesh, learning_rate=0.5)
    params = model.init(seed=1)
    period = np.tile(np.arange(8, dtype=np.int32), 5)
    toks = np.stack([period[i:i + 16] for i in range(4)])
    labels = np.stack([period[i + 1:i + 17] for i in range(4)])
    first = None
    for _ in range(30):
        params, loss = model.step(params, toks, labels)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.5, (first, float(loss))


def test_matches_single_device_oracle():
    # the (1, 1) mesh forward must equal the same math on 8 devices
    cfg = TransformerConfig(vocab=17, max_seq=8, embed=16, heads=2, layers=2)
    rng = np.random.default_rng(3)
    toks, labels = batch(rng, B=2, S=8, vocab=cfg.vocab)

    single = TransformerLM(cfg, mesh2d(1, 1), learning_rate=0.1)
    p1 = single.init(seed=7)
    multi = TransformerLM(cfg, mesh2d(2, 4), learning_rate=0.1)
    p8 = multi.init(seed=7)

    p1n, loss1 = single.step(p1, toks, labels)
    p8n, loss8 = multi.step(p8, toks, labels)
    np.testing.assert_allclose(float(loss1), float(loss8), rtol=1e-5)
    a = jax.tree.leaves(p1n)
    b = jax.tree.leaves(p8n)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=5e-4, atol=1e-5)


def test_params_stay_replicated():
    cfg = TransformerConfig(vocab=11, max_seq=8, embed=16, heads=2, layers=1)
    model = TransformerLM(cfg, mesh2d(2, 4), learning_rate=0.1)
    params = model.init()
    rng = np.random.default_rng(5)
    toks, labels = batch(rng, B=2, S=8, vocab=cfg.vocab)
    params, _ = model.step(params, toks, labels)
    emb = params["embed"]
    assert emb.sharding.is_fully_replicated
