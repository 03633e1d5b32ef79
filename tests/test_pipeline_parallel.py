"""GPipe-style SPMD pipeline (parallel/pipeline_parallel.py): the
pipelined forward/backward must match running the stage stack
sequentially on one device — scheduling must not change the math."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_core_tpu.parallel.pipeline_parallel import pipeline_apply


def stage_fn(w, x):
    """One homogeneous MLP stage: [mb, D] -> [mb, D]."""
    return jnp.tanh(x @ w["a"]) @ w["b"] + x


def make_params(num_stages, D, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(0, 0.5, (num_stages, D, D)),
                         jnp.float32),
        "b": jnp.asarray(rng.normal(0, 0.5, (num_stages, D, D)),
                         jnp.float32),
    }


def sequential_apply(params, mb):
    out = []
    for m in range(mb.shape[0]):
        x = mb[m]
        for s in range(params["a"].shape[0]):
            x = stage_fn({"a": params["a"][s], "b": params["b"][s]}, x)
        out.append(x)
    return jnp.stack(out)


def pipe_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("pipe",))


@pytest.mark.parametrize("stages,micro", [(4, 4), (8, 3), (2, 6)])
def test_pipeline_matches_sequential(stages, micro):
    D = 16
    mesh = pipe_mesh(stages)
    params = make_params(stages, D)
    mb = jnp.asarray(
        np.random.default_rng(1).normal(0, 1, (micro, 8, D)), jnp.float32)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=({"a": P("pipe"), "b": P("pipe")}, P()),
        out_specs=P())
    def run(params, mb):
        local = {"a": params["a"][0], "b": params["b"][0]}
        return pipeline_apply(stage_fn, local, mb)

    got = run(params, mb)
    want = sequential_apply(params, mb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_composes_with_data_axis():
    """DP x PP on a ('data', 'pipe') mesh: each data-shard's microbatches
    flow through the same stage stack; outputs must match the sequential
    oracle for every data shard."""
    D = 8
    mesh_devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(mesh_devs, ("data", "pipe"))
    params = make_params(4, D, seed=5)
    rng = np.random.default_rng(6)
    # leading batch dim sharded over "data"; microbatch axis next
    mb = jnp.asarray(rng.normal(0, 1, (2, 3, 4, D)), jnp.float32)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=({"a": P("pipe"), "b": P("pipe")}, P("data")),
        out_specs=P("data"))
    def run(params, mb):
        local = {"a": params["a"][0], "b": params["b"][0]}
        return pipeline_apply(stage_fn, local, mb[0],
                              axis_name="pipe")[None]

    got = np.asarray(run(params, mb))
    for d in range(2):
        want = sequential_apply(params, mb[d])
        np.testing.assert_allclose(got[d], np.asarray(want), rtol=1e-5,
                                   atol=1e-5)


def test_pipeline_backward_trains():
    """Autodiff through the schedule: per-stage gradients match the
    sequential program's, and a few SGD steps reduce the loss."""
    stages, micro, D = 4, 4, 8
    mesh = pipe_mesh(stages)
    params = make_params(stages, D, seed=2)
    rng = np.random.default_rng(3)
    mb = jnp.asarray(rng.normal(0, 1, (micro, 8, D)), jnp.float32)
    target = jnp.asarray(rng.normal(0, 1, (micro, 8, D)), jnp.float32)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=({"a": P("pipe"), "b": P("pipe")}, P(), P()),
        out_specs=({"a": P("pipe"), "b": P("pipe")}, P()))
    def grad_step(params, mb, target):
        local = {"a": params["a"][0], "b": params["b"][0]}

        def loss_fn(w):
            out = pipeline_apply(stage_fn, w, mb)
            return jnp.mean((out - target) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(local)
        # each pipe rank owns its stage's slice: restack for out_specs
        g = jax.tree.map(lambda t: t[None], g)
        return g, loss

    def seq_loss(params):
        return jnp.mean((sequential_apply(params, mb) - target) ** 2)

    g_pipe, loss_pipe = grad_step(params, mb, target)
    loss_seq, g_seq = jax.value_and_grad(seq_loss)(params)
    np.testing.assert_allclose(float(loss_pipe), float(loss_seq),
                               rtol=1e-5)
    for k in ("a", "b"):
        np.testing.assert_allclose(np.asarray(g_pipe[k]),
                                   np.asarray(g_seq[k]),
                                   rtol=1e-4, atol=1e-5)

    # a few steps of SGD through the pipeline reduce the loss
    losses = []
    for _ in range(5):
        g, loss = grad_step(params, mb, target)
        params = jax.tree.map(lambda p, gg: p - 0.1 * gg, params, g)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
