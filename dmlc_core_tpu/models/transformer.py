"""Sequence-parallel causal transformer LM — the long-context consumer.

The reference library ships no models (SURVEY: "no models, no ops, no
autograd"); its deliverable is the sharded data pipeline. This model is the
framework's demonstration consumer for the *other* sharding axis: context
parallelism. The training step runs under `shard_map` over a 2-D
("data", "seq") mesh —

- batch axis sharded over "data" (the DP contract inherited from
  InputSplit's part/num_parts exact cover),
- sequence axis sharded over "seq", with attention computed by the
  ppermute ring (parallel/ring.py ring_attention) so a sequence of length
  S costs O(S / seq_devices) activation memory per device,
- parameters replicated; gradients psum'd over both axes inside the same
  shard_map, so the update is computed identically everywhere and
  replication is preserved without any cross-step resharding.

Everything is static-shape, scan-free Python loops over layers (unrolled at
trace time), bfloat16-friendly: matmuls hit the MXU, masks/softmax fuse.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu.parallel.ring import ring_attention
from dmlc_core_tpu.parallel.varying import mark_varying

__all__ = ["TransformerConfig", "TransformerLM"]

Params = Dict[str, Any]


class TransformerConfig(NamedTuple):
    vocab: int = 256
    max_seq: int = 128
    embed: int = 64
    heads: int = 4
    layers: int = 2
    mlp_mult: int = 4
    dtype: Any = jnp.float32


def _layer_norm(x, scale, bias, eps=1e-5):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) * lax.rsqrt(v + eps) * scale + bias


class TransformerLM:
    """Causal LM with ring-attention sequence parallelism.

    Usage: build with a 2-D mesh (axes "data", "seq"); `step(params,
    tokens, labels)` consumes [B, S] int32 arrays sharded
    P("data", "seq") and returns (new_params, global mean loss).
    """

    def __init__(self, config: TransformerConfig, mesh: Mesh,
                 learning_rate: float = 0.1):
        self.config = config
        self.mesh = mesh
        self.lr = learning_rate
        axes = mesh.axis_names
        assert "data" in axes and "seq" in axes, (
            f"need ('data', 'seq') mesh axes, got {axes}")
        tok_spec = P("data", "seq")
        rep_spec = P()
        self._step = jax.jit(shard_map(
            self._shard_step, mesh=mesh,
            in_specs=(rep_spec, tok_spec, tok_spec),
            out_specs=(rep_spec, rep_spec)))
        self.token_sharding = NamedSharding(mesh, tok_spec)
        self.param_sharding = NamedSharding(mesh, rep_spec)

    # ------------------------------------------------------------- params --
    def init(self, seed: int = 0) -> Params:
        """Fresh parameter pytree, sharded per the layer partition specs."""
        cfg = self.config
        rng = np.random.default_rng(seed)
        D = cfg.embed

        def dense(m, n, s):
            return jnp.asarray(
                rng.normal(0, s, size=(m, n)).astype(np.float32))

        params: Params = {
            "embed": dense(cfg.vocab, D, 0.02),
            "pos": dense(cfg.max_seq, D, 0.02),
            "ln_f": {"scale": jnp.ones((D,)), "bias": jnp.zeros((D,))},
            "layers": [],
        }
        for _ in range(cfg.layers):
            params["layers"].append({
                "ln1": {"scale": jnp.ones((D,)), "bias": jnp.zeros((D,))},
                "qkv": dense(D, 3 * D, D ** -0.5),
                "proj": dense(D, D, (2 * D) ** -0.5),
                "ln2": {"scale": jnp.ones((D,)), "bias": jnp.zeros((D,))},
                "w1": dense(D, cfg.mlp_mult * D, D ** -0.5),
                "w2": dense(cfg.mlp_mult * D, D, (cfg.mlp_mult * D) ** -0.5),
            })
        return jax.device_put(params, self.param_sharding)

    # ------------------------------------------------------------ forward --
    def _forward_local(self, params: Params, tokens: jnp.ndarray
                       ) -> jnp.ndarray:
        """Per-shard forward: tokens [b, s_loc] -> logits [b, s_loc, V].

        Runs inside shard_map; attention is the 'seq'-axis ring, everything
        else is position-local so it needs no communication.
        """
        cfg = self.config
        H = cfg.heads
        D = cfg.embed
        hd = D // H
        b, s_loc = tokens.shape
        me = lax.axis_index("seq")

        x = jnp.take(params["embed"], tokens, axis=0)
        pos = lax.dynamic_slice_in_dim(params["pos"], me * s_loc, s_loc,
                                       axis=0)
        x = (x + pos[None]).astype(cfg.dtype)

        for layer in params["layers"]:
            h = _layer_norm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
            qkv = (h @ layer["qkv"].astype(cfg.dtype)).reshape(
                b, s_loc, 3, H, hd)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            att = ring_attention(q, k, v, axis_name="seq", causal=True)
            att = att.reshape(b, s_loc, D) @ layer["proj"].astype(cfg.dtype)
            x = x + att
            h = _layer_norm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
            h = jax.nn.gelu(h @ layer["w1"].astype(cfg.dtype))
            x = x + h @ layer["w2"].astype(cfg.dtype)

        x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
        return (x @ params["embed"].T.astype(cfg.dtype)).astype(jnp.float32)

    def _shard_step(self, params: Params, tokens: jnp.ndarray,
                    labels: jnp.ndarray):
        axes = ("data", "seq")
        # replicated params must be typed device-varying inside the shard
        # body: otherwise autodiff's transpose rule inserts an implicit
        # cross-device psum into their cotangents (e.g. through the
        # position-table dynamic_slice) and the explicit psum below
        # double-counts by the axis size
        vparams = mark_varying(params, axes)

        def local_loss(p):
            logits = self._forward_local(p, tokens)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None],
                                       axis=-1)[..., 0]
            return nll.sum(), nll.size

        (loss_sum, count), grads = jax.value_and_grad(
            local_loss, has_aux=True)(vparams)
        # global reductions over BOTH mesh axes: loss for reporting, grads
        # so the replicated update stays identical on every device; the
        # update applies to the original (replicated-typed) params so the
        # outputs satisfy the replicated out_specs
        loss_sum = lax.psum(loss_sum, axes)
        total = lax.psum(jnp.asarray(count, jnp.float32), axes)
        grads = jax.tree.map(lambda g: lax.psum(g, axes), grads)
        new_params = jax.tree.map(lambda p, g: p - self.lr * g / total,
                                  params, grads)
        return new_params, loss_sum / total

    # --------------------------------------------------------------- step --
    def step(self, params: Params, tokens: jnp.ndarray,
             labels: jnp.ndarray):
        """One SGD step on next-token loss; returns (params, mean_loss)."""
        tokens = jax.device_put(tokens, self.token_sharding)
        labels = jax.device_put(labels, self.token_sharding)
        return self._step(params, tokens, labels)
