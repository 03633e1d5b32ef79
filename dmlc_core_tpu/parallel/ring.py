"""Ring collectives and ring attention over a mesh axis.

The reference's tracker *computes* a ring topology and brokers the TCP
links for Rabit's ring allreduce (reference tracker.py:193-252
find_share_ring/get_ring + assign_rank handing each worker its ring
prev/next). On TPU the ring is the hardware: ICI neighbors under a
`jax.sharding.Mesh` axis. This module provides the two ring algorithms that
make long-context and multi-chip training first-class:

- :func:`ring_allreduce` — the classic reduce-scatter + all-gather ring
  (what Rabit runs over the tracker's ring_map), written with
  `lax.ppermute` so each step moves one chunk to the ring neighbor. It is
  numerically equivalent to `lax.psum`; `psum` is what production code
  should call (XLA already routes it over ICI rings) — this explicit form
  exists for Rabit-semantics parity and as the shard_map collective
  template.
- :func:`ring_attention` — blockwise attention over a sequence-sharded
  axis (sequence/context parallelism): K/V blocks rotate around the ring
  while each device keeps a flash-style online-softmax accumulator for its
  local queries, so attention over a sequence of length P*L needs only
  O(L) memory per device. No counterpart exists in the reference (SURVEY
  §5: sequence parallelism ABSENT) — this is the TPU-native capability the
  framework adds for long-context workloads.

All functions here are *per-shard* code meant to run inside
`jax.shard_map` over the relevant mesh axis; `sequence_parallel_attention`
is the mesh-level wrapper.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_allreduce", "ring_attention", "ring_attention_zigzag",
           "sequence_parallel_attention", "zigzag_permutation"]

_NEG_INF = -1e30


def _axis_size(axis_name: str) -> int:
    return lax.psum(1, axis_name)


def ring_allreduce(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Sum `x` across `axis_name` with an explicit 2(P-1)-step ring.

    Per-shard function (call inside shard_map). Equivalent to
    `lax.psum(x, axis_name)`; see module docstring for why both exist.
    """
    p = _axis_size(axis_name)
    if p == 1:
        return x
    me = lax.axis_index(axis_name)
    shape = x.shape
    flat = x.reshape(-1)
    # pad to P equal chunks
    chunk = -(-flat.size // p)
    flat = jnp.pad(flat, (0, chunk * p - flat.size))
    chunks = flat.reshape(p, chunk)
    fwd = [(i, (i + 1) % p) for i in range(p)]

    # reduce-scatter: after P-1 steps, device d owns the full sum of chunk
    # (d+1) mod P. Each step: send the chunk we just accumulated, add the
    # incoming one.
    def rs_step(s, chunks):
        # send chunk index (me - s) mod p, receive (me - s - 1) mod p
        send_idx = jnp.mod(me - s, p)
        buf = lax.dynamic_index_in_dim(chunks, send_idx, axis=0,
                                       keepdims=False)
        got = lax.ppermute(buf, axis_name, fwd)
        recv_idx = jnp.mod(me - s - 1, p)
        recv = lax.dynamic_index_in_dim(chunks, recv_idx, axis=0,
                                        keepdims=False)
        return lax.dynamic_update_index_in_dim(chunks, recv + got, recv_idx,
                                               axis=0)

    chunks = lax.fori_loop(0, p - 1, rs_step, chunks)

    # all-gather: rotate the completed chunks around the ring
    def ag_step(s, chunks):
        send_idx = jnp.mod(me + 1 - s, p)
        buf = lax.dynamic_index_in_dim(chunks, send_idx, axis=0,
                                       keepdims=False)
        got = lax.ppermute(buf, axis_name, fwd)
        recv_idx = jnp.mod(me - s, p)
        return lax.dynamic_update_index_in_dim(chunks, got, recv_idx, axis=0)

    chunks = lax.fori_loop(0, p - 1, ag_step, chunks)
    return chunks.reshape(-1)[: x.size].reshape(shape)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = False,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Blockwise ring attention for sequence-sharded q/k/v.

    Per-shard function (call inside shard_map over `axis_name`). Shapes are
    local: q [B, L, H, D], k/v [B, L, H, D] — the global sequence is P*L
    with this device holding block `axis_index`. K/V blocks travel the ring
    (P ppermute steps) while a running (max, denominator, numerator)
    accumulator applies the online-softmax rescaling, so the full [L, P*L]
    score matrix never materializes.

    causal=True masks by *global* positions: query i attends key j iff
    global_i >= global_j, reproducing dense causal attention exactly.
    """
    p = _axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, L, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    qf = q.astype(jnp.float32) * scale
    fwd = [(i, (i + 1) % p) for i in range(p)]
    q_pos = me * L + jnp.arange(L)  # global query positions

    # derive the accumulator initializers from q so they carry the same
    # device-varying axes as the data — scan requires the carry's varying
    # set to be invariant, and q is varying over every enclosing shard_map
    # axis (not just `axis_name` when nested in a larger mesh)
    zero = qf[..., 0] * 0.0                      # [B, L, H] float32
    m0 = zero + _NEG_INF
    s0 = zero
    o0 = qf * 0.0

    def step(carry, _):
        m, s, o, k_blk, v_blk, src = carry
        scores = jnp.einsum("blhd,bmhd->blhm", qf,
                            k_blk.astype(jnp.float32))
        if causal:
            k_pos = src * L + jnp.arange(L)
            mask = q_pos[:, None] >= k_pos[None, :]  # [L, M]
            scores = jnp.where(mask[None, :, None, :], scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # guard fully-masked rows: exp(-inf - -inf) -> use stable shift
        shift = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        pij = jnp.exp(scores - shift[..., None])
        if causal:
            pij = jnp.where(mask[None, :, None, :], pij, 0.0)
        alpha = jnp.exp(jnp.where(m <= _NEG_INF, _NEG_INF, m - shift))
        s = s * alpha + pij.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "blhm,bmhd->blhd", pij, v_blk.astype(jnp.float32))
        # rotate k/v to the next device; we now hold block (src - 1) mod p
        k_blk = lax.ppermute(k_blk, axis_name, fwd)
        v_blk = lax.ppermute(v_blk, axis_name, fwd)
        src = jnp.mod(src - 1, p)
        return (m_new, s, o, k_blk, v_blk, src), None

    (m, s, o, _, _, _), _ = lax.scan(step, (m0, s0, o0, k, v, me),
                                     None, length=p)
    out = o / jnp.maximum(s, 1e-30)[..., None]
    return out.astype(q.dtype)


def _online_update(m, s, o, qf, k_blk, v_blk, mask=None):
    """One online-softmax accumulation of (qf · k_blk) v_blk into (m, s, o).

    qf [B, Lc, H, D] (pre-scaled), k/v [B, Mc, H, D], mask [Lc, Mc] or
    None (None = every score live — the zigzag fast path's full pairs)."""
    scores = jnp.einsum("blhd,bmhd->blhm", qf, k_blk.astype(jnp.float32))
    if mask is not None:
        scores = jnp.where(mask[None, :, None, :], scores, _NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    shift = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
    pij = jnp.exp(scores - shift[..., None])
    if mask is not None:
        pij = jnp.where(mask[None, :, None, :], pij, 0.0)
    alpha = jnp.exp(jnp.where(m <= _NEG_INF, _NEG_INF, m - shift))
    s = s * alpha + pij.sum(axis=-1)
    o = o * alpha[..., None] + jnp.einsum(
        "blhm,bmhd->blhd", pij, v_blk.astype(jnp.float32))
    return m_new, s, o


def zigzag_permutation(seq_len: int, num_devices: int) -> "jnp.ndarray":
    """Global-index permutation for the zigzag sequence layout.

    The sequence splits into 2P chunks C0..C2P-1; device d holds
    [C_d, C_{2P-1-d}] — pairing an early chunk with a late one so causal
    masking gives every device the SAME amount of live attention work
    per ring step (the plain contiguous layout leaves early devices idle
    while late ones compute, and the per-step ppermute barrier makes the
    slowest device the step's wall clock). perm[i] = the global position
    stored at packed slot i; apply with `x[..., perm, :]` on the sequence
    axis before sharding, and invert with argsort for outputs/labels.
    """
    p = num_devices
    if seq_len % (2 * p):
        raise ValueError(f"seq_len {seq_len} must divide by 2*P={2 * p}")
    lc = seq_len // (2 * p)
    chunks = []
    for d in range(p):
        chunks.append(jnp.arange(d * lc, (d + 1) * lc))
        hi = 2 * p - 1 - d
        chunks.append(jnp.arange(hi * lc, (hi + 1) * lc))
    return jnp.concatenate(chunks)


def ring_attention_zigzag(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          axis_name: str,
                          scale: Optional[float] = None) -> jnp.ndarray:
    """Causal ring attention over the ZIGZAG layout — the load-balanced
    form that skips the dead half of the causal mask.

    Per-shard function (inside shard_map over `axis_name`); inputs are
    local zigzag shards (zigzag_permutation applied globally BEFORE
    sharding): q/k/v [B, L, H, D] with L = 2*Lc, local rows = global
    chunks (d, 2P-1-d). Exactly equal to dense causal attention on the
    permuted sequence (tests pin it against mha_reference).

    Why it is faster than :func:`ring_attention` for causal work: chunk
    pairing makes every (device, step) compute exactly two FULL
    Lc x Lc chunk pairs with NO masking (their liveness is provable from
    the chunk ids: at step s>0 holding blocks from src, the live pairs
    are [(q_lo, k_lo), (q_hi, k_lo)] when src < me and
    [(q_hi, k_lo), (q_hi, k_hi)] when src > me — the other two pairs of
    the 2x2 chunk square are entirely in the masked future and are never
    computed). Total matmul work is 3 + 2(P-1) chunk pairs vs the plain
    ring's 4P half-masked ones: ~2x fewer causal-attention FLOPs at
    large P, and identical work per device per step, so the per-step
    ppermute barrier never waits on an unlucky device.
    """
    p = _axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, L, H, D = q.shape
    if L % 2:
        raise ValueError(f"zigzag local length {L} must be even")
    lc = L // 2
    if scale is None:
        scale = D ** -0.5
    qf = q.astype(jnp.float32) * scale
    q_lo, q_hi = qf[:, :lc], qf[:, lc:]
    fwd = [(i, (i + 1) % p) for i in range(p)]

    # accumulators per local q chunk, initializers derived from q so they
    # carry the enclosing shard_map axes' varying set (same rationale as
    # ring_attention)
    zero = qf[..., 0] * 0.0                        # [B, L, H]
    m = zero + _NEG_INF
    s = zero
    o = qf * 0.0

    def split(a):
        return a[:, :lc], a[:, lc:]

    def join2(lo, hi):
        return jnp.concatenate([lo, hi], axis=1)

    # prologue (the diagonal, src == me): two causal in-chunk pairs plus
    # the always-live (q_hi, k_lo) cross pair
    tri = jnp.arange(lc)[:, None] >= jnp.arange(lc)[None, :]
    m_lo, m_hi = split(m)
    s_lo, s_hi = split(s)
    o_lo, o_hi = split(o)
    k_lo0, k_hi0 = split(k)
    v_lo0, v_hi0 = split(v)
    m_lo, s_lo, o_lo = _online_update(m_lo, s_lo, o_lo, q_lo, k_lo0, v_lo0,
                                      mask=tri)
    m_hi, s_hi, o_hi = _online_update(m_hi, s_hi, o_hi, q_hi, k_hi0, v_hi0,
                                      mask=tri)
    m_hi, s_hi, o_hi = _online_update(m_hi, s_hi, o_hi, q_hi, k_lo0, v_lo0)

    def step(carry, _):
        m_lo, s_lo, o_lo, m_hi, s_hi, o_hi, k_blk, v_blk, src = carry
        k_blk = lax.ppermute(k_blk, axis_name, fwd)
        v_blk = lax.ppermute(v_blk, axis_name, fwd)
        src = jnp.mod(src - 1, p)
        k_l, k_h = split(k_blk)
        v_l, v_h = split(v_blk)
        is_lt = src < me
        # pair 0: (q_lo if src < me else q_hi) x k_lo — always fully live
        q0 = jnp.where(is_lt, 0.0, 1.0)  # selector as data, no cond
        q0f = q_lo * (1.0 - q0) + q_hi * q0
        m0 = m_lo * (1.0 - q0) + m_hi * q0
        s0 = s_lo * (1.0 - q0) + s_hi * q0
        o0 = o_lo * (1.0 - q0) + o_hi * q0
        m0, s0, o0 = _online_update(m0, s0, o0, q0f, k_l, v_l)
        # write back to whichever chunk pair 0 belongs to
        m_lo = jnp.where(is_lt, m0, m_lo)
        s_lo = jnp.where(is_lt, s0, s_lo)
        o_lo = jnp.where(is_lt, o0, o_lo)
        m_hi = jnp.where(is_lt, m_hi, m0)
        s_hi = jnp.where(is_lt, s_hi, s0)
        o_hi = jnp.where(is_lt, o_hi, o0)
        # pair 1: q_hi x (k_lo if src < me else k_hi) — always fully live
        k1 = jnp.where(is_lt, 0.0, 1.0)
        k1f = k_l * (1.0 - k1) + k_h * k1
        v1f = v_l * (1.0 - k1) + v_h * k1
        m_hi, s_hi, o_hi = _online_update(m_hi, s_hi, o_hi, q_hi, k1f, v1f)
        return (m_lo, s_lo, o_lo, m_hi, s_hi, o_hi, k_blk, v_blk, src), None

    carry = (m_lo, s_lo, o_lo, m_hi, s_hi, o_hi, k, v, me)
    (m_lo, s_lo, o_lo, m_hi, s_hi, o_hi, _, _, _), _ = lax.scan(
        step, carry, None, length=p - 1)
    m = join2(m_lo, m_hi)
    s = join2(s_lo, s_hi)
    o = join2(o_lo, o_hi)
    out = o / jnp.maximum(s, 1e-30)[..., None]
    return out.astype(q.dtype)


def sequence_parallel_attention(q: jnp.ndarray, k: jnp.ndarray,
                                v: jnp.ndarray, mesh: Mesh,
                                axis_name: str = "seq",
                                causal: bool = False,
                                layout: str = "contiguous") -> jnp.ndarray:
    """Mesh-level ring attention: shard the sequence axis, run the ring.

    q/k/v are *global* arrays [B, S, H, D] with S divisible by the mesh
    axis size; returns the attention output with the same sharding.

    layout="zigzag" (causal only) permutes the sequence into the
    balanced zigzag layout, runs :func:`ring_attention_zigzag` (~2x
    fewer causal FLOPs), and un-permutes the output — a drop-in for
    one-shot calls. Models that call attention per layer should instead
    keep activations in zigzag layout end to end (permute tokens once,
    use global position ids) and call ring_attention_zigzag directly;
    this wrapper's per-call permute is the convenience form.
    """
    p = mesh.shape[axis_name]
    spec = P(None, axis_name, None, None)
    sharding = NamedSharding(mesh, spec)
    if layout == "zigzag":
        if not causal:
            raise ValueError("layout='zigzag' balances the CAUSAL mask; "
                             "use the plain ring for bidirectional")
        perm = zigzag_permutation(q.shape[1], p)
        inv = jnp.argsort(perm)
        q, k, v = (jnp.take(t, perm, axis=1) for t in (q, k, v))
        fn = functools.partial(ring_attention_zigzag, axis_name=axis_name)
    elif layout == "contiguous":
        fn = functools.partial(ring_attention, axis_name=axis_name,
                               causal=causal)
    else:
        raise ValueError(f"unknown layout {layout!r}")
    mapped = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    q, k, v = (jax.device_put(t, sharding) for t in (q, k, v))
    out = mapped(q, k, v)
    if layout == "zigzag":
        out = jnp.take(out, inv, axis=1)
    return out
