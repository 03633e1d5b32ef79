"""Plain reference of the factorisation-machine cells: second-order FM by its
definition, logistic loss, plain SGD, in ``jax.numpy`` and float32.

    y(x) = b + sum_i w_i x_i + sum_{i<j} <v_i, v_j> x_i x_j      (Rendle 2010)

It imports nothing of the program and takes nothing the program made: the
rows come from the generator's memory, the initial factors from the stated
rule (``init_scale * normal(PRNGKey(seed), [F, K])``, zero ``w`` and ``b``).
The pairwise term is summed pair by pair, each feature of a row against the
features before it, not by the identity the program uses.

With ``l2 = 0`` plain SGD moves only the rows of ``w`` and ``v`` a batch
touches, so the reference holds the touched rows alone (``compact``): the
norm of a full leaf's gradient or change equals the norm over its touched
rows. ``dtype`` other than float32 makes the *control*: the same mathematics
in the next precision down.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Params(NamedTuple):
    b: jnp.ndarray   # []
    w: jnp.ndarray   # [U]
    v: jnp.ndarray   # [U, K]


class Batch(NamedTuple):
    label: jnp.ndarray  # [R]
    col: jnp.ndarray    # [R, L] compact ids; padding points at row 0
    val: jnp.ndarray    # [R, L]; 0 on padding


def initial_factors(seed: int, num_features: int, rank: int,
                    init_scale: float, rows: np.ndarray) -> jnp.ndarray:
    """Rows ``rows`` of the stated initial ``v``. The whole table is made
    once on the device and dropped: a row depends on the table's shape."""
    return _initial_rows(seed, jnp.asarray(rows), num_features, rank,
                         init_scale)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _initial_rows(seed, rows, num_features, rank, init_scale):
    full = init_scale * jax.random.normal(
        jax.random.PRNGKey(seed), (num_features, rank), jnp.float32)
    return full[rows]


def pad_rows(lens: np.ndarray, col: np.ndarray, val: np.ndarray,
             width: int) -> Tuple[np.ndarray, np.ndarray]:
    """CSR tokens as [R, width] matrices with 0-valued padding."""
    rows = lens.size
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    pos = np.arange(col.size) - np.repeat(starts, lens)
    r = np.repeat(np.arange(rows), lens)
    c = np.zeros((rows, width), np.int32)
    x = np.zeros((rows, width), np.float32)
    c[r, pos] = col
    x[r, pos] = val
    return c, x


def margin(p: Params, batch: Batch) -> jnp.ndarray:
    x = batch.val.astype(p.v.dtype)
    linear = jnp.sum(p.w[batch.col] * x, axis=1)
    vx = p.v[batch.col] * x[..., None]                       # [R, L, K]
    before = jnp.cumsum(vx, axis=1) - vx      # sum over j < i of v_j x_j
    pairs = jnp.sum(vx * before, axis=(1, 2))  # sum over j < i of <.,.>
    return p.b + linear + pairs


def loss(p: Params, batch: Batch) -> jnp.ndarray:
    m = margin(p, batch)
    y = batch.label.astype(m.dtype)
    per_row = jnp.maximum(m, 0) - m * y + jnp.log1p(jnp.exp(-jnp.abs(m)))
    return jnp.mean(per_row)


def sgd_steps(p0: Params, batches: Batch, learning_rate: float) -> Dict:
    """Plain SGD over the stacked ``batches`` ([S, R, ...] leaves), traced as
    one program so that a run pays one compilation. Returns each step's
    loss, the first step's gradient and the states after the first and the
    last step."""
    steps = batches.label.shape[0]
    lr = jnp.asarray(learning_rate, p0.v.dtype)
    p = p0
    losses, first_grad, after_first = [], None, None
    for i in range(steps):
        batch = Batch(*(leaf[i] for leaf in batches))
        value, grad = jax.value_and_grad(loss)(p, batch)
        losses.append(value)
        p = Params(*(a - lr * g for a, g in zip(p, grad)))
        if i == 0:
            first_grad, after_first = grad, p
    return {"losses": jnp.stack(losses).astype(jnp.float32),
            "first_grad": first_grad, "after_first": after_first, "final": p}


def leaf_norms(tree) -> List[jnp.ndarray]:
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in tree]


def diff(a: Params, b: Params) -> List[jnp.ndarray]:
    return [x.astype(jnp.float32) - y.astype(jnp.float32)
            for x, y in zip(a, b)]


def cast(p: Params, dtype) -> Params:
    return Params(*(a.astype(dtype) for a in p))


@functools.partial(jax.jit, static_argnames=("learning_rate", "dtype"))
def readings(v0: jnp.ndarray, batches: Batch, learning_rate: float,
             dtype: str = "float32") -> Dict:
    """Losses, per-leaf norms of the first gradient and of the change after
    the last step. In float32 the gradient is the one ``value_and_grad``
    gives. A lower ``dtype`` makes the control, which stands in the program's
    place: its gradient is worked out from its state after one step,
    ``(p0 - p1) / learning_rate``, as the program's is."""
    p0 = Params(jnp.zeros((), jnp.float32),
                jnp.zeros((v0.shape[0],), jnp.float32), v0)
    p0 = cast(p0, jnp.dtype(dtype))
    out = sgd_steps(p0, batches, learning_rate)
    if dtype == "float32":
        grad = leaf_norms(out["first_grad"])
    else:
        grad = [n / learning_rate
                for n in leaf_norms(diff(p0, out["after_first"]))]
    return {"losses": out["losses"], "grad_norms": jnp.stack(grad),
            "change_norms": jnp.stack(leaf_norms(diff(out["final"], p0)))}
