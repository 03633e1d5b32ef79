"""Plain reference of the factorisation-machine cells trained with the click
logs' trainers' rule: the FM and its loss as ``reference/fm.py`` writes them,
then FTRL-proximal on ``b`` and ``w`` (McMahan et al., "Ad Click Prediction:
a View from the Trenches", KDD 2013, Algorithm 1; ``b`` with
``l1 = l2 = 0``, a feature every row has) and AdaGrad on ``v`` (Li, Wang,
Liu, Smola, "DiFacto", WSDM 2016), in ``jax.numpy`` and float32, written as
the equations, per coordinate, on the batch-mean gradient ``g``:

    sigma = (sqrt(n + g^2) - sqrt(n)) / alpha
    z <- z + g - sigma * w
    n <- n + g^2
    w <- 0 if |z| <= l1 else -(z - sign(z) l1) / ((beta + sqrt(n)) / alpha + l2)
    G <- G + g_v^2
    v <- v - v_lr * g_v / (v_beta + sqrt(G))

It imports nothing of the program and takes nothing the program made. A row
whose gradient is 0 does not move under these equations (sigma is 0, and
``w`` is the closed form of the same ``z`` and ``n``), so the reference holds
the rows the check's batches touch alone (``compact``), as ``reference/fm.py``
does; a slot starts at 0, so its change is its own norm. ``dtype`` other than
float32 makes the *control*: the same mathematics in the next precision down,
slots included.

The readings are the losses and, for each of the eight leaves (``b``, ``w``,
``v``, then the slots ``zb``, ``nb``, ``z``, ``n``, ``g``: the equations'
``G``), the norm of its change after the first step and after the last: the
first change takes the place of the first gradient's norm, since FTRL's
change is not ``lr * g``.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from reference import fm
from reference.fm import Batch, initial_factors, pad_rows  # noqa: F401

LEAVES = ("b", "w", "v", "zb", "nb", "z", "n", "g")


class State(NamedTuple):
    b: jnp.ndarray    # []
    w: jnp.ndarray    # [U]
    v: jnp.ndarray    # [U, K]
    zb: jnp.ndarray   # []
    nb: jnp.ndarray   # []
    z: jnp.ndarray    # [U]
    n: jnp.ndarray    # [U]
    g: jnp.ndarray    # [U, K]: AdaGrad's G


class Rule(NamedTuple):
    alpha: float
    beta: float
    l1: float
    l2: float
    v_lr: float
    v_beta: float


def ftrl(w, z, n, g, alpha, beta, l1, l2):
    """One FTRL-proximal step per coordinate, as the equations read."""
    sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / alpha
    z = z + g - sigma * w
    n = n + g * g
    shrunk = -(z - jnp.sign(z) * l1) / ((beta + jnp.sqrt(n)) / alpha + l2)
    w = jnp.where(jnp.abs(z) <= l1, jnp.zeros_like(z), shrunk)
    return w, z, n


def adagrad(v, big_g, g, lr, beta):
    big_g = big_g + g * g
    return v - lr * g / (beta + jnp.sqrt(big_g)), big_g


def update(s: State, grad: fm.Params, rule: Rule) -> State:
    dt = s.w.dtype
    c = {k: jnp.asarray(x, dt) for k, x in rule._asdict().items()}
    zero = jnp.asarray(0, dt)
    b, zb, nb = ftrl(s.b, s.zb, s.nb, grad.b, c["alpha"], c["beta"], zero,
                     zero)
    w, z, n = ftrl(s.w, s.z, s.n, grad.w, c["alpha"], c["beta"], c["l1"],
                   c["l2"])
    v, big_g = adagrad(s.v, s.g, grad.v, c["v_lr"], c["v_beta"])
    return State(b, w, v, zb, nb, z, n, big_g)


def rule_steps(s0: State, batches: Batch, rule: Rule) -> Dict:
    """The rule over the stacked ``batches`` ([S, R, ...] leaves), traced as
    one program. ``fm.loss`` is a mean over the rows, so its gradient is the
    batch-mean gradient."""
    s = s0
    losses, after_first = [], None
    for i in range(batches.label.shape[0]):
        batch = Batch(*(leaf[i] for leaf in batches))
        value, grad = jax.value_and_grad(fm.loss)(
            fm.Params(s.b, s.w, s.v), batch)
        losses.append(value)
        s = update(s, grad, rule)
        if i == 0:
            after_first = s
    return {"losses": jnp.stack(losses).astype(jnp.float32),
            "after_first": after_first, "final": s}


def initial_state(v0: jnp.ndarray, dtype) -> State:
    u = v0.shape[0]
    zero, rows = jnp.zeros((), dtype), jnp.zeros((u,), dtype)
    return State(zero, rows, v0.astype(dtype), zero, zero, rows, rows,
                 jnp.zeros(v0.shape, dtype))


@functools.partial(jax.jit, static_argnames=("rule", "dtype"))
def readings(v0: jnp.ndarray, batches: Batch, rule: Tuple,
             dtype: str = "float32") -> Dict:
    """Losses and per-leaf norms of the change after the first and after the
    last step (``LEAVES``' order), from zero slots, ``b = w = 0`` and the
    stated ``v``."""
    with jax.default_matmul_precision("highest"):
        s0 = initial_state(v0, jnp.dtype(dtype))
        out = rule_steps(s0, batches, Rule(*rule))
        return {"losses": out["losses"],
                "first_change_norms": jnp.stack(fm.leaf_norms(
                    fm.diff(out["after_first"], s0))),
                "change_norms": jnp.stack(fm.leaf_norms(
                    fm.diff(out["final"], s0)))}
