"""Plain optimizers for the reference side of a training cell, and how
the first gradient is read back from an optimizer's state.  Written from
the papers (Kingma & Ba 2015, Algorithm 1; Sutskever et al. 2013 as
Goyal et al. 2017 eq. 9 state it); imports nothing of the program."""

import jax
import jax.numpy as jnp


def init(hp, params):
    """The optimizer's moments at nought."""
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    if hp["name"] == "adam":
        return {"m": zeros(), "v": zeros()}
    if hp["name"] == "sgd":
        return {"velocity": zeros()}
    raise ValueError(hp["name"])


def update(hp, grads, state, params, t):
    """Step ``t`` (counted from 1); returns (new params, new moments)."""
    lr = hp["learning_rate"]
    if hp["name"] == "adam":
        b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g,
                         state["v"], grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        new = jax.tree.map(
            lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
            params, m, v)
        return new, {"m": m, "v": v}
    mu, wd = hp["momentum"], hp.get("weight_decay", 0.0)
    grads = jax.tree.map(lambda g, p: g + wd * p, grads, params)
    vel = jax.tree.map(lambda u, g: mu * u + g, state["velocity"], grads)
    new = jax.tree.map(lambda p, u: p - lr * u, params, vel)
    return new, {"velocity": vel}


def first_gradient(hp, moment):
    """The gradient the optimizer was given at its FIRST step, from its
    first-moment state after that step (``m`` for Adam, the velocity for
    momentum SGD, which then still holds weight decay's term: the
    reference's is read the same way, so both sides carry it)."""
    if hp["name"] == "adam":
        return jax.tree.map(lambda m: m / (1 - hp["beta1"]), moment)
    return moment
