"""Plain reference: AdamW, shared by every family's training check.

Decoupled weight decay on every leaf and bias-corrected moments, one leaf
at a time, the moments kept on the host between steps. It knows leaves by
name and nothing of the architecture.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def adamw_init(leaves):
    """Both moments, kept on the HOST between steps so that the reference
    fits beside nothing but its own weights and gradients."""
    return {"step": 0,
            "m": {k: np.zeros(v.shape, np.float32) for k, v in leaves.items()},
            "v": {k: np.zeros(v.shape, np.float32) for k, v in leaves.items()}}


def adamw_step(leaves, grads, state, opt):
    """Decoupled weight decay on every leaf, bias-corrected moments: the
    textbook AdamW the configuration's ``optimizer`` group parametrises.
    One leaf at a time; ``leaves`` and ``grads`` are used up."""
    t = state["step"] + 1
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (upd + wd * p), m, v

    new = {}
    for k in list(leaves):
        p, m, v = one(leaves.pop(k), grads.pop(k), state["m"][k], state["v"][k])
        new[k] = p
        state["m"][k], state["v"][k] = np.asarray(m), np.asarray(v)
    state["step"] = t
    return new, state
