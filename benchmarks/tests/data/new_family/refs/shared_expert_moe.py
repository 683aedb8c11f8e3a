"""Plain reference: a routed decoder with a dense start and a shared expert.

``refs/decoder.py``'s pieces and drivers and ``refs/olmoe.py``'s routed
block; what is added is which layer is which, and the shared expert: a
SwiGLU MLP every token goes through, added to the routed experts' output.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from . import decoder, olmoe
from .decoder import loss0_expected  # noqa: F401

ROUTED = ("router", "experts_gate_up", "experts_down", "shared_gate_up",
          "shared_down")


def layer_names(model, i):
    p = f"layers.{i}."
    tail = ("gate_up", "down") if i < model["first_k_dense_replace"] else ROUTED
    return [p + t for t in ("attn_norm", "mlp_norm", "qkv", "o") + tail]


def layer(model, w, x, quant=None, route=None):
    """One block, dense or routed by the leaves it is given."""
    eps = model["rms_norm_eps"]
    h = x + decoder.attention(model, w, decoder.rms_norm(x, w["attn_norm"], eps),
                              quant)
    z = decoder.rms_norm(h, w["mlp_norm"], eps)
    if "router" not in w:
        return (h + decoder.dense_mlp(w, z, quant),
                jnp.zeros((), jnp.float32), None)
    y, aux, counts = olmoe.routed_block(model, w, z, quant, decoder.mm, route)
    g, u = jnp.split(decoder.mm(z, w["shared_gate_up"], quant), 2, -1)
    y = y + decoder.mm(jax.nn.silu(g) * u, w["shared_down"], quant)
    return h + y, aux, counts


def logits_at(model, get, blocks, quant=None):
    return decoder.logits_at(model, get, blocks, quant,
                             arch=sys.modules[__name__])


def loss_and_grads(model, leaves, ids, labels, quant=None, rows_per_block=1):
    return decoder.loss_and_grads(model, leaves, ids, labels, quant,
                                  rows_per_block, arch=sys.modules[__name__])
