"""Plain reference: the routed expert block of OLMoE (arXiv:2409.02060).

A softmax router in float32 over all experts, the top-k experts per token,
each expert a SwiGLU MLP, the outputs weighted by the router's
probabilities. Computed the plainest way there is: every expert runs over
every token and the tokens it was not chosen for get weight 0.

Departures from the published model, both because the PROGRAM departs and a
reference that did not would only measure that (PERF.md, Open questions):

* ``norm_topk_prob``: OLMoE publishes ``false``; ``parallel/moe.py``
  renormalises the top-k weights to sum to 1 whenever k > 1. What runs is
  read from the configuration's ``departures`` group (the published key
  stays as the source has it).
* the load-balance term is GShard's (arXiv:2006.16668, eq. 4) with the
  TOP-1 expert's share, as the program has it, where OLMoE counts all top-k.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _route(model, w, z, quant, mm):
    t = z.reshape(-1, z.shape[-1])
    logits = jnp.matmul(t, w["router"], precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, -1)
    gates, ids = jax.lax.top_k(probs, model["num_experts_per_tok"])
    if model.get("departures", {}).get("norm_topk_prob", model.get("norm_topk_prob")):
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return t, probs, gates, ids


def merge_counts(per_block, tokens):
    """Batch-wide share of top-1 choices per expert, and the token count."""
    ce = sum(c for _, c in per_block) / tokens
    return {"top1_share": ce, "tokens": tokens}


def routed_block(model, w, z, quant, mm, route=None):
    """z [n, s, H] -> (y [n, s, H], this block's part of the load-balance
    term, (router probabilities summed over tokens [E], top-1 counts [E])).
    With ``route`` (merge_counts of the whole batch) the parts of all
    blocks of rows add up to the batch's term exactly, value and gradient;
    without it the rows given are taken for the whole batch."""
    e = model["num_experts"]
    t, probs, gates, ids = _route(model, w, z, quant, mm)
    weight = jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32)
                     * gates[..., None], 1)                       # [t, E]

    def expert(acc, xs):
        w_gu, w_dn, wt = xs
        g, u = jnp.split(mm(t, w_gu, quant), 2, -1)
        return acc + wt[:, None] * mm(jax.nn.silu(g) * u, w_dn, quant), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(t),
                        (w["experts_gate_up"], w["experts_down"], weight.T))
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32)
    if route is None:
        aux = jnp.sum(jnp.mean(probs, 0) * jnp.mean(top1, 0)) * e
    else:
        aux = jnp.sum(jnp.sum(probs, 0) / route["tokens"]
                      * jax.lax.stop_gradient(route["top1_share"])) * e
    return y.reshape(z.shape), aux, (jnp.sum(probs, 0), jnp.sum(top1, 0))
