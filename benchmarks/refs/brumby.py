"""Plain reference: a decoder of power-retention layers of degree 2, each
followed by a dense gated MLP (``model_type: brumby``, as Brumby-14B-Base
publishes it: Qwen3-14B's shape with the softmax of every attention layer
replaced), given the first ``num_hidden_layers`` layers of it.

Full sequence, float32 under ``jax.default_matmul_precision("highest")`` (the
callers set it), in the QUADRATIC form: every position weighs every earlier
one, no state, no kernel, no cache, no ``phi``. The program runs the same
function as a recurrence over a state of 8,256 x 128 numbers a KV head; the
two share no algebra. ``refs/decoder.py`` gives ``mm`` / ``_round`` (the
control's rounding) and ``rms_norm``; nothing of the program is imported.

A published layer is two residual blocks, each behind its own RMSNorm:

    x <- x + Retention(RMSNorm(x; w, eps))
    x <- x + MLP(RMSNorm(x; w', eps))      MLP(u) = (silu(u W_gate) * (u W_up)) W_down

and after the last layer ``logits = RMSNorm(x; w_f) W_head`` (untied), no
embedding scale, no bias anywhere.

Retention. d = ``head_dim``, Hq = ``num_attention_heads``, Hkv =
``num_key_value_heads``, R = Hq / Hkv; one leaf [q | k | v].
    q = u W_q [T, Hq, d]   k = u W_k [T, Hkv, d]   v = u W_v [T, Hkv, d]
    log g[t, h] = log sigmoid((u W_g)[t, h])          W_g [hidden, Hkv], float32
    q <- RoPE(RMSNorm_d(q; w_qn), t)   k <- RoPE(RMSNorm_d(k; w_kn), t)
         (per-head norm over the d, THEN the rotary embedding: rotate-half,
          ``rope_theta``, all d dims, position t counted from the sequence's
          start)
    G[t, h] = sum_{i <= t} log g[i, h]
    for query head n of group h = n // R, and j <= t:
        s[t, j] = (q[t, n] . k[j, h]) / sqrt(d)
        w[t, j] = exp(G[t, h] - G[j, h]) * s[t, j] ** 2
        y[t, n] = sum_j w[t, j] v[j, h] / (sum_j w[t, j] + 1e-6)
    out = concat_n(y) W_o

computed in blocks of ``QUERY_BLOCK`` queries so that 5,120 positions fit.

Leaves are named by BLOCK, two a layer (the program builds a layer as two
residual blocks): layer ``i``'s mixer is ``layers.<2i>``, its MLP
``layers.<2i + 1>``.

Departures from the published model: none that its config states. What the
config does not state (the degree, the gate, the normaliser: it has no key for
any of them) is listed in the configuration's ``assumed`` group.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .decoder import (_round, loss0_expected, mm, rms_norm,  # noqa: F401
                      rope, rope_tables)

EPS = 1e-6              # added to the sum of a position's weights
QUERY_BLOCK = 512
LEAVES = {
    "p": ("norm", "qkv", "gate", "q_norm", "k_norm", "o"),
    "-": ("norm", "gate_up", "down"),
}


def pattern(model) -> str:
    """One character a BLOCK: every layer's mixer (``p``), then its MLP."""
    return "p-" * model["num_hidden_layers"]


def layer_names(model, i):
    """The leaves of layer ``i``: its mixer's block, then its MLP's."""
    return ([f"layers.{2 * i}.{t}" for t in LEAVES["p"]]
            + [f"layers.{2 * i + 1}.{t}" for t in LEAVES["-"]])


def retention(model, w, u, quant=None):
    """u [n, T, hidden] (normalised) -> [n, T, hidden]; every row starts at
    position 0."""
    n, T, _ = u.shape
    hq, hkv, d = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    eps, high = model["rms_norm_eps"], jax.lax.Precision.HIGHEST
    q, k, v = jnp.split(mm(u, w["qkv"], quant), [hq * d, (hq + hkv) * d], -1)
    cos, sin = rope_tables(d, jnp.arange(T), model["rope_theta"])
    q = rope(rms_norm(q.reshape(n, T, hq, d), w["q_norm"], eps), cos, sin)
    k = rope(rms_norm(k.reshape(n, T, hkv, d), w["k_norm"], eps), cos, sin)
    q = _round(q, quant).reshape(n, T, hkv, hq // hkv, d)
    k, v = _round(k, quant), _round(v.reshape(n, T, hkv, d), quant)
    log_g = jax.nn.log_sigmoid(jnp.matmul(u, w["gate"], precision=high))
    total = jnp.cumsum(log_g, axis=1)                            # [n, T, Hkv]
    out = []
    for a in range(0, T, QUERY_BLOCK):
        b = min(a + QUERY_BLOCK, T)
        s = jnp.einsum("nthrd,njhd->nhrtj", q[:, a:b], k[:, :b],
                       precision=high) / math.sqrt(d)
        seg = (jnp.moveaxis(total[:, a:b], 1, 2)[..., :, None]
               - jnp.moveaxis(total[:, :b], 1, 2)[..., None, :])  # [n, h, t, j]
        causal = jnp.arange(a, b)[:, None] >= jnp.arange(b)[None, :]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        weight = decay[:, :, None] * s * s                       # [n, h, r, t, j]
        y = jnp.einsum("nhrtj,njhd->nthrd", _round(weight, quant), v[:, :b],
                       precision=high)
        out.append(y / (jnp.moveaxis(jnp.sum(weight, -1), 3, 1)[..., None]
                        + EPS))
    y = jnp.concatenate(out, axis=1).reshape(n, T, hq * d)
    return mm(y, w["o"], quant)


def mlp(w, u, quant=None):
    gate, up = jnp.split(mm(u, w["gate_up"], quant), 2, -1)
    return mm(jax.nn.silu(gate) * up, w["down"], quant)


def layer(model, w, x, quant=None):
    """One published layer: ``w`` = (its mixer's leaves, its MLP's), by their
    short names."""
    mixer, ffn = w
    eps = model["rms_norm_eps"]
    x = x + retention(model, mixer, rms_norm(x, mixer["norm"], eps), quant)
    return x + mlp(ffn, rms_norm(x, ffn["norm"], eps), quant)


def _short(model, i, leaves):
    """Layer ``i``'s leaves as ``layer`` takes them."""
    return tuple({name.split(".", 2)[2]: leaves[name]
                  for name in layer_names(model, i)
                  if name.startswith(f"layers.{block}.")}
                 for block in (2 * i, 2 * i + 1))


def logits_at(model, get, blocks, quant=None):
    """As ``decoder.logits_at``: for each block (ids [n, s], rows, cols) the
    logits [len(rows), V] at positions (rows[j], cols[j]), one layer's
    weights alive at a time; the table and the head are arguments of their
    programs, never captured constants."""
    step = jax.jit(lambda w, x: layer(model, w, x, quant))
    take = jax.jit(lambda e, ids: jnp.take(e, ids, axis=0))
    embed = get(["embed"])["embed"]
    xs = [take(embed, ids) for ids, _, _ in blocks]
    del embed
    for i in range(model["num_hidden_layers"]):
        w = _short(model, i, get(layer_names(model, i)))
        xs = [step(w, x) for x in xs]
    tail = get(["final_norm", "head"])
    head = jax.jit(lambda t, x: mm(rms_norm(x, t["final_norm"],
                                            model["rms_norm_eps"]),
                                   t["head"], quant))
    return [head(tail, x[rows, cols]) for x, (_, rows, cols) in zip(xs, blocks)]


def loss_and_grads(model, leaves, ids, labels, quant=None, rows_per_block=1):
    """Mean next-token cross entropy over the whole batch and its gradient,
    accumulated over blocks of rows. No cell trains this family."""
    n, s = ids.shape
    one = jax.checkpoint(lambda w, x: layer(model, w, x, quant))

    def block_loss(lv, x, y):
        h = jnp.take(lv["embed"], x, axis=0)
        for i in range(model["num_hidden_layers"]):
            h = one(_short(model, i, lv), h)
        h = rms_norm(h, lv["final_norm"], model["rms_norm_eps"])
        logp = jax.nn.log_softmax(mm(h, lv["head"], quant), -1)
        return -jnp.sum(jnp.take_along_axis(logp, y[..., None], -1)) / (n * s)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(lv, acc, x, y):
        l, g = jax.value_and_grad(block_loss)(lv, x, y)
        return l, jax.tree.map(jnp.add, acc, g)

    loss, grads = 0.0, jax.tree.map(jnp.zeros_like, leaves)
    for a in range(0, n, rows_per_block):
        l, grads = step(leaves, grads, ids[a:a + rows_per_block],
                        labels[a:a + rows_per_block])
        loss = loss + l
    return loss, grads
