"""Plain reference: a hybrid decoder of Mamba-1 and attention layers, each
followed by a dense gated MLP (``model_type: jamba`` with ``num_experts: 1``,
as AI21-Jamba2-3B publishes it), whole.

Full sequence, float32 under ``jax.default_matmul_precision("highest")`` (the
callers set it): no cache, no kernel, and the recurrence TOKEN BY TOKEN in a
``lax.scan`` over time on a state [channels, state size], the published
layout, not the program's. ``refs/decoder.py`` gives ``mm`` / ``_round`` (the
control's rounding) and ``rms_norm``, ``refs/nemotron_h.py`` the attention
without a position embedding; nothing of the program is imported.

Layer ``i`` of ``num_hidden_layers`` is an attention layer where ``i %
attn_layer_period == attn_layer_offset`` and a Mamba layer otherwise
(``kinds``); ``num_experts: 1`` makes every feed-forward the dense MLP:

    x <- x + Mixer_i(RMSNorm(x; w, eps))
    x <- x + MLP(RMSNorm(x; w', eps))      MLP(u) = (silu(u W_gate) * (u W_up)) W_down

and after the last layer ``logits = RMSNorm(x; w_f) E^T``, ``E`` the embedding
(``tie_word_embeddings``), no embedding scale.

Mamba. I = ``mamba_expand`` x hidden, N = ``mamba_d_state``, R =
``mamba_dt_rank``, K = ``mamba_d_conv``.
    [x | z] = u W_in                         widths I | I, no bias
    x <- silu(conv(x) + b_conv)   depthwise causal over time, K taps (the last
                                  tap is the token itself), zeros before the
                                  sequence
    [dt | B | C] = x W_x                     widths R | N | N, no bias
    dt <- RMSNorm(dt; w_dt)  B <- RMSNorm(B; w_b)  C <- RMSNorm(C; w_c)
    Delta = softplus(dt W_dt + b_dt) [I]     A = -exp(A_log) [I, N]
    h_t[c, n] = exp(Delta_t[c] A[c, n]) h_{t-1}[c, n] + Delta_t[c] B_t[n] x_t[c]
    y_t[c] = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]
    out = (y * silu(z)) W_out

Attention. q = u W_q (``num_attention_heads`` of ``head_dim``), k = u W_k, v
= u W_v (``num_key_value_heads`` of it; one leaf [q | k | v]), causal softmax
at 1 / sqrt(head_dim), W_o; NO position embedding of any kind.

Leaves are named by BLOCK, two a layer (the program builds a layer as two
residual blocks): layer ``i``'s mixer is ``layers.<2i>``, its MLP
``layers.<2i + 1>``. ``A_log`` is the leaf [N, I]: the published tensor's
transpose, which with seeded values is a name only.

Departures from the published model: none. What its config does not state is
listed in the configuration's ``assumed`` group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .decoder import _round, loss0_expected, mm, rms_norm  # noqa: F401
from .nemotron_h import attention

LEAVES = {
    "m": ("norm", "in_proj", "conv", "conv_bias", "x_proj", "dt_norm",
          "b_norm", "c_norm", "dt_proj", "dt_bias", "A_log", "D", "out_proj"),
    "*": ("norm", "qkv", "o"),
    "-": ("norm", "gate_up", "down"),
}


def kinds(model) -> str:
    """One character a LAYER: ``*`` attention, ``m`` Mamba."""
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return "".join("*" if i % period == offset else "m"
                   for i in range(model["num_hidden_layers"]))


def pattern(model) -> str:
    """One character a BLOCK: every layer's mixer, then its MLP (``-``)."""
    return "".join(k + "-" for k in kinds(model))


def layer_names(model, i):
    """The leaves of layer ``i``: its mixer's block, then its MLP's."""
    return ([f"layers.{2 * i}.{t}" for t in LEAVES[kinds(model)[i]]]
            + [f"layers.{2 * i + 1}.{t}" for t in LEAVES["-"]])


def mamba_dims(model):
    """(I, N, R, K)."""
    return (model["mamba_expand"] * model["hidden_size"],
            model["mamba_d_state"], model["mamba_dt_rank"],
            model["mamba_d_conv"])


def mamba(model, w, u, quant=None):
    """u [n, s, hidden] (normalised) -> [n, s, hidden]; every row starts
    from a zero state."""
    rows, s, _ = u.shape
    inner, n, rank, k = mamba_dims(model)
    eps = model["rms_norm_eps"]
    x, z = jnp.split(mm(u, w["in_proj"], quant), 2, -1)
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    x = jax.nn.silu(w["conv_bias"] + sum(
        w["conv"][i] * padded[:, i:i + s] for i in range(k)))
    dbc = mm(x, w["x_proj"], quant)
    dt = rms_norm(dbc[..., :rank], w["dt_norm"], eps)
    b_mat = _round(rms_norm(dbc[..., rank:rank + n], w["b_norm"], eps), quant)
    c_mat = _round(rms_norm(dbc[..., rank + n:], w["c_norm"], eps), quant)
    delta = jax.nn.softplus(mm(dt, w["dt_proj"], quant) + w["dt_bias"])
    a = -jnp.exp(w["A_log"]).T                           # [I, N]
    x = _round(x, quant)

    def token(h, t):
        x_t, b_t, c_t, d_t = t           # [rows, I], [rows, N] x 2, [rows, I]
        h = (jnp.exp(d_t[..., None] * a) * h
             + (d_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], -1)

    _, y = jax.lax.scan(
        token, jnp.zeros((rows, inner, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b_mat, c_mat, delta)))
    y = jnp.moveaxis(y, 0, 1) + w["D"] * x               # [rows, s, I]
    return mm(y * jax.nn.silu(z), w["out_proj"], quant)


def mlp(w, u, quant=None):
    gate, up = jnp.split(mm(u, w["gate_up"], quant), 2, -1)
    return mm(jax.nn.silu(gate) * up, w["down"], quant)


def layer(model, w, x, quant=None):
    """One published layer: ``w`` = (its mixer's leaves, its MLP's), by
    their short names; the mixer's kind by the leaves it is given."""
    mixer, ffn = w
    eps = model["rms_norm_eps"]
    mix = mamba if "in_proj" in mixer else attention
    x = x + mix(model, mixer, rms_norm(x, mixer["norm"], eps), quant)
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
    weights alive at a time; the head is the embedding's transpose."""
    step = jax.jit(lambda w, x: layer(model, w, x, quant))
    embed = get(["embed"])["embed"]
    xs = [jnp.take(embed, ids, axis=0) for ids, _, _ in blocks]
    del embed
    for i in range(model["num_hidden_layers"]):
        w = _short(model, i, get(layer_names(model, i)))
        xs = [step(w, x) for x in xs]
    tail = get(["final_norm", "embed"])
    return [mm(rms_norm(x[rows, cols], tail["final_norm"],
                        model["rms_norm_eps"]), tail["embed"].T, quant)
            for x, (_, rows, cols) in zip(xs, blocks)]


def loss_and_grads(model, leaves, ids, labels, quant=None, rows_per_block=1):
    """Mean next-token cross entropy over the whole batch and its gradient
    (the embedding's takes both of its uses), accumulated over blocks of
    rows. No cell trains this family."""
    n, s = ids.shape
    one = jax.checkpoint(lambda w, x: layer(model, w, x, quant))

    def block_loss(lv, x, y):
        h = jnp.take(lv["embed"], x, axis=0)
        for i in range(model["num_hidden_layers"]):
            h = one(_short(model, i, lv), h)
        h = rms_norm(h, lv["final_norm"], model["rms_norm_eps"])
        logp = jax.nn.log_softmax(mm(h, lv["embed"].T, quant), -1)
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
