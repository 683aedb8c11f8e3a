"""Plain reference: a decoder-only transformer with grouped-query attention.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (the callers set it): RMSNorm,
rotary embedding (half-rotation, as Hugging Face's Llama/Mistral/OLMoE),
causal softmax attention with the KV heads repeated, a SwiGLU MLP or the
routed block of ``refs/olmoe.py``, and cross entropy (AdamW, which knows
nothing of the architecture, is ``benchmarks/adamw.py``). No kernels, no
cache, no batching tricks. It imports nothing of the program and takes its
weights from ``benchmarks/weights.py`` by canonical name.

Another family's reference reuses the pieces (``mm``, ``rms_norm``,
``attention``, ``dense_mlp``) and the two drivers: ``logits_at`` and
``loss_and_grads`` walk the layers through ``arch.layer_names`` and
``arch.layer``, which are this module's own unless a family passes its.

``quant`` is the control of "How correct is decided": the same mathematics
with every matrix multiplication's inputs rounded to the next precision
below the configuration's ("fp8": float8_e4m3 with a per-tensor scale;
"bf16"; None: none). It exists to show that the limits catch it.
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp

from . import olmoe


def _round(x, quant):
    """``x`` rounded to the control's precision. ``reduce_precision`` is an
    operation of its own, which the compiler may not fold away as it may a
    pair of converts. The backward pass sees the rounded values but is not
    rounded itself (a gradient of 1e-5 would vanish in float8), which makes
    this the mildest control there is."""
    if quant is None:
        return x
    if quant == "bf16":
        low = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    elif quant == "fp8":        # e4m3, scaled per tensor into its range
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
        low = jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                       mantissa_bits=3) * scale
    else:
        raise ValueError(f"unknown control precision {quant!r}")
    return x + jax.lax.stop_gradient(low - x)


def mm(a, b, quant=None):
    return jnp.matmul(_round(a, quant), _round(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_tables(head_dim, positions, theta):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def attention(model, w, x, quant):
    """x [n, s, H] -> [n, s, H]; causal, every row starts at position 0."""
    n, s, h = x.shape
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or h // n_q
    qkv = mm(x, w["qkv"], quant)
    q, k, v = jnp.split(qkv, [n_q * hd, (n_q + n_kv) * hd], -1)
    q = q.reshape(n, s, n_q, hd)
    k = k.reshape(n, s, n_kv, hd)
    v = v.reshape(n, s, n_kv, hd)
    cos, sin = rope_tables(hd, jnp.arange(s), model["rope_theta"])
    q, k = rope(q, cos, sin), rope(k, cos, sin)
    k = jnp.repeat(k, n_q // n_kv, axis=2)
    v = jnp.repeat(v, n_q // n_kv, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", _round(q, quant), _round(k, quant),
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("nhqk,nkhd->nqhd", _round(probs, quant), _round(v, quant),
                     precision=jax.lax.Precision.HIGHEST)
    return mm(out.reshape(n, s, n_q * hd), w["o"], quant)


def dense_mlp(w, x, quant):
    g, u = jnp.split(mm(x, w["gate_up"], quant), 2, -1)
    return mm(jax.nn.silu(g) * u, w["down"], quant)


def layer_names(model, i):
    p = f"layers.{i}."
    tail = (("router", "experts_gate_up", "experts_down")
            if model.get("num_experts") else ("gate_up", "down"))
    return [p + t for t in ("attn_norm", "mlp_norm", "qkv", "o") + tail]


def layer(model, w, x, quant=None, route=None):
    """One block. ``w`` holds this layer's leaves by their short names.
    Returns (x, aux, counts): aux is the routed block's load-balance term
    (0 for a dense block) and counts its routing sums (None for a dense
    block); ``route`` is what olmoe.routed_block needs to take that term
    over a whole batch while it sees a block of rows."""
    eps = model["rms_norm_eps"]
    h = x + attention(model, w, rms_norm(x, w["attn_norm"], eps), quant)
    z = rms_norm(h, w["mlp_norm"], eps)
    if model.get("num_experts"):
        y, aux, counts = olmoe.routed_block(model, w, z, quant, mm, route)
    else:
        y, aux, counts = dense_mlp(w, z, quant), jnp.zeros((), jnp.float32), None
    return h + y, aux, counts


def _short(model, i, leaves, arch):
    p = f"layers.{i}."
    return {n[len(p):]: leaves[n] for n in arch.layer_names(model, i)}


def _arch(arch):
    return arch or sys.modules[__name__]


# -- serving: logits of a few rows, one layer's weights at a time -------------

def logits_at(model, get, blocks, quant=None, arch=None):
    """For each block (ids [n, s], rows, cols): logits [len(rows), V] at
    positions (rows[j], cols[j]). Rows are padded on the right (causal, so
    padding cannot reach back). ``get(names)`` returns those leaves in
    float32; it is called once per layer, so only one layer's weights are
    alive, and each block is a batch of its own, so the rest fits."""
    arch = _arch(arch)
    step = jax.jit(lambda w, x: arch.layer(model, w, x, quant)[0])
    embed = get(["embed"])["embed"]
    xs = [jnp.take(embed, ids, axis=0) for ids, _, _ in blocks]
    del embed
    for i in range(model["num_hidden_layers"]):
        w = _short(model, i, get(arch.layer_names(model, i)), arch)
        xs = [step(w, x) for x in xs]
    tail = get(["final_norm", "head"])
    return [mm(rms_norm(x[rows, cols], tail["final_norm"],
                        model["rms_norm_eps"]), tail["head"], quant)
            for x, (_, rows, cols) in zip(xs, blocks)]


# -- training: loss and gradients ---------------------------------------------

def _forward_rows(model, leaves, ids, quant, routes, arch):
    """(final hidden states, load-balance term, per-layer routing sums)."""
    x = jnp.take(leaves["embed"], ids, axis=0)
    aux, seen = jnp.zeros((), jnp.float32), []
    block = jax.checkpoint(lambda w, x_, r: arch.layer(model, w, x_, quant, r))
    for i in range(model["num_hidden_layers"]):
        x, a, counts = block(_short(model, i, leaves, arch), x,
                             None if routes is None else routes[i])
        aux = aux + a
        seen.append(counts)
    return rms_norm(x, leaves["final_norm"], model["rms_norm_eps"]), aux, seen


def _nll_sum(model, leaves, hidden, labels, quant):
    logits = mm(hidden, leaves["head"], quant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def loss_and_grads(model, leaves, ids, labels, quant=None, rows_per_block=1,
                   arch=None):
    """Mean next-token cross entropy (+ the routed blocks' load-balance
    term times ``aux_loss_weight``) over the whole batch, and its gradient,
    accumulated over blocks of rows so that it fits. The load-balance term
    couples all tokens of the batch, so a first pass without gradients takes
    each routed layer's batch-wide routing shares (olmoe.merge_counts); a
    layer that routes nothing reports no counts and gets no shares."""
    arch = _arch(arch)
    n, s = ids.shape
    blocks = [(a, min(a + rows_per_block, n))
              for a in range(0, n, rows_per_block)]
    routed = bool(model.get("num_experts"))
    routes = None
    if routed:
        first = jax.jit(lambda lv, x: _forward_rows(model, lv, x, quant,
                                                    None, arch)[2])
        per_block = [first(leaves, ids[a:b]) for a, b in blocks]
        routes = [None if per_block[0][i] is None else
                  olmoe.merge_counts([pb[i] for pb in per_block], n * s)
                  for i in range(model["num_hidden_layers"])]

    def block_loss(lv, x, y, rt):
        hidden, aux, _ = _forward_rows(model, lv, x, quant, rt, arch)
        return (_nll_sum(model, lv, hidden, y, quant) / (n * s)
                + model.get("aux_loss_weight", 0.0) * aux)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(lv, acc, x, y, rt):
        l, g = jax.value_and_grad(block_loss)(lv, x, y, rt)
        return l, jax.tree.map(jnp.add, acc, g)

    loss, grads = 0.0, jax.tree.map(jnp.zeros_like, leaves)
    for a, b in blocks:
        l, grads = step(leaves, grads, ids[a:b], labels[a:b], routes)
        loss = loss + l
    return loss, grads


def loss0_expected(model, init_std):
    """Cross entropy of seeded weights before any step: unit-RMS hidden
    states against N(0, std^2) head columns give logits ~N(0, H std^2), and
    E[logsumexp] of V such is ln V + H std^2 / 2."""
    return math.log(model["vocab_size"]) + model["hidden_size"] * init_std ** 2 / 2
