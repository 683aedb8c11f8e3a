"""Plain reference: a decoder whose every layer is compressed convolutional
attention (CCA, arXiv:2510.04476) and top-1 routed experts behind an MLP
router with a state handed down the layers (ZAYA1, arXiv:2511.17127;
``model_type: zaya``), with a per-channel scale on both branches of each
residual merge and the embedding tied to the output head.

Full sequence, float32 under ``jax.default_matmul_precision("highest")``
(the callers set it): no cache, no kernel, no state carried between tokens
(a token's previous token is the row before it). ``refs/decoder.py`` gives
``mm`` / ``_round`` (the control's rounding), ``rms_norm`` and the rotary
tables; nothing of the program is imported.

Per layer, x [S, H]; Hq query heads, Hkv key/value heads, G = Hq / Hkv, head
size d, Cq = Hq d, Ck = Hkv d, n = RMSNorm; ``prev(a)[t] = a[t-1]``, 0 at t = 0:

1. CCA. u = n(x); q0 = u Wq [S, Cq], k0 = u Wk [S, Ck].
   m_q[h] = (q0[h] + k0[h // G]) / 2; m_k[j] = mean of m_q over j's G heads.
   c = [q0 | k0]; c1 = a0 prev(c) + a1 c + b (per channel);
   c2 = prev(c1) A0 + c1 A1 + b', A0 and A1 block-diagonal: one [d, d] block
   a head over the Hq + Hkv heads. q = c2[:Cq] + m_q, k = c2[Cq:] + m_k.
   v = [u Wv1 | prev(u Wv2)], each Ck / 2 wide, read as Hkv heads of d: with
   two heads, head 0 is the token's own projection, head 1 the previous
   token's.
   q <- sqrt(d) q / |q|, k <- tau[j] sqrt(d) k / |k| per head; rotary on the
   first partial_rotary_factor x d dims of each head; causal GQA softmax
   attention at 1/sqrt(d); a = concat_h(o_h) Wo.
2. h = s_r x + s_o a + b_o (per channel).
3. z = n(h); r = z Wd + bd (+ g r_prev for every layer but the first; r is
   handed on AFTER the addition); p = softmax(W3 gelu(W2 gelu(W1 n(r) + b1)
   + b2)) over E experts and one skip; e* = argmax p.
4. y = p[e*] SwiGLU_e*(z), 0 where e* is the skip; x' = s_r' h + s_o' y + b_o'.
5. logits = n(x) E^T, E the embedding.

What the published config does not state is listed in the configuration's
``assumed`` group, word for word.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import decoder
from .decoder import _round, loss0_expected, mm, rms_norm  # noqa: F401

LEAVES = ("attn_norm", "q_down", "k_down", "v_down", "conv0", "conv0_bias",
          "conv1", "conv1_bias", "temp", "o", "attn_res_scale",
          "attn_out_scale", "attn_out_bias", "mlp_norm", "router_down",
          "router_down_bias", "router_gate", "router_norm", "router_w1",
          "router_b1", "router_w2", "router_b2", "router_w3",
          "experts_gate_up", "experts_down", "mlp_res_scale",
          "mlp_out_scale", "mlp_out_bias")


HEAD_ROWS = 1024        # rows of logits made at a time in ``logits_at``


def layer_names(model, i):
    return [f"layers.{i}.{t}" for t in LEAVES]


def _prev(a):
    """a[:, t-1] at t, zeros at t = 0 (a: [n, s, ...])."""
    return jnp.pad(a, ((0, 0), (1, 0)) + ((0, 0),) * (a.ndim - 2))[:, :-1]


def _unit(x, d):
    return x * (math.sqrt(d) / jnp.maximum(
        jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)), 1e-6))


def _partial_rope(x, rot, theta):
    cos, sin = decoder.rope_tables(rot, jnp.arange(x.shape[1]), theta)
    return jnp.concatenate([decoder.rope(x[..., :rot], cos, sin),
                            x[..., rot:]], -1)


def cca(model, w, u, quant, drop=()):
    """u [n, s, H] (normalised) -> [n, s, H]; every row starts at 0."""
    n, s, _ = u.shape
    hq, hkv, d = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    g = hq // hkv
    q0, k0 = mm(u, w["q_down"], quant), mm(u, w["k_down"], quant)
    m_q = (q0.reshape(n, s, hq, d)
           + jnp.repeat(k0.reshape(n, s, hkv, d), g, axis=2)) / 2
    m_k = jnp.mean(m_q.reshape(n, s, hkv, g, d), axis=3)
    c = jnp.concatenate([q0, k0], -1)
    c1 = (w["conv0"][0] * _prev(c) + w["conv0"][1] * c
          + w["conv0_bias"]).reshape(n, s, hq + hkv, d)

    def blocks(a, taps):
        return jnp.einsum("nshd,hde->nshe", _round(a, quant),
                          _round(taps, quant),
                          precision=jax.lax.Precision.HIGHEST)
    c2 = (blocks(_prev(c1), w["conv1"][0]) + blocks(c1, w["conv1"][1])
          + w["conv1_bias"].reshape(hq + hkv, d))
    if "conv" in drop:
        c2 = jnp.zeros_like(c2)
    if "qk_mean" in drop:
        m_q, m_k = jnp.zeros_like(m_q), jnp.zeros_like(m_k)
    q, k = c2[:, :, :hq] + m_q, c2[:, :, hq:] + m_k
    half = hkv * d // 2
    v2 = mm(u, w["v_down"][:, half:], quant)
    v = jnp.concatenate([mm(u, w["v_down"][:, :half], quant),
                         v2 if "v_shift" in drop else _prev(v2)],
                        -1).reshape(n, s, hkv, d)
    rot = int(d * model["partial_rotary_factor"])
    theta = model["rope_parameters"]["hybrid"]["rope_theta"]
    q = _partial_rope(_unit(q, d), rot, theta)
    k = _partial_rope(_unit(k, d) * w["temp"][:, None], rot, theta)
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", _round(q, quant), _round(k, quant),
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("nhqk,nkhd->nqhd", _round(probs, quant), _round(v, quant),
                     precision=jax.lax.Precision.HIGHEST)
    return mm(out.reshape(n, s, hq * d), w["o"], quant)


def router(model, w, t, r_prev):
    """t [tokens, H], r_prev [tokens, R] or None -> (p [tokens, E + 1], r)."""
    hi = jax.lax.Precision.HIGHEST
    r = jnp.matmul(t, w["router_down"], precision=hi) + w["router_down_bias"]
    if r_prev is not None:
        r = r + w["router_gate"] * r_prev
    h = rms_norm(r, w["router_norm"], model["rms_norm_eps"])
    h = jax.nn.gelu(jnp.matmul(h, w["router_w1"], precision=hi)
                    + w["router_b1"], approximate=False)
    h = jax.nn.gelu(jnp.matmul(h, w["router_w2"], precision=hi)
                    + w["router_b2"], approximate=False)
    return jax.nn.softmax(jnp.matmul(h, w["router_w3"], precision=hi), -1), r


def swiglu(x, w_gate_up, w_down, quant):
    g, u = jnp.split(mm(x, w_gate_up, quant), 2, -1)
    return mm(jax.nn.silu(g) * u, w_down, quant)


def routed_block(model, w, z, r_prev, quant):
    """z [n, s, H] -> (y [n, s, H], r [n s, R]). Every expert runs over every
    token; a token's own expert gets its probability as weight, the others
    and the skip choice 0."""
    t = z.reshape(-1, z.shape[-1])
    p, r = router(model, w, t, r_prev)
    share = p * jax.nn.one_hot(jnp.argmax(p, -1), p.shape[-1], dtype=p.dtype)

    def expert(acc, xs):
        w_gu, w_dn, wt = xs
        return acc + wt[:, None] * swiglu(t, w_gu, w_dn, quant), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(t),
                        (w["experts_gate_up"], w["experts_down"],
                         share.T[:model["num_experts"]]))
    return y.reshape(z.shape), r


def layer(model, w, x, r_prev=None, quant=None, drop=()):
    """One block: (x', r). ``drop`` names terms of the layer left out, for the
    tests' leave-one-out control and nothing else: "conv" (both
    convolutions), "qk_mean", "v_shift", "router_state", "residual_scale"."""
    eps = model["rms_norm_eps"]

    def merge(kind, res, out):
        if "residual_scale" in drop:
            return res + out
        return (w[kind + "_res_scale"] * res + w[kind + "_out_scale"] * out
                + w[kind + "_out_bias"])
    h = merge("attn", x, cca(model, w, rms_norm(x, w["attn_norm"], eps),
                             quant, drop))
    y, r = routed_block(model, w, rms_norm(h, w["mlp_norm"], eps),
                        None if "router_state" in drop else r_prev, quant)
    return merge("mlp", h, y), r


def _short(model, i, leaves):
    p = f"layers.{i}."
    return {n[len(p):]: leaves[n] for n in layer_names(model, i)}


# -- serving: logits of a few rows, one layer's weights at a time -------------

def logits_at(model, get, blocks, quant=None, drop=()):
    """As ``decoder.logits_at``: for each block (ids [n, s], rows, cols) the
    logits [len(rows), V] at (rows[j], cols[j]); ``get(names)`` makes one
    layer's leaves at a time. The head is the embedding, transposed."""
    first = jax.jit(lambda w, x: layer(model, w, x, None, quant, drop))
    step = jax.jit(lambda w, x, r: layer(model, w, x, r, quant, drop))
    embed = get(["embed"])["embed"]
    xs = [(jnp.take(embed, ids, axis=0), None) for ids, _, _ in blocks]
    del embed
    for i in range(model["num_hidden_layers"]):
        w = _short(model, i, get(layer_names(model, i)))
        xs = [first(w, x) if r is None else step(w, x, r) for x, r in xs]
    tail = get(["final_norm", "embed"])

    @jax.jit
    def head(x, embed):
        return jnp.einsum("rh,vh->rv", _round(x, quant), _round(embed, quant),
                          precision=jax.lax.Precision.HIGHEST)
    out = []
    for (x, _), (_, rows, cols) in zip(xs, blocks):
        x = rms_norm(x[rows, cols], tail["final_norm"], model["rms_norm_eps"])
        # on the host, HEAD_ROWS rows at a time: 4,096 rows of 262,272
        # float32 logits would be 4.3 GB a block beside the 2.1 GB table
        out.append(np.concatenate(
            [np.asarray(head(x[a:a + HEAD_ROWS], tail["embed"]))
             for a in range(0, x.shape[0], HEAD_ROWS)]))
    return out


# -- training: loss and gradients ---------------------------------------------

def _nll_sum(model, leaves, ids, labels, quant):
    x, r = jnp.take(leaves["embed"], ids, axis=0), None
    block = jax.checkpoint(lambda w, x_, r_: layer(model, w, x_, r_, quant))
    for i in range(model["num_hidden_layers"]):
        x, r = block(_short(model, i, leaves), x, r)
    hidden = rms_norm(x, leaves["final_norm"], model["rms_norm_eps"])
    logp = jax.nn.log_softmax(mm(hidden, leaves["embed"].T, quant), -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def loss_and_grads(model, leaves, ids, labels, quant=None, rows_per_block=1):
    """Mean next-token cross entropy over the whole batch and its gradient,
    accumulated over blocks of rows. No auxiliary term: the family balances
    its experts with a bias (left out here: the configuration says why)."""
    n, s = ids.shape

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(lv, acc, x, y):
        l, g = jax.value_and_grad(
            lambda lv_: _nll_sum(model, lv_, x, y, quant) / (n * s))(lv)
        return l, jax.tree.map(jnp.add, acc, g)

    loss, grads = 0.0, jax.tree.map(jnp.zeros_like, leaves)
    for a in range(0, n, rows_per_block):
        l, grads = step(leaves, grads, ids[a:a + rows_per_block],
                        labels[a:a + rows_per_block])
        loss = loss + l
    return loss, grads
