"""Plain reference: a hybrid decoder of Mamba-2, attention and routed-expert
blocks (``model_type: nemotron_h``, as NVIDIA-Nemotron-3-Nano-30B-A3B
publishes it), given the SHARE of it a configuration holds (the first
``num_hidden_layers`` blocks of the pattern, ``n_routed_experts`` experts
from ``first_expert_held`` of a router ``router_width`` wide, ``vocab_size``
ids).

Full sequence, float32 under ``jax.default_matmul_precision("highest")`` (the
callers set it): no cache, no kernel, and the recurrence TOKEN BY TOKEN in a
``lax.scan`` over time, not in the chunked form the program runs a prompt in
(it must not share the program's algebra). ``refs/decoder.py`` gives ``mm`` /
``_round`` (the control's rounding), ``rms_norm`` and the two drivers;
nothing of the program is imported.

``u = RMSNorm(x; w, eps)``; every block is ``x <- x + Mixer(u)``, the mixer
by the block's character in ``hybrid_override_pattern``; logits =
``RMSNorm(x; w_f) W_head``, the head untied, no embedding scale.

``M``, Mamba-2. H heads of P, d_inner = H P, G groups, state size N,
conv_dim = d_inner + 2 G N.
    [z | xBC | dt] = u W_in                  widths d_inner | conv_dim | H
    xBC <- silu(conv(xBC))   depthwise causal over time, ``conv_kernel`` taps
                             (the last tap is the token itself), a bias,
                             zeros before the sequence
    [x | B | C] = xBC        x -> [H, P]; B, C -> [G, N]; head h uses group
                             h // (H / G)
    Delta = softplus(dt + dt_bias) [H]       A = -exp(A_log) [H]
    S_t[h] = exp(Delta_t[h] A[h]) S_{t-1}[h] + Delta_t[h] x_t[h] (x) B_t[g(h)]
                             S: [P, N], zero before the sequence
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
    y <- GroupRMSNorm(y * silu(z); w, groups of d_inner / G)   gate BEFORE norm
    out = y W_out

``*``, attention. q = u W_q, k = u W_k, v = u W_v (one leaf [q | k | v]),
causal softmax at 1 / sqrt(head_dim), the KV heads repeated, W_o; NO position
embedding of any kind.

``E``, experts. s = sigmoid(u W_r) over ``router_width`` experts; the choice
is the top-k of s + b; weights s[chosen] / (sum + 1e-20) x
``routed_scaling_factor``; expert i: relu(u W_up,i)^2 W_down,i; the experts
held here add their share, a choice held elsewhere adds nothing; one shared
expert of the same form is added.

What the published config does not state is listed in the configuration's
``assumed`` group.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp

from . import decoder
from .decoder import _round, loss0_expected, mm, rms_norm  # noqa: F401

LEAVES = {
    "M": ("norm", "in_proj", "conv", "conv_bias", "dt_bias", "A_log", "D",
          "gate_norm", "out_proj"),
    "*": ("norm", "qkv", "o"),
    "E": ("norm", "router", "router_bias", "experts_up", "experts_down",
          "shared_up", "shared_down"),
}


def kinds(model) -> str:
    """The pattern of the blocks held: one character a block."""
    return model["hybrid_override_pattern"][:model["num_hidden_layers"]]


def layer_names(model, i):
    return [f"layers.{i}.{t}" for t in LEAVES[kinds(model)[i]]]


def mamba_dims(model):
    """(H, P, G, N, d_inner, conv_dim)."""
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    return h, p, g, n, h * p, h * p + 2 * g * n


def mamba(model, w, u, quant=None):
    """u [n, s, hidden] (normalised) -> [n, s, hidden]; every row starts
    from a zero state."""
    rows, s, _ = u.shape
    h, p, g, n, inner, conv = mamba_dims(model)
    k = model["conv_kernel"]
    zxd = mm(u, w["in_proj"], quant)
    z, xbc, dt = (zxd[..., :inner], zxd[..., inner:inner + conv],
                  zxd[..., inner + conv:])
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["conv_bias"] + sum(
        w["conv"][i] * padded[:, i:i + s] for i in range(k)))
    x = _round(xbc[..., :inner], quant).reshape(rows, s, h, p)
    per = h // g
    b_mat = jnp.repeat(_round(xbc[..., inner:inner + g * n], quant).reshape(
        rows, s, g, n), per, axis=2)                     # [rows, s, H, N]
    c_mat = jnp.repeat(_round(xbc[..., inner + g * n:], quant).reshape(
        rows, s, g, n), per, axis=2)
    delta = jax.nn.softplus(dt + w["dt_bias"])           # [rows, s, H]
    a = -jnp.exp(w["A_log"])

    def token(state, t):
        x_t, b_t, c_t, d_t = t
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], -1)

    _, y = jax.lax.scan(
        token, jnp.zeros((rows, h, p, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b_mat, c_mat, delta)))
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x      # [rows, s, H, P]
    y = y.reshape(rows, s, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(rows, s, g, inner // g), 1.0,
                 model["rms_norm_eps"]).reshape(rows, s, inner)
    return mm(y * w["gate_norm"], w["out_proj"], quant)


def attention(model, w, u, quant=None):
    """u [n, s, hidden] -> [n, s, hidden]: causal GQA softmax attention, no
    position embedding, one query head at a time (32 heads of [s, s]
    float32 scores at once would be 3.4 GB a row at 5,120 positions)."""
    rows, s, _ = u.shape
    n_q, n_kv, hd = (model["num_attention_heads"],
                     model["num_key_value_heads"], model["head_dim"])
    qkv = mm(u, w["qkv"], quant)
    q, k, v = jnp.split(qkv, [n_q * hd, (n_q + n_kv) * hd], -1)
    q = q.reshape(rows, s, n_q, hd)
    k = jnp.repeat(k.reshape(rows, s, n_kv, hd), n_q // n_kv, axis=2)
    v = jnp.repeat(v.reshape(rows, s, n_kv, hd), n_q // n_kv, axis=2)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv_h):
        q_h, k_h, v_h = qkv_h                            # [rows, s, hd]
        scores = jnp.einsum("nqd,nkd->nqk", _round(q_h, quant),
                            _round(k_h, quant),
                            precision=jax.lax.Precision.HIGHEST
                            ) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("nqk,nkd->nqd", _round(probs, quant),
                          _round(v_h, quant),
                          precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return mm(jnp.moveaxis(out, 0, 2).reshape(rows, s, n_q * hd), w["o"],
              quant)


def router(model, w, t):
    """t [tokens, hidden] -> share [tokens, router_width]: each chosen
    expert's weight, 0 elsewhere."""
    s = jax.nn.sigmoid(jnp.matmul(t, w["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(s + w["router_bias"], model["num_experts_per_tok"])
    weights = jnp.take_along_axis(s, ids, -1)
    if model["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    weights = weights * model["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(ids, model["router_width"],
                                  dtype=jnp.float32) * weights[..., None], 1)


def relu2(x, w_up, w_down, quant):
    return mm(jnp.square(jax.nn.relu(mm(x, w_up, quant))), w_down, quant)


def experts(model, w, u, quant=None):
    """u [n, s, hidden] -> [n, s, hidden]: every expert held runs over every
    token and is weighted by its share of the token (0 where it was not
    chosen; a choice held elsewhere is nobody's here), plus the shared
    expert."""
    t = u.reshape(-1, u.shape[-1])
    first, held = model["first_expert_held"], model["n_routed_experts"]
    share = router(model, w, t)[:, first:first + held]

    def expert(acc, xs):
        w_up, w_down, wt = xs
        return acc + wt[:, None] * relu2(t, w_up, w_down, quant), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(t),
                        (w["experts_up"], w["experts_down"], share.T))
    y = y + relu2(t, w["shared_up"], w["shared_down"], quant)
    return y.reshape(u.shape)


def layer(model, w, x, quant=None, route=None):
    """One block, its kind by the leaves it is given. Returns (x, aux,
    counts) as ``decoder.layer`` does: no auxiliary term (the router's
    balance is its selection bias), so 0 and None."""
    u = rms_norm(x, w["norm"], model["rms_norm_eps"])
    mixer = (mamba if "in_proj" in w else attention if "qkv" in w
             else experts)
    return x + mixer(model, w, u, quant), jnp.zeros((), jnp.float32), None


def logits_at(model, get, blocks, quant=None):
    return decoder.logits_at(model, get, blocks, quant,
                             arch=sys.modules[__name__])


def loss_and_grads(model, leaves, ids, labels, quant=None, rows_per_block=1):
    return decoder.loss_and_grads(model, leaves, ids, labels, quant,
                                  rows_per_block, arch=sys.modules[__name__])
