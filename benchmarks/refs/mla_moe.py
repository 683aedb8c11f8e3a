"""Plain reference: a decoder with latent (MLA) attention and sigmoid-routed
experts beside a shared expert, after a dense start (DeepSeek-V2/V3's layout
as GLM-4.7-Flash publishes it, ``model_type: glm4_moe_lite``).

``refs/decoder.py``'s pieces (``mm``, ``_round``, ``rms_norm``, ``rope``,
``dense_mlp``) and its two drivers through ``arch=``; what is written here
is the attention and the routed block, in the EXPANDED form only: per-head
keys and values are made from the latent for every position, no cache, no
absorption, no kernels. Float32 under ``jax.default_matmul_precision(
"highest")`` (the callers set it).

Attention (x: the layer's normalised input):
    c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads of [nope | rope], RoPE on rope
    [c | k_r] = x W_kva;  c_kv = RMSNorm(c);  k_r = RoPE(k_r), one for all heads
    [k_nope_h | v_h] = c_kv W_kvb per head;  k_h = [k_nope_h | k_r]
    causal softmax(q_h k_h^T / sqrt(nope + rope)) v_h;  heads concatenated x W_o

Routed block:  s = sigmoid(x W_g) (float32); experts = top-k of s + b;
    weights = s at the chosen experts (WITHOUT b), / (their sum + 1e-20) if
    norm_topk_prob, x routed_scaling_factor;  y = sum weight x SwiGLU_e(x)
    + SwiGLU_shared(x). Every expert runs over every token and the tokens
    it was not chosen for get weight 0.

What the published config does not state is listed in the configuration's
``assumed`` group: the rotate-half convention on the rope dims, softmax scale
1/sqrt(nope + rope), kv_b's columns per head as [nope | v].
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp

from . import decoder
from .decoder import _round, loss0_expected, mm, rms_norm  # noqa: F401

ATTN = ("attn_norm", "mlp_norm", "q_a", "q_a_norm", "q_b", "kv_a",
        "kv_a_norm", "kv_b", "o")
DENSE = ("gate_up", "down")
ROUTED = ("router", "router_bias", "experts_gate_up", "experts_down",
          "shared_gate_up", "shared_down")


def layer_names(model, i):
    p = f"layers.{i}."
    tail = DENSE if i < model["first_k_dense_replace"] else ROUTED
    return [p + t for t in ATTN + tail]


def attention(model, w, x, quant):
    """x [n, s, H] -> [n, s, H]; causal, every row starts at position 0."""
    n, s, _ = x.shape
    heads, eps = model["num_attention_heads"], model["rms_norm_eps"]
    rank = model["kv_lora_rank"]
    nope, rope, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    q = mm(rms_norm(mm(x, w["q_a"], quant), w["q_a_norm"], eps), w["q_b"],
           quant).reshape(n, s, heads, nope + rope)
    ckr = mm(x, w["kv_a"], quant)
    c_kv = rms_norm(ckr[..., :rank], w["kv_a_norm"], eps)
    cos, sin = decoder.rope_tables(rope, jnp.arange(s), model["rope_theta"])
    q_rope = decoder.rope(q[..., nope:], cos, sin)
    k_r = decoder.rope(ckr[..., None, rank:], cos, sin)        # [n, s, 1, rope]
    kv = mm(c_kv, w["kv_b"], quant).reshape(n, s, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (n, s, heads, rope))], -1)
    scores = jnp.einsum("nqhd,nkhd->nhqk", _round(q, quant), _round(k, quant),
                        precision=jax.lax.Precision.HIGHEST
                        ) / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("nhqk,nkhd->nqhd", _round(probs, quant),
                     _round(kv[..., nope:], quant),
                     precision=jax.lax.Precision.HIGHEST)
    return mm(out.reshape(n, s, heads * vd), w["o"], quant)


def router(model, w, t):
    """t [tokens, H] -> (weights [tokens, k], experts [tokens, k])."""
    s = jax.nn.sigmoid(jnp.matmul(t, w["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(s + w["router_bias"], model["num_experts_per_tok"])
    weights = jnp.take_along_axis(s, ids, -1)
    if model["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * model["routed_scaling_factor"], ids


def swiglu(x, w_gate_up, w_down, quant):
    g, u = jnp.split(mm(x, w_gate_up, quant), 2, -1)
    return mm(jax.nn.silu(g) * u, w_down, quant)


def routed_block(model, w, z, quant):
    """z [n, s, H] -> [n, s, H]: the routed experts plus the shared one."""
    t = z.reshape(-1, z.shape[-1])
    weights, ids = router(model, w, t)
    share = jnp.sum(jax.nn.one_hot(ids, model["n_routed_experts"],
                                   dtype=jnp.float32) * weights[..., None], 1)

    def expert(acc, xs):
        w_gu, w_dn, wt = xs
        return acc + wt[:, None] * swiglu(t, w_gu, w_dn, quant), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(t),
                        (w["experts_gate_up"], w["experts_down"], share.T))
    y = y + swiglu(t, w["shared_gate_up"], w["shared_down"], quant)
    return y.reshape(z.shape)


def layer(model, w, x, quant=None, route=None):
    """One block, dense or routed by the leaves it is given. Returns
    (x, aux, counts) as ``decoder.layer`` does: this model trains with no
    auxiliary term (its balance is the selection bias), so 0 and None."""
    eps = model["rms_norm_eps"]
    h = x + attention(model, w, rms_norm(x, w["attn_norm"], eps), quant)
    z = rms_norm(h, w["mlp_norm"], eps)
    y = (routed_block(model, w, z, quant) if "router" in w
         else decoder.dense_mlp(w, z, quant))
    return h + y, jnp.zeros((), jnp.float32), None


def logits_at(model, get, blocks, quant=None):
    return decoder.logits_at(model, get, blocks, quant,
                             arch=sys.modules[__name__])


def loss_and_grads(model, leaves, ids, labels, quant=None, rows_per_block=1):
    return decoder.loss_and_grads(model, leaves, ids, labels, quant,
                                  rows_per_block, arch=sys.modules[__name__])
