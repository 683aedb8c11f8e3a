"""Plain reference: a hybrid decoder of Gated DeltaNet and gated-attention
layers, each followed by softmax-routed experts beside a gated shared expert
(``model_type: qwen3_next``, as Qwen3-Next-80B-A3B-Instruct publishes it),
given the SHARE of it a configuration holds (the first ``num_hidden_layers``
layers, ``num_experts`` experts from ``first_expert_held`` of a router
``router_width`` wide, ``vocab_size`` ids).

Full sequence, float32 under ``jax.default_matmul_precision("highest")`` (the
callers set it): no cache, no kernel, no batching, the delta rule TOKEN BY
TOKEN in a ``lax.scan`` over time (not the chunked form the program runs a
prompt in: it must not share the program's algebra) and the attention a full
softmax one head at a time. ``refs/decoder.py`` gives ``mm`` / ``_round`` (the
control's rounding) and ``rope_tables``; nothing of the program is imported.

Layer ``i`` is a full-attention layer where ``(i + 1) %
full_attention_interval == 0`` and a Gated DeltaNet layer otherwise
(``kinds``); every layer's second half is the routed block
(``decoder_sparse_step`` 1, ``mlp_only_layers`` empty):

    x <- x + Mixer_i(Norm(x; w))      x <- x + Experts(Norm(x; w'))
    logits = Norm(x; w_f) W_head      Norm(x; w) = x / rms(x) * (1 + w)

every such norm ZERO-CENTRED and in float32, the head untied.

Gated DeltaNet. Hk key heads of K, Hv value heads of V (``linear_*``), key
head j serving value heads j Hv / Hk .. (j + 1) Hv / Hk - 1.
    [q | k | v | z | b | a] = u W_in     widths Hk K | Hk K | Hv V | Hv V | Hv | Hv
    [q | k | v] <- silu(conv([q | k | v]))   depthwise causal over time,
                             ``linear_conv_kernel_dim`` taps (the last tap is
                             the token itself), NO bias, zeros before the
                             sequence
    q <- q / sqrt(|q|^2 + 1e-6) / sqrt(K)     k <- k / sqrt(|k|^2 + 1e-6)
    beta = sigmoid(b)        alpha = exp(-exp(A_log) softplus(a + dt_bias))
    S <- alpha_t S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;
    o_t = S^T q_t            S [K, V] a value head, zero before the sequence
    out = concat_h(RMSNorm(o_t[h]; w_g [V]) * silu(z_t[h])) W_out
                             (norm first, then the gate; this norm is NOT
                             zero-centred)

Gated attention. One leaf ``qkv`` = [Hq x [q | gate] | k | v] (a head's query
and its gate side by side, as ``q_proj`` gives them); zero-centred RMSNorm
over each head of q and of k; a rotary embedding (half-rotation) on the first
``partial_rotary_factor`` x head_dim dims of q and k; causal softmax at 1 /
sqrt(head_dim), the KV heads repeated; ``o * sigmoid(gate)``; W_o; no bias.

Experts. p = softmax(u W_r) over ``router_width`` experts in float32; the top
``num_experts_per_tok``; weights p[chosen] / sum (``norm_topk_prob``); expert
i: (silu(u W_gate,i) * (u W_up,i)) W_down,i; the experts held here add their
share, a choice held elsewhere adds nothing; one shared expert of the same
form times sigmoid(u w_sg) is added.

Leaves are named by BLOCK, two a layer (the program builds a layer as two
residual blocks): layer ``i``'s mixer is ``layers.<2i>``, its experts
``layers.<2i + 1>``. What the published config does not state is listed in
the configuration's ``assumed`` group.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .decoder import _round, loss0_expected, mm, rope_tables  # noqa: F401

LEAVES = {
    "d": ("norm", "in_proj", "conv", "dt_bias", "A_log", "gate_norm",
          "out_proj"),
    "a": ("norm", "qkv", "q_norm", "k_norm", "o"),
    "e": ("norm", "router", "experts_gate_up", "experts_down",
          "shared_gate_up", "shared_down", "shared_gate"),
}
_HIGH = jax.lax.Precision.HIGHEST


def kinds(model) -> str:
    """One character a LAYER: ``a`` gated attention, ``d`` Gated DeltaNet."""
    every = model["full_attention_interval"]
    return "".join("a" if (i + 1) % every == 0 else "d"
                   for i in range(model["num_hidden_layers"]))


def pattern(model) -> str:
    """One character a BLOCK: every layer's mixer, then its experts (``e``)."""
    return "".join(k + "e" for k in kinds(model))


def layer_names(model, i):
    """The leaves of layer ``i``: its mixer's block, then its experts'."""
    return ([f"layers.{2 * i}.{t}" for t in LEAVES[kinds(model)[i]]]
            + [f"layers.{2 * i + 1}.{t}" for t in LEAVES["e"]])


def delta_dims(model):
    """(Hk, Hv, K, V, taps)."""
    return (model["linear_num_key_heads"], model["linear_num_value_heads"],
            model["linear_key_head_dim"], model["linear_value_head_dim"],
            model["linear_conv_kernel_dim"])


def norm(x, w, eps):
    """Zero-centred RMSNorm: ``x / rms(x) * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def delta_rule(q, k, v, alpha, beta):
    """The gated delta rule token by token from a zero state. q, k [n, s,
    H, K]; v [n, s, H, V]; alpha, beta [n, s, H] -> o [n, s, H, V]."""
    n, _, h, key = q.shape

    def token(state, t):
        q_t, k_t, v_t, a_t, b_t = t
        state = a_t[..., None, None] * state
        read = jnp.einsum("nhkv,nhk->nhv", state, k_t, precision=_HIGH)
        u_t = b_t[..., None] * (v_t - read)
        state = state + k_t[..., None] * u_t[:, :, None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, q_t, precision=_HIGH)

    _, o = jax.lax.scan(
        token, jnp.zeros((n, h, key, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1)


def delta_net(model, w, u, quant=None):
    """u [n, s, hidden] (normalised) -> [n, s, hidden]; every row starts
    from a zero state."""
    rows, s, _ = u.shape
    hk, hv, key, val, taps = delta_dims(model)
    kd, vd = hk * key, hv * val
    proj = mm(u, w["in_proj"], quant)
    qkv, z = proj[..., :2 * kd + vd], proj[..., 2 * kd + vd:2 * (kd + vd)]
    b, a = jnp.split(proj[..., 2 * (kd + vd):], 2, -1)
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = _round(jax.nn.silu(sum(w["conv"][i] * padded[:, i:i + s]
                                 for i in range(taps))), quant)

    def unit(t):
        t = jnp.repeat(t.reshape(rows, s, hk, key), hv // hk, axis=2)
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    o = delta_rule(
        unit(qkv[..., :kd]) / math.sqrt(key), unit(qkv[..., kd:2 * kd]),
        qkv[..., 2 * kd:].reshape(rows, s, hv, val),
        jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])),
        jax.nn.sigmoid(b))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + model["rms_norm_eps"]) * w["gate_norm"]
    o = o * jax.nn.silu(z.reshape(rows, s, hv, val))
    return mm(o.reshape(rows, s, vd), w["out_proj"], quant)


def partial_rope(t, cos, sin):
    """t [n, s, H, hd]: the first ``cos.shape[-1]`` dims turned (half
    rotation within them), the rest as they are."""
    rot = cos.shape[-1]
    head, rest = t[..., :rot], t[..., rot:]
    turned = jnp.concatenate([-head[..., rot // 2:], head[..., :rot // 2]], -1)
    return jnp.concatenate(
        [head * cos[None, :, None, :] + turned * sin[None, :, None, :], rest],
        -1)


def attention(model, w, u, quant=None):
    """u [n, s, hidden] -> [n, s, hidden]: causal GQA softmax attention with
    an output gate, one query head at a time."""
    rows, s, _ = u.shape
    n_q, n_kv, hd = (model["num_attention_heads"],
                     model["num_key_value_heads"], model["head_dim"])
    eps = model["rms_norm_eps"]
    proj = mm(u, w["qkv"], quant)
    qg = proj[..., :2 * n_q * hd].reshape(rows, s, n_q, 2 * hd)
    k, v = jnp.split(proj[..., 2 * n_q * hd:].reshape(rows, s, 2 * n_kv, hd),
                     2, axis=2)
    cos, sin = rope_tables(int(model["partial_rotary_factor"] * hd),
                           jnp.arange(s), model["rope_theta"])
    q = partial_rope(norm(qg[..., :hd], w["q_norm"], eps), cos, sin)
    k = partial_rope(norm(k, w["k_norm"], eps), cos, sin)
    k = jnp.repeat(k, n_q // n_kv, axis=2)
    v = jnp.repeat(v, n_q // n_kv, axis=2)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv_h):
        q_h, k_h, v_h = qkv_h                            # [rows, s, hd]
        scores = jnp.einsum("nqd,nkd->nqk", _round(q_h, quant),
                            _round(k_h, quant), precision=_HIGH
                            ) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("nqk,nkd->nqd", _round(probs, quant),
                          _round(v_h, quant), precision=_HIGH)

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    out = jnp.moveaxis(out, 0, 2) * jax.nn.sigmoid(qg[..., hd:])
    return mm(out.reshape(rows, s, n_q * hd), w["o"], quant)


def router(model, w, t):
    """t [tokens, hidden] -> share [tokens, router_width]: each chosen
    expert's weight, 0 elsewhere."""
    p = jax.nn.softmax(jnp.matmul(t, w["router"], precision=_HIGH), -1)
    weights, ids = jax.lax.top_k(p, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(ids, model["router_width"],
                                  dtype=jnp.float32) * weights[..., None], 1)


def swiglu(x, w_gate_up, w_down, quant):
    gate, up = jnp.split(mm(x, w_gate_up, quant), 2, -1)
    return mm(jax.nn.silu(gate) * up, w_down, quant)


def routed(model, w, t, quant=None):
    """t [tokens, hidden] -> the held experts' part: every expert held runs
    over every token and is weighted by its share of the token (0 where it
    was not chosen; a choice held elsewhere is nobody's here)."""
    first, held = model["first_expert_held"], model["num_experts"]
    share = router(model, w, t)[:, first:first + held]

    def expert(acc, xs):
        w_gu, w_dn, wt = xs
        return acc + wt[:, None] * swiglu(t, w_gu, w_dn, quant), None

    return jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(t),
                        (w["experts_gate_up"], w["experts_down"], share.T))[0]


def shared(w, t, quant=None):
    """The shared expert times its gate ``sigmoid(t w_sg)``."""
    gate = jax.nn.sigmoid(jnp.matmul(t, w["shared_gate"], precision=_HIGH))
    return gate * swiglu(t, w["shared_gate_up"], w["shared_down"], quant)


def experts(model, w, u, quant=None):
    t = u.reshape(-1, u.shape[-1])
    return (routed(model, w, t, quant) + shared(w, t, quant)).reshape(u.shape)


def layer(model, w, x, quant=None):
    """One published layer: ``w`` = (its mixer's leaves, its experts'), by
    their short names; the mixer's kind by the leaves it is given."""
    mixer, ffn = w
    eps = model["rms_norm_eps"]
    mix = delta_net if "in_proj" in mixer else attention
    x = x + mix(model, mixer, norm(x, mixer["norm"], eps), quant)
    return x + experts(model, ffn, norm(x, ffn["norm"], eps), quant)


def _short(model, i, leaves):
    """Layer ``i``'s leaves as ``layer`` takes them."""
    return tuple({name.split(".", 2)[2]: leaves[name]
                  for name in layer_names(model, i)
                  if name.startswith(f"layers.{block}.")}
                 for block in (2 * i, 2 * i + 1))


def logits_at(model, get, blocks, quant=None):
    """As ``decoder.logits_at``: for each block (ids [n, s], rows, cols) the
    logits [len(rows), V] at positions (rows[j], cols[j]), one layer's
    weights alive at a time."""
    step = jax.jit(lambda w, x: layer(model, w, x, quant))
    embed = get(["embed"])["embed"]
    xs = [jnp.take(embed, ids, axis=0) for ids, _, _ in blocks]
    del embed
    for i in range(model["num_hidden_layers"]):
        w = _short(model, i, get(layer_names(model, i)))
        xs = [step(w, x) for x in xs]
    tail = get(["final_norm", "head"])
    return [mm(norm(x[rows, cols], tail["final_norm"],
                    model["rms_norm_eps"]), tail["head"], quant)
            for x, (_, rows, cols) in zip(xs, blocks)]


def loss_and_grads(model, leaves, ids, labels, quant=None, rows_per_block=1):
    """Mean next-token cross entropy over the whole batch and its gradient,
    accumulated over blocks of rows (no auxiliary term: the router's balance
    loss is left out). No cell trains this family."""
    n, s = ids.shape
    one = jax.checkpoint(lambda w, x: layer(model, w, x, quant))

    def block_loss(lv, x, y):
        h = jnp.take(lv["embed"], x, axis=0)
        for i in range(model["num_hidden_layers"]):
            h = one(_short(model, i, lv), h)
        h = norm(h, lv["final_norm"], model["rms_norm_eps"])
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
