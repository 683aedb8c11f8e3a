"""The family of hybrid decoders whose blocks are ``x + Mixer(RMSNorm(x))``
with the mixer one of a Mamba-2 layer, GQA attention without a position
embedding, or sigmoid-routed non-gated (squared-ReLU) experts beside a shared
one (``model_type: nemotron_h``, under the keys
NVIDIA-Nemotron-3-Nano-30B-A3B publishes: ``hybrid_override_pattern``,
``mamba_num_heads``, ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``,
``conv_kernel``, ``n_routed_experts``, ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``).

A configuration may hold a SHARE of the model: the first
``num_hidden_layers`` characters of the pattern, ``n_routed_experts`` experts
from ``first_expert_held`` on behind a router that keeps ``router_width``
outputs, ``vocab_size`` ids. Every count below is of the share held.

Canonical leaves (matrices [in, out]; H heads of P, I = H P, G groups of state
size N, C = I + 2 G N, K taps; Hq / Hkv heads of d; E experts held of width F,
R = router_width, S the shared expert's width):

    embed [V, hidden]   head [hidden, V]   final_norm [hidden]
    layers.<i>.norm [hidden]                      every block
    M: layers.<i>.in_proj [hidden, I + C + H]     columns [z | xBC | dt]
       layers.<i>.conv [K, C] (tap K - 1: the token itself)   .conv_bias [C]
       layers.<i>.dt_bias [H]   .A_log [H]   .D [H]   .gate_norm [I]
       layers.<i>.out_proj [I, hidden]
    *: layers.<i>.qkv [hidden, (Hq + 2 Hkv) d]    columns [q | k | v]
       layers.<i>.o [Hq d, hidden]
    E: layers.<i>.router [hidden, R]   .router_bias [R]       (float32)
       layers.<i>.experts_up [E, hidden, F]   .experts_down [E, F, hidden]
       layers.<i>.shared_up [hidden, S]       .shared_down [S, hidden]

Kinds (``weights.py``: "norm" ones, "router" float32 normal(0, 0.02), "matrix"
normal(0, 0.02) in the configuration's dtype) are chosen so that every term is
a visible share of what it feeds: the convolution's taps, ``D`` and the norms
are 1 (each of the last K tokens and the skip enter at full weight); the
convolution's bias, ``dt_bias``, ``A_log``, the router and its selection bias
are float32 normal, so the step is softplus(N(0, ~1)) ~ 0.8 and A ~ -1: a
state that forgets half of itself a token and still carries tens of tokens at
a visible weight; projections and experts are matrices.

The reference is ``refs/nemotron_h.py``. Required work, below, is what a
serving deployment moves: a decode tick reads every weight but the embedding
once (its gather is a few rows), the held experts as far as the tick's rows
are expected to hit them, K and V rows of every live token in the attention
layers, and READS AND WRITES every slot's recurrent state in the Mamba layers.
"""

from __future__ import annotations

from ..refs.nemotron_h import (kinds, logits_at, loss0_expected,  # noqa: F401
                               loss_and_grads, mamba_dims)


def leaf_shapes(model: dict) -> dict:
    d, v = model["hidden_size"], model["vocab_size"]
    h, _, _, _, inner, conv = mamba_dims(model)
    hd = model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    e, r, f = (model["n_routed_experts"], model["router_width"],
               model["moe_intermediate_size"])
    s = model["n_shared_experts"] * model["moe_shared_expert_intermediate_size"]
    out = {"embed": ((v, d), "matrix"), "head": ((d, v), "matrix"),
           "final_norm": ((d,), "norm")}
    for i, kind in enumerate(kinds(model)):
        p = f"layers.{i}."
        out[p + "norm"] = ((d,), "norm")
        if kind == "M":
            out.update({
                p + "in_proj": ((d, inner + conv + h), "matrix"),
                p + "conv": ((model["conv_kernel"], conv), "norm"),
                p + "conv_bias": ((conv,), "router"),
                p + "dt_bias": ((h,), "router"),
                p + "A_log": ((h,), "router"),
                p + "D": ((h,), "norm"),
                p + "gate_norm": ((inner,), "norm"),
                p + "out_proj": ((inner, d), "matrix")})
        elif kind == "*":
            out.update({
                p + "qkv": ((d, (n_q + 2 * n_kv) * hd), "matrix"),
                p + "o": ((n_q * hd, d), "matrix")})
        else:
            out.update({
                p + "router": ((d, r), "router"),
                p + "router_bias": ((r,), "router"),
                p + "experts_up": ((e, d, f), "matrix"),
                p + "experts_down": ((e, f, d), "matrix"),
                p + "shared_up": ((d, s), "matrix"),
                p + "shared_down": ((s, d), "matrix")})
    return out


# -- required work ------------------------------------------------------------

def mamba_matrix_params(model) -> int:
    """in_proj and out_proj of one Mamba-2 layer."""
    h, _, _, _, inner, conv = mamba_dims(model)
    return model["hidden_size"] * (inner + conv + h) + inner * model["hidden_size"]


def mamba_small_params(model) -> int:
    """The float32 vectors of one Mamba-2 layer: the taps and their bias,
    dt_bias, A_log, D, the gated norm, the block's norm."""
    h, _, _, _, inner, conv = mamba_dims(model)
    return ((model["conv_kernel"] + 1) * conv + 3 * h + inner
            + model["hidden_size"])


def attention_params(model) -> int:
    hd = model["head_dim"]
    return model["hidden_size"] * hd * (
        2 * model["num_attention_heads"] + 2 * model["num_key_value_heads"])


def expert_params(model) -> int:
    """One routed expert: up and down, no gate."""
    return 2 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_params(model) -> int:
    return (2 * model["hidden_size"] * model["n_shared_experts"]
            * model["moe_shared_expert_intermediate_size"])


def router_params(model) -> int:
    return (model["hidden_size"] + 1) * model["router_width"]


def layer_params(model, kind: str) -> int:
    d = model["hidden_size"]
    if kind == "M":
        return mamba_matrix_params(model) + mamba_small_params(model)
    if kind == "*":
        return attention_params(model) + d
    return (model["n_routed_experts"] * expert_params(model)
            + shared_params(model) + router_params(model) + d)


def param_count(model) -> int:
    """Every parameter of the share held, norms included."""
    d = model["hidden_size"]
    return (2 * model["vocab_size"] * d + d
            + sum(layer_params(model, k) for k in kinds(model)))


def experts_hit(model, rows: int) -> float:
    """Held experts that ``rows`` tokens choosing top-k of the router's
    whole width at random are expected to reach: E (1 - (1 - k / R)^rows)."""
    k, r = model["num_experts_per_tok"], model["router_width"]
    return model["n_routed_experts"] * (1.0 - (1.0 - k / r) ** rows)


def weight_bytes(model, rows: int | None = None, itemsize: int = 2) -> float:
    """Bytes of weights a decode tick of ``rows`` rows reads: the head once
    (the embedding is a gather of a few rows), every block's matrices in
    ``itemsize`` and its vectors and router in float32, the held experts as
    far as the rows are expected to hit them (``rows`` None: all)."""
    d = model["hidden_size"]
    hit = (model["n_routed_experts"] if rows is None
           else experts_hit(model, rows))
    per = {"M": itemsize * mamba_matrix_params(model)
           + 4 * mamba_small_params(model),
           "*": itemsize * attention_params(model) + 4 * d,
           "E": itemsize * (hit * expert_params(model) + shared_params(model))
           + 4 * (router_params(model) + d)}
    return (itemsize * d * model["vocab_size"] + 4 * d
            + sum(per[k] for k in kinds(model)))


def kv_bytes_per_token(model, itemsize: int = 2) -> int:
    """K and V rows of Hkv heads of d, in the ATTENTION layers alone."""
    return (kinds(model).count("*") * 2 * model["num_key_value_heads"]
            * model["head_dim"] * itemsize)


def slot_state_bytes(model, itemsize: int = 2) -> int:
    """A slot's recurrent state, one Mamba-2 layer: the recurrence's state
    [H, P, N] in float32 (whatever the configuration's dtype: the model
    card's serving command asks for a float32 SSM cache) and the
    convolution's last K - 1 inputs [K - 1, C] in ``itemsize``."""
    h, p, _, n, _, conv = mamba_dims(model)
    return 4 * h * p * n + itemsize * (model["conv_kernel"] - 1) * conv


def decode_tick_bytes(model, live_tokens: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode tick of ``engine.max_batch`` rows:
    the weights (``weight_bytes``), K and V of every live token in the
    attention layers, and every slot's state read and written in every
    Mamba-2 layer."""
    rows = model["engine"]["max_batch"]
    return (weight_bytes(model, rows, itemsize)
            + live_tokens * kv_bytes_per_token(model, itemsize)
            + 2 * rows * kinds(model).count("M")
            * slot_state_bytes(model, itemsize))


def ssm_state_update(model, shapes, itemsize: int = 2) -> dict:
    """The decode tick's state update of every Mamba-2 layer, for ONE run of
    the tick program (``ops.pallas.ssm.ssm_state_update``), all
    ``engine.max_batch`` slots (a slot between requests is updated like
    another: the bytes follow the slots, not the live tokens). FLOPs a slot
    a head: P N each for the decay's product, the outer product with B, its
    sum into the state, and 2 P N for the reading against C: 5 P N. Bytes:
    the float32 state once each way, and beside it x, the step and the
    reading y a head ([P] each, float32) and B and C a group."""
    h, p, g, n, _, _ = mamba_dims(model)
    slots, layers = model["engine"]["max_batch"], kinds(model).count("M")
    state = 4 * h * p * n
    beside = 4 * (3 * h * p + 2 * g * n)
    return {"fwd": {"flops": layers * slots * 5 * h * p * n,
                    "bytes": layers * slots * (2 * state + beside)}}


def train_flops_per_token(model, seq_len: int) -> float:
    """Required FLOPs to train on one token, forward and backward: 6 per
    weight the token is multiplied with (the head, the Mamba-2 and
    attention projections, the router, ``num_experts_per_tok`` experts as
    far as this share holds them, the shared expert), plus 6 x 5 P N a head
    a Mamba-2 layer for the recurrence and causal attention over Hq heads of
    d: 6 x Hq x d x (s + 1) an attention layer. No cell trains this family."""
    h, p, _, n, _, _ = mamba_dims(model)
    held = model["n_routed_experts"] / model["router_width"]
    per = {"M": mamba_matrix_params(model) + 5 * h * p * n,
           "*": attention_params(model)
           + model["num_attention_heads"] * model["head_dim"] * (seq_len + 1),
           "E": (model["hidden_size"] * model["router_width"]
                 + held * model["num_experts_per_tok"] * expert_params(model)
                 + shared_params(model))}
    return 6.0 * (model["hidden_size"] * model["vocab_size"]
                  + sum(per[k] for k in kinds(model)))
