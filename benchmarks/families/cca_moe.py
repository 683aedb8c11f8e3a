"""The family of decoders whose every layer is compressed convolutional
attention (CCA) and top-1 routed experts behind an MLP router with a state
handed down the layers, residual merges scaled per channel, the embedding
tied to the head (ZAYA1's layout, under the keys ZAYA1-8B publishes:
``cca_time0``, ``cca_time1``, ``head_dim``, ``num_experts``,
``router_hidden_size``, ``partial_rotary_factor``).

Canonical leaves (matrices [in, out]; Hq / Hkv heads of d; Cq = Hq d,
Ck = Hkv d, C = Cq + Ck; R = router_hidden_size; E experts of width F):

    embed [V, H] (also the head, transposed)      final_norm [H]
    layers.<i>.attn_norm [H]        layers.<i>.mlp_norm [H]
    layers.<i>.q_down [H, Cq]   .k_down [H, Ck]   .v_down [H, Ck] ([Wv1 | Wv2])
    layers.<i>.conv0 [2, C] (taps: previous, current)   .conv0_bias [C]
    layers.<i>.conv1 [2, Hq + Hkv, d, d] (tap, head, in, out)  .conv1_bias [C]
    layers.<i>.temp [Hkv]           layers.<i>.o [Cq, H]
    layers.<i>.{attn,mlp}_res_scale / _out_scale / _out_bias [H]
    layers.<i>.router_down [H, R]   .router_down_bias [R]   .router_gate [R]
    layers.<i>.router_norm [R]      .router_w1 / _w2 [R, R]  .router_b1 / _b2 [R]
    layers.<i>.router_w3 [R, E + 1] (the last output is the skip choice)
    layers.<i>.experts_gate_up [E, H, 2 F]        .experts_down [E, F, H]

Kinds (``weights.py``: "norm" ones, "router" float32 normal(0, 0.02), "matrix"
normal(0, 0.02) in the configuration's dtype) are chosen so that every term
is a visible share of what it feeds: the depthwise taps, ``temp``, the
residual scales and ``router_gate`` are 1 (the previous token, the previous
layer's state and both branches of a merge enter at full weight), biases and
the router's matrices are float32 normal, projections and the block
convolution matrices. ``router_gate`` of layer 0 multiplies nothing.

The reference is ``refs/cca_moe.py``. Required work, below, is what a serving
deployment moves: a decode tick reads every weight once (the tied matrix as
the head; the embedding's gather is a few rows), the experts as far as the
tick's rows are expected to hit them, K and V rows of every live token, and
reads and writes the per-slot conv/shift state.
"""

from __future__ import annotations

from ..refs.cca_moe import (logits_at, loss0_expected,  # noqa: F401
                            loss_and_grads)


def _dims(model):
    d = model["head_dim"]
    return (model["hidden_size"], model["num_attention_heads"] * d,
            model["num_key_value_heads"] * d, d)


def leaf_shapes(model: dict) -> dict:
    if (model["cca_time0"], model["cca_time1"]) != (2, 2):
        # CompressedConvAttention's convolutions have 2 taps: the state a
        # slot carries is one previous token's
        raise ValueError(f"the program runs convolutions of 2 taps; the "
                         f"configuration states cca_time0="
                         f"{model['cca_time0']}, cca_time1="
                         f"{model['cca_time1']}")
    h, cq, ck, d = _dims(model)
    heads = (cq + ck) // d
    r, e, f = (model["router_hidden_size"], model["num_experts"],
               model["moe_intermediate_size"])
    out = {"embed": ((model["vocab_size"], h), "matrix"),
           "final_norm": ((h,), "norm")}
    for i in range(model["num_hidden_layers"]):
        p = f"layers.{i}."
        out.update({
            p + "attn_norm": ((h,), "norm"), p + "mlp_norm": ((h,), "norm"),
            p + "q_down": ((h, cq), "matrix"),
            p + "k_down": ((h, ck), "matrix"),
            p + "v_down": ((h, ck), "matrix"),
            p + "conv0": ((model["cca_time0"], cq + ck), "norm"),
            p + "conv0_bias": ((cq + ck,), "router"),
            p + "conv1": ((model["cca_time1"], heads, d, d), "matrix"),
            p + "conv1_bias": ((cq + ck,), "router"),
            p + "temp": ((ck // d,), "norm"),
            p + "o": ((cq, h), "matrix"),
            p + "router_down": ((h, r), "router"),
            p + "router_down_bias": ((r,), "router"),
            p + "router_gate": ((r,), "norm"),
            p + "router_norm": ((r,), "norm"),
            p + "router_w1": ((r, r), "router"),
            p + "router_b1": ((r,), "router"),
            p + "router_w2": ((r, r), "router"),
            p + "router_b2": ((r,), "router"),
            p + "router_w3": ((r, e + 1), "router"),
            p + "experts_gate_up": ((e, h, 2 * f), "matrix"),
            p + "experts_down": ((e, f, h), "matrix")})
        for kind in ("attn", "mlp"):
            out.update({p + kind + "_res_scale": ((h,), "norm"),
                        p + kind + "_out_scale": ((h,), "norm"),
                        p + kind + "_out_bias": ((h,), "router")})
    return out


# -- required work ------------------------------------------------------------

def projection_params(model) -> int:
    """CCA's four projections: q, k and v down, and the one up."""
    h, cq, ck, _ = _dims(model)
    return h * (cq + 2 * ck) + cq * h


def conv_params(model) -> int:
    """The depthwise taps, the block-diagonal taps, their biases."""
    _, cq, ck, d = _dims(model)
    c = cq + ck
    return (model["cca_time0"] * c + c
            + model["cca_time1"] * (c // d) * d * d + c)


def router_params(model) -> int:
    h, r, e = (model["hidden_size"], model["router_hidden_size"],
               model["num_experts"])
    return h * r + r + r + r + 2 * (r * r + r) + r * (e + 1)


def expert_params(model) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def small_params(model) -> int:
    """A layer's float32 vectors outside the router: two norms, the
    temperature, three vectors for each of the two residual merges."""
    return 8 * model["hidden_size"] + model["num_key_value_heads"]


def layer_params(model) -> int:
    return (model["num_experts"] * expert_params(model)
            + projection_params(model) + conv_params(model)
            + router_params(model) + small_params(model))


def param_count(model) -> int:
    h = model["hidden_size"]
    return (model["vocab_size"] * h + h
            + model["num_hidden_layers"] * layer_params(model))


def experts_hit(model, rows: int) -> float:
    """Experts that ``rows`` tokens choosing top-k of E at random are
    expected to reach: E (1 - (1 - k/E)^rows)."""
    e, k = model["num_experts"], model["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def weight_bytes(model, rows: int | None = None, itemsize: int = 2) -> float:
    """Bytes of weights a decode tick of ``rows`` rows reads: the tied
    matrix once (as the head), every layer's projections and block taps in
    ``itemsize``, its router and vectors in float32, its experts as far as
    the rows are expected to hit them (``rows`` None: all)."""
    h = model["hidden_size"]
    _, cq, ck, d = _dims(model)
    c = cq + ck
    hit = model["num_experts"] if rows is None else experts_hit(model, rows)
    block_taps = model["cca_time1"] * (c // d) * d * d
    per_layer = (itemsize * (projection_params(model) + block_taps
                             + hit * expert_params(model))
                 + 4 * (conv_params(model) - block_taps
                        + router_params(model) + small_params(model)))
    return (itemsize * h * model["vocab_size"] + 4 * h
            + model["num_hidden_layers"] * per_layer)


def kv_bytes_per_token(model, itemsize: int = 2) -> int:
    """K and V rows of Hkv heads of d a layer, in the compressed space."""
    return (model["num_hidden_layers"] * 2 * model["num_key_value_heads"]
            * model["head_dim"] * itemsize)


def slot_state_bytes(model, itemsize: int = 2) -> int:
    """A slot's recurrent state, one layer: the last token's pre-conv row
    and its first convolution's output (C numbers each) and the half of
    its value projection the next token takes (Ck / 2)."""
    _, cq, ck, _ = _dims(model)
    return itemsize * (2 * (cq + ck) + ck // 2)


def decode_tick_bytes(model, live_tokens: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode tick of ``engine.max_batch`` rows:
    the weights (``weight_bytes``), K and V of every live token, and every
    slot's state read and written, in every layer."""
    rows = model["engine"]["max_batch"]
    return (weight_bytes(model, rows, itemsize)
            + live_tokens * kv_bytes_per_token(model, itemsize)
            + 2 * rows * model["num_hidden_layers"]
            * slot_state_bytes(model, itemsize))


def train_flops_per_token(model, seq_len: int) -> float:
    """Required FLOPs to train on one token, forward and backward: 6 per
    weight the token is multiplied with (the tied matrix as the head, the
    projections, the block taps, the router, ``num_experts_per_tok``
    experts), plus causal attention over Hq heads of d in the compressed
    space: 6 x Hq x d x (s + 1) a layer (QK^T and PV, half the square)."""
    h, cq, ck, d = _dims(model)
    per_layer = (projection_params(model)
                 + model["cca_time1"] * ((cq + ck) // d) * d * d
                 + router_params(model)
                 + model["num_experts_per_tok"] * expert_params(model))
    return (6.0 * (h * model["vocab_size"]
                   + model["num_hidden_layers"] * per_layer)
            + model["num_hidden_layers"] * 6.0 * cq * (seq_len + 1))
