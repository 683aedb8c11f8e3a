"""The family of hybrid decoders whose layers are a Gated DeltaNet mixer or a
gated-attention mixer, each followed by softmax-routed SwiGLU experts beside
a gated shared expert, every norm zero-centred (``model_type: qwen3_next``,
under the keys Qwen3-Next-80B-A3B-Instruct publishes:
``full_attention_interval``, ``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``partial_rotary_factor``, ``num_experts``,
``moe_intermediate_size``, ``shared_expert_intermediate_size``).

A configuration may hold a SHARE of the model: the first ``num_hidden_layers``
layers, ``num_experts`` experts from ``first_expert_held`` on behind a router
that keeps ``router_width`` outputs, ``vocab_size`` ids. Every count below is
of the share held.

Canonical leaves, named by BLOCK (the program builds a layer as two residual
blocks, its mixer's ``layers.<2i>`` and its experts' ``layers.<2i + 1>``;
matrices [in, out]; Hk key heads of K serving Hv value heads of V, C = 2 Hk K
+ Hv V convolved channels, T taps; Hq / Hkv heads of d; E experts held of
width F behind a router R wide, S the shared expert's width):

    embed [V, hidden]   head [hidden, V]   final_norm [hidden]
    layers.<j>.norm [hidden]                      every block
    d: layers.<j>.in_proj [hidden, C + Hv V + 2 Hv]   columns [q | k | v | z | b | a],
                                                  heads in order inside each
       layers.<j>.conv [T, C] (tap T - 1: the token itself; no bias)
       layers.<j>.dt_bias [Hv]   .A_log [Hv]   .gate_norm [V]
       layers.<j>.out_proj [Hv V, hidden]
    a: layers.<j>.qkv [hidden, 2 (Hq + Hkv) d]    columns [Hq x [q | gate] | k | v]
       layers.<j>.q_norm [d]   .k_norm [d]        layers.<j>.o [Hq d, hidden]
    e: layers.<j>.router [hidden, R]   .shared_gate [hidden, 1]      (float32)
       layers.<j>.experts_gate_up [E, hidden, 2 F]   .experts_down [E, F, hidden]
       layers.<j>.shared_gate_up [hidden, 2 S]       .shared_down [S, hidden]

(The published checkpoint stores the DeltaNet projection's columns grouped by
key head, and q, k and v projections of the attention apart; with seeded
weights the order is a name only.)

Kinds (``weights.py``: "norm" ones, "router" float32 normal(0, 0.02), "matrix"
normal(0, 0.02) in the configuration's dtype): every ZERO-CENTRED norm's ``w``
(the blocks', the final one, ``q_norm``, ``k_norm``) is float32 normal, so
``1 + w`` is visibly not 1; ``A_log`` and ``dt_bias`` too, so ``alpha =
exp(-exp(~0) softplus(~0)) ~ 0.5``: a state that forgets half of itself a
token (a trained model's forgets far more slowly; the bytes, the FLOPs and
the in-place rule are the same); the router and ``w_sg`` as every router;
the convolution's taps and the inner gated norm are 1.

The reference is ``refs/qwen3_next.py``. Required work, below, is what a
serving deployment moves: a decode tick reads every weight but the embedding
once (its gather is a few rows), the held experts as far as the tick's rows
are expected to hit them, K and V rows of every live token in the attention
layers, and READS AND WRITES every slot's delta-rule state and convolution
window in the DeltaNet layers.
"""

from __future__ import annotations

from ..refs.qwen3_next import (delta_dims, kinds, logits_at,  # noqa: F401
                               loss0_expected, loss_and_grads, pattern)


def _widths(model):
    """(key width Hk K, value width Hv V, convolved channels C)."""
    hk, hv, key, val, _ = delta_dims(model)
    return hk * key, hv * val, 2 * hk * key + hv * val


def leaf_shapes(model: dict) -> dict:
    d, v = model["hidden_size"], model["vocab_size"]
    _, hv, _, val, taps = delta_dims(model)
    _, vd, conv = _widths(model)
    hd = model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    e, r, f = (model["num_experts"], model["router_width"],
               model["moe_intermediate_size"])
    s = model["shared_expert_intermediate_size"]
    out = {"embed": ((v, d), "matrix"), "head": ((d, v), "matrix"),
           "final_norm": ((d,), "router")}
    for j, kind in enumerate(pattern(model)):
        p = f"layers.{j}."
        out[p + "norm"] = ((d,), "router")
        if kind == "d":
            out.update({
                p + "in_proj": ((d, conv + vd + 2 * hv), "matrix"),
                p + "conv": ((taps, conv), "norm"),
                p + "dt_bias": ((hv,), "router"),
                p + "A_log": ((hv,), "router"),
                p + "gate_norm": ((val,), "norm"),
                p + "out_proj": ((vd, d), "matrix")})
        elif kind == "a":
            out.update({
                p + "qkv": ((d, 2 * (n_q + n_kv) * hd), "matrix"),
                p + "q_norm": ((hd,), "router"),
                p + "k_norm": ((hd,), "router"),
                p + "o": ((n_q * hd, d), "matrix")})
        else:
            out.update({
                p + "router": ((d, r), "router"),
                p + "shared_gate": ((d, 1), "router"),
                p + "experts_gate_up": ((e, d, 2 * f), "matrix"),
                p + "experts_down": ((e, f, d), "matrix"),
                p + "shared_gate_up": ((d, 2 * s), "matrix"),
                p + "shared_down": ((s, d), "matrix")})
    return out


# -- required work ------------------------------------------------------------

def delta_matrix_params(model) -> int:
    """in_proj and out_proj of one Gated DeltaNet layer."""
    _, hv, _, _, _ = delta_dims(model)
    _, vd, conv = _widths(model)
    return model["hidden_size"] * (conv + vd + 2 * hv + vd)


def delta_small_params(model) -> int:
    """The float32 vectors of one DeltaNet block: the taps, dt_bias, A_log,
    the gated norm, the block's norm."""
    _, hv, _, val, taps = delta_dims(model)
    return taps * _widths(model)[2] + 2 * hv + val + model["hidden_size"]


def attention_matrix_params(model) -> int:
    hd = model["head_dim"]
    return model["hidden_size"] * hd * (
        3 * model["num_attention_heads"] + 2 * model["num_key_value_heads"])


def expert_params(model) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_params(model) -> int:
    return 3 * model["hidden_size"] * model["shared_expert_intermediate_size"]


def router_params(model) -> int:
    """The float32 leaves of one expert block: the router, the shared
    expert's gate, the block's norm."""
    return model["hidden_size"] * (model["router_width"] + 2)


def block_params(model, kind: str) -> int:
    if kind == "d":
        return delta_matrix_params(model) + delta_small_params(model)
    if kind == "a":
        return (attention_matrix_params(model) + 2 * model["head_dim"]
                + model["hidden_size"])
    return (model["num_experts"] * expert_params(model)
            + shared_params(model) + router_params(model))


def param_count(model) -> int:
    """Every parameter of the share held, norms included."""
    d = model["hidden_size"]
    return (2 * model["vocab_size"] * d + d
            + sum(block_params(model, k) for k in pattern(model)))


def experts_hit(model, rows: int) -> float:
    """Held experts that ``rows`` tokens choosing top-k of the router's
    whole width at random are expected to reach: E (1 - (1 - k / R)^rows)."""
    k, r = model["num_experts_per_tok"], model["router_width"]
    return model["num_experts"] * (1.0 - (1.0 - k / r) ** rows)


def weight_bytes(model, rows: int | None = None, itemsize: int = 2) -> float:
    """Bytes of weights a decode tick of ``rows`` rows reads: the head once
    (the embedding is a gather of a few rows), every block's matrices in
    ``itemsize`` and its vectors, router and gate in float32, the held
    experts as far as the rows are expected to hit them (``rows`` None:
    all)."""
    d = model["hidden_size"]
    hit = model["num_experts"] if rows is None else experts_hit(model, rows)
    per = {"d": itemsize * delta_matrix_params(model)
           + 4 * delta_small_params(model),
           "a": itemsize * attention_matrix_params(model)
           + 4 * (2 * model["head_dim"] + d),
           "e": itemsize * (hit * expert_params(model) + shared_params(model))
           + 4 * router_params(model)}
    return (itemsize * d * model["vocab_size"] + 4 * d
            + sum(per[k] for k in pattern(model)))


def kv_bytes_per_token(model, itemsize: int = 2) -> int:
    """K and V rows of Hkv heads of d, in the ATTENTION layers alone."""
    return (kinds(model).count("a") * 2 * model["num_key_value_heads"]
            * model["head_dim"] * itemsize)


def slot_state_bytes(model, itemsize: int = 2) -> int:
    """A slot's recurrent state, one DeltaNet layer: the delta rule's state
    [Hv, K, V] in float32 (whatever the configuration's dtype) and the
    convolution's last T - 1 inputs [T - 1, C] in ``itemsize``."""
    _, hv, key, val, taps = delta_dims(model)
    return 4 * hv * key * val + itemsize * (taps - 1) * _widths(model)[2]


def decode_tick_bytes(model, live_tokens: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode tick of ``engine.max_batch`` rows:
    the weights (``weight_bytes``), K and V of every live token in the
    attention layers, and every slot's state and window read and written in
    every DeltaNet layer."""
    rows = model["engine"]["max_batch"]
    return (weight_bytes(model, rows, itemsize)
            + live_tokens * kv_bytes_per_token(model, itemsize)
            + 2 * rows * kinds(model).count("d")
            * slot_state_bytes(model, itemsize))


def _beside_state(model, itemsize: int) -> int:
    """Bytes a position (or a tick's slot) moves beside the state, one
    DeltaNet layer: v in ``itemsize``, the reading in float32 and the two
    scalars a value head; k and q a KEY head in ``itemsize``."""
    hk, hv, key, val, _ = delta_dims(model)
    return hv * (val * (itemsize + 4) + 8) + 2 * hk * key * itemsize


def gated_delta_state_update(model, shapes, itemsize: int = 2) -> dict:
    """The decode tick's state update of every DeltaNet layer, for ONE run
    of the tick program (``ops.pallas.gated_delta.gated_delta_state_update``),
    all ``engine.max_batch`` slots (a slot between requests is updated like
    another: the bytes follow the slots, not the live tokens). FLOPs a slot
    a value head: K V each for the decay, and 2 K V each for the read-back
    against k, the rank-one term added, and the reading against q: 7 K V.
    Bytes: the float32 state once each way, and beside it v in ``itemsize``,
    the reading in float32, the head's two scalars, and k and q a KEY head
    in ``itemsize``."""
    _, hv, key, val, _ = delta_dims(model)
    slots, layers = model["engine"]["max_batch"], kinds(model).count("d")
    state = 4 * hv * key * val
    beside = _beside_state(model, itemsize)
    return {"fwd": {"flops": layers * slots * 7 * hv * key * val,
                    "bytes": layers * slots * (2 * state + beside)}}


def gated_delta_chunked(model, shapes, itemsize: int = 2) -> dict:
    """A prompt's chunked delta rule through every DeltaNet layer, for ONE
    run of a prefill program of ``shapes["prompt_tokens"]`` positions (its
    bucket; 1,024, the cell's widest, where none is given), in chunks of
    ``shapes["chunk"]`` (64). FLOPs a position a value head, products
    counted whole (a triangle's masked half too): the chunk's K K^T and Q
    K^T (4 C K), the unit lower-triangular system solved for the K + V
    columns of W and U (C (K + V): a substitution touches the triangle
    alone), W S, Q S and the state built (6 K V), the scores times V' (2 C
    V). Bytes a position: q, k a key head and v in ``itemsize``, the two
    scalars, the reading in float32; a layer: the state once out (it stays
    on the chip between chunks in a fused form). Near the ridge: at 1,024
    positions the bytes take 0.39 ms and the products 0.25 at the MXU's
    bfloat16 peak; in float32 at the highest precision (six passes) the
    products take longer."""
    _, hv, key, val, _ = delta_dims(model)
    length, chunk = shapes.get("prompt_tokens", 1024), shapes.get("chunk", 64)
    layers = kinds(model).count("d")
    flops = hv * (4 * chunk * key + chunk * (key + val) + 6 * key * val
                  + 2 * chunk * val)
    beside = _beside_state(model, itemsize)
    return {"fwd": {"flops": layers * length * flops,
                    "bytes": layers * (length * beside
                                       + 4 * hv * key * val)}}


def train_flops_per_token(model, seq_len: int) -> float:
    """Required FLOPs to train on one token, forward and backward: 6 per
    weight the token is multiplied with (the head, the mixers' projections,
    the router and the shared expert's gate, ``num_experts_per_tok`` experts
    as far as this share holds them, the shared expert), plus 3 times the
    chunked form's operations a position a DeltaNet layer and causal
    attention over Hq heads of d: 6 x Hq x d x (s + 1) an attention layer.
    No cell trains this family."""
    chunked = (gated_delta_chunked(model, {"prompt_tokens": 1})["fwd"]["flops"]
               / max(kinds(model).count("d"), 1))
    held = model["num_experts"] / model["router_width"]
    per = {"d": delta_matrix_params(model) + chunked / 2,
           "a": attention_matrix_params(model)
           + model["num_attention_heads"] * model["head_dim"] * (seq_len + 1),
           "e": (model["hidden_size"] * (model["router_width"] + 1)
                 + held * model["num_experts_per_tok"] * expert_params(model)
                 + shared_params(model))}
    return 6.0 * (model["hidden_size"] * model["vocab_size"]
                  + sum(per[k] for k in pattern(model)))
