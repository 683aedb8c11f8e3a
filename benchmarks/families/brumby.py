"""The family of decoders whose every layer is a power-retention mixer of
degree 2 followed by a dense gated MLP (``model_type: brumby``, under the keys
Brumby-14B-Base publishes: Qwen3's, ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``intermediate_size``, ``rope_theta``,
``tie_word_embeddings`` false). The model is attention-free: no layer keeps a
page. A configuration holds the first ``num_hidden_layers`` layers (a
pipeline stage) with the whole table and the whole head.

Canonical leaves, named by BLOCK (the program builds a layer as two residual
blocks, its mixer's ``layers.<2i>`` and its MLP's ``layers.<2i + 1>``;
matrices [in, out]; Hq / Hkv heads of d; F the MLP's width):

    embed [V, hidden]   final_norm [hidden]   head [hidden, V]
    layers.<j>.norm [hidden]                      every block
    p: layers.<j>.qkv [hidden, (Hq + 2 Hkv) d]    columns [q | k | v]
       layers.<j>.gate [hidden, Hkv]              float32, as a router's
       layers.<j>.q_norm [d]   .k_norm [d]        the per-head RMSNorms
       layers.<j>.o [Hq d, hidden]
    -: layers.<j>.gate_up [hidden, 2 F]           columns [gate | up]
       layers.<j>.down [F, hidden]

Kinds (``weights.py``: "norm" ones, "router" float32 normal(0, 0.02), "matrix"
normal(0, 0.02) in the configuration's dtype). The gate's input is a unit-RMS
vector through N(0, 0.02) columns of 5,120, so its logit is ~N(0, 1.4) and a
state forgets about half of itself a token (a trained model's forgets far
more slowly; the bytes, the operations and the in-place rule are the same).

The reference is ``refs/brumby.py``, the QUADRATIC form. Required work, below,
is what a serving deployment moves: a decode tick reads every layer's weights
once and the head once (the table's gather is a few rows), and READS AND
WRITES every slot's state, D = d (d + 1) / 2 = 8,256 rows of d and one of
normaliser a KV head: the count is the mathematics', so the 8,320 rows the
program's layout holds show as a lower roofline share, not as more required
bytes.
"""

from __future__ import annotations

from ..refs.brumby import (layer_names, logits_at,  # noqa: F401
                           loss0_expected, loss_and_grads, pattern)


def leaf_shapes(model: dict) -> dict:
    d, v, f = (model["hidden_size"], model["vocab_size"],
               model["intermediate_size"])
    hd = model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    out = {"embed": ((v, d), "matrix"), "final_norm": ((d,), "norm"),
           "head": ((d, v), "matrix")}
    for j, kind in enumerate(pattern(model)):
        p = f"layers.{j}."
        out[p + "norm"] = ((d,), "norm")
        if kind == "p":
            out.update({
                p + "qkv": ((d, (n_q + 2 * n_kv) * hd), "matrix"),
                p + "gate": ((d, n_kv), "router"),
                p + "q_norm": ((hd,), "norm"),
                p + "k_norm": ((hd,), "norm"),
                p + "o": ((n_q * hd, d), "matrix")})
        else:
            out.update({p + "gate_up": ((d, 2 * f), "matrix"),
                        p + "down": ((f, d), "matrix")})
    return out


# -- required work ------------------------------------------------------------

def retention_matrix_params(model) -> int:
    """q, k, v and o of one layer."""
    return model["hidden_size"] * model["head_dim"] * (
        2 * model["num_attention_heads"] + 2 * model["num_key_value_heads"])


def retention_small_params(model) -> int:
    """The float32 leaves of one mixer: the gate's projection and the two
    head norms."""
    return (model["hidden_size"] * model["num_key_value_heads"]
            + 2 * model["head_dim"])


def mlp_params(model) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def layer_params(model) -> int:
    """One published layer: its mixer, its MLP and its two norms."""
    return (retention_matrix_params(model) + retention_small_params(model)
            + mlp_params(model) + 2 * model["hidden_size"])


def param_count(model) -> int:
    """Every parameter HELD: the layers, the table, the head, the final
    norm."""
    d = model["hidden_size"]
    return (2 * model["vocab_size"] * d + d
            + model["num_hidden_layers"] * layer_params(model))


def weight_bytes(model, itemsize: int = 2) -> int:
    """Bytes of weights a decode tick reads: every layer's matrices and the
    head once in ``itemsize`` (the table's gather is a few rows), every
    vector and the gate's projection in float32."""
    d = model["hidden_size"]
    per = (itemsize * (retention_matrix_params(model) + mlp_params(model))
           + 4 * (retention_small_params(model) + 2 * d))
    return (itemsize * d * model["vocab_size"] + 4 * d
            + model["num_hidden_layers"] * per)


def state_rows(model) -> int:
    """D = d (d + 1) / 2: the distinct products of pairs of d numbers."""
    return model["head_dim"] * (model["head_dim"] + 1) // 2


def slot_state_bytes(model) -> int:
    """A slot's state, one layer: Hkv x (D x d + D) float32, whatever the
    configuration's dtype."""
    return 4 * model["num_key_value_heads"] * state_rows(model) * (
        model["head_dim"] + 1)


def decode_tick_bytes(model, live_tokens: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode tick of ``engine.max_batch`` rows:
    the weights once and every slot's state read and written in every
    layer. No term follows the live tokens: nothing is kept a token."""
    rows = model["engine"]["max_batch"]
    return (weight_bytes(model, itemsize)
            + 2 * rows * model["num_hidden_layers"] * slot_state_bytes(model))


def power_state_update(model, shapes, itemsize: int = 2) -> dict:
    """The decode tick's state update of every layer, for ONE run of the
    tick program (``ops.pallas.power_retention.power_state_update``), all
    ``engine.max_batch`` slots (a slot between requests is updated like
    another). Operations a slot a KV head: on each of the D x d elements of
    the state the decay, the rank-1 term's product and sum and 2 for each
    of the R readings (3 + 2 R), the same on the D of the normaliser, and 2
    D to form ``phi`` of k and of each q. Bytes: the float32 state and
    normaliser once each way; beside them q, k, v in ``itemsize``, the gate
    and the reading in float32."""
    hd, hkv = model["head_dim"], model["num_key_value_heads"]
    group = model["num_attention_heads"] // hkv
    slots, layers = model["engine"]["max_batch"], model["num_hidden_layers"]
    rows = state_rows(model)
    flops = hkv * ((3 + 2 * group) * rows * (hd + 1) + 2 * (group + 1) * rows)
    beside = hkv * ((group + 2) * hd * itemsize + 4 + 4 * group * hd)
    return {"fwd": {"flops": layers * slots * flops,
                    "bytes": layers * slots * (2 * slot_state_bytes(model)
                                               + beside)}}


def power_retention_chunked(model, shapes, itemsize: int = 2) -> dict:
    """A prompt's chunked retention through every layer, for ONE run of a
    prefill program of ``shapes["prompt_tokens"]`` positions (its bucket;
    4,096, the cell's widest, where none is given), in chunks of
    ``shapes["chunk"]`` (128). Operations a position a KV head: the R
    readings against the carried state and normaliser and the position's
    share of the state built (2 (R + 1) D (d + 1)), ``phi`` of k and of
    each q (2 (R + 1) D), and inside its chunk R rows of scores, their
    squares under the decay and the weighted values (R (4 d + 3) chunk:
    counted whole, a kernel computes the masked half too). Bytes a
    position: q, k, v in ``itemsize``, the gate, the reading in float32; a
    layer: the state once out (it lives in VMEM between chunks: that is
    the kernel). Bound by the MXU."""
    hd, hkv = model["head_dim"], model["num_key_value_heads"]
    group = model["num_attention_heads"] // hkv
    length, chunk = shapes.get("prompt_tokens", 4096), shapes.get("chunk", 128)
    layers, rows = model["num_hidden_layers"], state_rows(model)
    flops = hkv * (2 * (group + 1) * rows * (hd + 1) + 2 * (group + 1) * rows
                   + group * (4 * hd + 3) * chunk)
    beside = hkv * ((group + 2) * hd * itemsize + 4 + 4 * group * hd)
    return {"fwd": {"flops": layers * length * flops,
                    "bytes": layers * (length * beside
                                       + slot_state_bytes(model))}}


def train_flops_per_token(model, seq_len: int) -> float:
    """Required FLOPs to train on one token, forward and backward: 6 per
    weight the token is multiplied with (the head, every projection and MLP)
    plus 3 times the chunked form's operations a position a layer. No cell
    trains this family."""
    chunked = power_retention_chunked(
        model, {"prompt_tokens": 1})["fwd"]["flops"] / model["num_hidden_layers"]
    per = retention_matrix_params(model) + mlp_params(model)
    return (6.0 * (model["hidden_size"] * model["vocab_size"]
                   + model["num_hidden_layers"] * per)
            + 3.0 * model["num_hidden_layers"] * chunked)
