"""The family of hybrid decoders whose layers are a Mamba-1 mixer or an
attention mixer without a position embedding, each followed by a dense gated
MLP (``model_type: jamba`` with ``num_experts: 1``, under the keys
AI21-Jamba2-3B publishes: ``attn_layer_period``, ``attn_layer_offset``,
``mamba_d_state``, ``mamba_dt_rank``, ``mamba_expand``, ``mamba_d_conv``,
``intermediate_size``, ``tie_word_embeddings``). Nothing is cut: every count
below is of the whole model.

Canonical leaves, named by BLOCK (the program builds a layer as two residual
blocks, its mixer's ``layers.<2i>`` and its MLP's ``layers.<2i + 1>``;
matrices [in, out]; I = expand x hidden channels, N the state size, R the
step's rank, K taps; Hq / Hkv heads of d; F the MLP's width):

    embed [V, hidden]   final_norm [hidden]       no head: the embedding's transpose
    layers.<j>.norm [hidden]                      every block
    m: layers.<j>.in_proj [hidden, 2 I]           columns [x | z]
       layers.<j>.conv [K, I] (tap K - 1: the token itself)   .conv_bias [I]
       layers.<j>.x_proj [I, R + 2 N]             columns [dt | B | C]
       layers.<j>.dt_norm [R]   .b_norm [N]   .c_norm [N]
       layers.<j>.dt_proj [R, I]   .dt_bias [I]
       layers.<j>.A_log [N, I] (the published [I, N], transposed: the layout of
       the state it decays)   .D [I]   layers.<j>.out_proj [I, hidden]
    *: layers.<j>.qkv [hidden, (Hq + 2 Hkv) d]    columns [q | k | v]
       layers.<j>.o [Hq d, hidden]
    -: layers.<j>.gate_up [hidden, 2 F]           columns [gate | up]
       layers.<j>.down [F, hidden]

Kinds (``weights.py``: "norm" ones, "router" float32 normal(0, 0.02), "matrix"
normal(0, 0.02) in the configuration's dtype), as Nemotron's family chose
them: the convolution's taps, ``D`` and every norm are 1; the convolution's
bias, ``dt_bias`` and ``A_log`` are float32 normal, so A ~ -1 and, the step's
input being a unit-RMS vector through N(0, 0.02) columns of rank R, Delta =
softplus(N(0, ~0.25)) ~ 0.7: a state that forgets half of itself a token (a
trained model's forgets far more slowly; the bytes, the exponentials and the
in-place rule are the same).

The reference is ``refs/jamba.py``. Required work, below, is what a serving
deployment moves: a decode tick reads every weight once (the embedding AS the
head; its gather is a few rows more), K and V rows of every live token in the
attention layers, and READS AND WRITES every slot's recurrent state and
convolution window in the Mamba layers.
"""

from __future__ import annotations

from ..refs.jamba import (kinds, logits_at, loss0_expected,  # noqa: F401
                          loss_and_grads, mamba_dims, pattern)


def leaf_shapes(model: dict) -> dict:
    d, v, f = (model["hidden_size"], model["vocab_size"],
               model["intermediate_size"])
    inner, n, rank, k = mamba_dims(model)
    hd = model["head_dim"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    out = {"embed": ((v, d), "matrix"), "final_norm": ((d,), "norm")}
    for j, kind in enumerate(pattern(model)):
        p = f"layers.{j}."
        out[p + "norm"] = ((d,), "norm")
        if kind == "m":
            out.update({
                p + "in_proj": ((d, 2 * inner), "matrix"),
                p + "conv": ((k, inner), "norm"),
                p + "conv_bias": ((inner,), "router"),
                p + "x_proj": ((inner, rank + 2 * n), "matrix"),
                p + "dt_norm": ((rank,), "norm"),
                p + "b_norm": ((n,), "norm"),
                p + "c_norm": ((n,), "norm"),
                p + "dt_proj": ((rank, inner), "matrix"),
                p + "dt_bias": ((inner,), "router"),
                p + "A_log": ((n, inner), "router"),
                p + "D": ((inner,), "norm"),
                p + "out_proj": ((inner, d), "matrix")})
        elif kind == "*":
            out.update({
                p + "qkv": ((d, (n_q + 2 * n_kv) * hd), "matrix"),
                p + "o": ((n_q * hd, d), "matrix")})
        else:
            out.update({p + "gate_up": ((d, 2 * f), "matrix"),
                        p + "down": ((f, d), "matrix")})
    return out


# -- required work ------------------------------------------------------------

def mamba_matrix_params(model) -> int:
    """in_proj, x_proj, dt_proj and out_proj of one Mamba layer."""
    inner, n, rank, _ = mamba_dims(model)
    return (3 * model["hidden_size"] * inner + inner * (rank + 2 * n)
            + rank * inner)


def mamba_small_params(model) -> int:
    """The float32 leaves of one Mamba mixer: the taps and their bias,
    dt_bias, A_log, D and the three inner norms."""
    inner, n, rank, k = mamba_dims(model)
    return (k + 3) * inner + n * inner + rank + 2 * n


def attention_params(model) -> int:
    return model["hidden_size"] * model["head_dim"] * (
        2 * model["num_attention_heads"] + 2 * model["num_key_value_heads"])


def mlp_params(model) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def layer_params(model, kind: str) -> int:
    """One published layer: its mixer, its MLP and its two norms."""
    mixer = (mamba_matrix_params(model) + mamba_small_params(model)
             if kind == "m" else attention_params(model))
    return mixer + mlp_params(model) + 2 * model["hidden_size"]


def param_count(model) -> int:
    """Every parameter, norms included; the tied table once."""
    d = model["hidden_size"]
    return (model["vocab_size"] * d + d
            + sum(layer_params(model, k) for k in kinds(model)))


def weight_bytes(model, itemsize: int = 2) -> int:
    """Bytes of weights a decode tick reads: every matrix once in
    ``itemsize`` (the embedding as the head), every vector in float32."""
    d = model["hidden_size"]
    per = {"m": itemsize * mamba_matrix_params(model)
           + 4 * mamba_small_params(model),
           "*": itemsize * attention_params(model)}
    return (itemsize * d * model["vocab_size"] + 4 * d
            + sum(per[k] + itemsize * mlp_params(model) + 8 * d
                  for k in kinds(model)))


def kv_bytes_per_token(model, itemsize: int = 2) -> int:
    """K and V rows of Hkv heads of d, in the ATTENTION layers alone."""
    return (kinds(model).count("*") * 2 * model["num_key_value_heads"]
            * model["head_dim"] * itemsize)


def slot_state_bytes(model, itemsize: int = 2) -> int:
    """A slot's recurrent state, one Mamba layer: the recurrence's state
    [N, I] in float32 (whatever the configuration's dtype) and the
    convolution's last K - 1 inputs [K - 1, I] in ``itemsize``."""
    inner, n, _, k = mamba_dims(model)
    return 4 * n * inner + itemsize * (k - 1) * inner


def decode_tick_bytes(model, live_tokens: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode tick of ``engine.max_batch`` rows:
    the weights once, K and V of every live token in the attention layers,
    and every slot's state and convolution window read and written in
    every Mamba layer."""
    rows = model["engine"]["max_batch"]
    return (weight_bytes(model, itemsize)
            + live_tokens * kv_bytes_per_token(model, itemsize)
            + 2 * rows * kinds(model).count("m")
            * slot_state_bytes(model, itemsize))


def selective_state_update(model, shapes, itemsize: int = 2) -> dict:
    """The decode tick's state update of every Mamba layer, for ONE run of
    the tick program (``ops.pallas.selective_ssm.selective_state_update``),
    all ``engine.max_batch`` slots (a slot between requests is updated like
    another). Operations a slot an element of the state [I, N]: the step
    times A, its exponential, the decay's product, the outer product with
    B, its sum into the state and 2 for the reading against C: 7 I N (one
    of them the exponential, which a Mamba-2 head pays once, not I N / H
    times). Bytes: the float32 state once each way; beside it the input
    in ``itemsize``, the step and the reading (float32 [I] each), B and C a
    slot; A once a layer."""
    inner, n, _, _ = mamba_dims(model)
    slots, layers = model["engine"]["max_batch"], kinds(model).count("m")
    state = 4 * inner * n
    beside = (itemsize + 8) * inner + 8 * n
    return {"fwd": {"flops": layers * slots * 7 * inner * n,
                    "bytes": layers * (slots * (2 * state + beside)
                                       + state)}}


def selective_scan(model, shapes, itemsize: int = 2) -> dict:
    """A prompt's scan through every Mamba layer, for ONE run of a prefill
    program of ``shapes["prompt_tokens"]`` positions (its bucket; 1,024,
    the cell's widest, where none is given): 7 I N operations a position as
    the tick's; bytes a position: the input in ``itemsize``, the step and
    the reading in float32 [I] each, B and C; a layer: A once and the state
    once out (it lives in VMEM between positions: that is the kernel)."""
    inner, n, _, _ = mamba_dims(model)
    length, layers = shapes.get("prompt_tokens", 1024), kinds(model).count("m")
    return {"fwd": {"flops": layers * length * 7 * inner * n,
                    "bytes": layers * (length * ((itemsize + 8) * inner
                                                 + 8 * n)
                                       + 8 * inner * n)}}


def train_flops_per_token(model, seq_len: int) -> float:
    """Required FLOPs to train on one token, forward and backward: 6 per
    weight the token is multiplied with (the tied table as the head, every
    projection and MLP), plus 6 x 7 I N / 2 a Mamba layer for the recurrence
    and causal attention over Hq heads of d: 6 x Hq x d x (s + 1) an
    attention layer. No cell trains this family."""
    inner, n, _, _ = mamba_dims(model)
    per = {"m": mamba_matrix_params(model) + 3.5 * inner * n,
           "*": attention_params(model)
           + model["num_attention_heads"] * model["head_dim"] * (seq_len + 1)}
    return 6.0 * (model["hidden_size"] * model["vocab_size"]
                  + sum(per[k] + mlp_params(model) for k in kinds(model)))
