"""Operations and bytes the algorithms NEED, worked out from shapes.

These are the numerators of every roofline share and of MFU. They count
what the mathematics requires and nothing an implementation adds:
recomputation, padding, a cache widened to float32 or a head repeated in
memory are the implementation's cost and show as a LOWER share. So no share
can pass 100% by a miscount here; tests/test_counts.py holds each function
to hand-worked numbers for the three configurations.

These are the counts of ONE family, ``families/gqa_decoder.py``, which is
the only module that imports this one: the harness reaches a count through
the configuration's family and never directly.

``model`` is a configuration's ``model`` group; ``shapes`` what a run's
traffic fixed (``rows_per_chip``, ``seq_len``).
"""

from __future__ import annotations


def _dims(model):
    h = model["hidden_size"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or h // n_q
    return h, n_q, n_kv, hd


def layer_matmul_params(model, active_only: bool = True) -> int:
    """Weights of one layer that a token is multiplied with: attention
    projections and the MLP; for a routed model the router and, with
    ``active_only``, only the experts a token is sent to."""
    h, n_q, n_kv, hd = _dims(model)
    attn = h * (n_q + 2 * n_kv) * hd + n_q * hd * h
    f = model["intermediate_size"]
    if model.get("num_experts"):
        e = model["num_experts_per_tok"] if active_only else model["num_experts"]
        return attn + h * model["num_experts"] + e * 3 * h * f
    return attn + 3 * h * f


def param_count(model) -> int:
    """All parameters, norms included."""
    h = model["hidden_size"]
    per_layer = layer_matmul_params(model, active_only=False) + 2 * h
    return (2 * model["vocab_size"] * h + h
            + model["num_hidden_layers"] * per_layer)


def train_flops_per_token(model, seq_len: int) -> float:
    """Required FLOPs to train on one token, forward and backward: 6 per
    weight a token is multiplied with (the output head included, the
    embedding gather not), plus CAUSAL attention: each of the four
    attention matmuls (QK^T and PV forward, and their two-each backward,
    6 in all) does 2 * hd * (s + 1) / 2 FLOPs per head and token on
    average, i.e. 6 * n_q * hd * (s + 1) per layer."""
    h, n_q, _, hd = _dims(model)
    layers = model["num_hidden_layers"]
    weights = layers * layer_matmul_params(model) + h * model["vocab_size"]
    return 6.0 * weights + layers * 6.0 * n_q * hd * (seq_len + 1)


def flash_flops(model, batch: int, seq_len: int) -> dict:
    """Causal attention of ONE layer over ``batch`` rows. Forward is the two
    matmuls QK^T and PV over the lower triangle, diagonal included.
    Backward counts the four matmuls it REQUIRES (dV = P^T dO, dP = dO V^T,
    dQ = dS K, dK = dS^T Q); a kernel's recomputation of QK^T is its own
    cost and shows as a lower share."""
    _, n_q, _, hd = _dims(model)
    pairs = batch * n_q * seq_len * (seq_len + 1) / 2
    return {"fwd": 2 * 2 * hd * pairs, "bwd": 4 * 2 * hd * pairs}


def flash_bytes(model, batch: int, seq_len: int, itemsize: int = 2) -> dict:
    """Least HBM traffic of one layer's attention: forward reads Q, K, V and
    writes O once (K and V at their own head count); backward reads Q, K,
    V, O, dO and writes dQ, dK, dV."""
    _, n_q, n_kv, hd = _dims(model)
    q = batch * seq_len * n_q * hd * itemsize
    kv = batch * seq_len * n_kv * hd * itemsize
    return {"fwd": 2 * q + 2 * kv, "bwd": 4 * q + 4 * kv}


def flash_attention(model, shapes) -> dict:
    """``flash_flops`` and ``flash_bytes`` as a ``work: "kernel"`` roofline
    takes them: per pass, for one training step (every layer)."""
    b, s, layers = shapes["rows_per_chip"], shapes["seq_len"], model["num_hidden_layers"]
    f, by = flash_flops(model, b, s), flash_bytes(model, b, s)
    return {k: {"flops": layers * f[k], "bytes": layers * by[k]}
            for k in ("fwd", "bwd")}


def expert_gemm(model, shapes, itemsize: int = 2) -> dict:
    """The per-expert matrix products of one training step of a routed
    model (every layer): each token's row goes through the gate, up and
    down projections ([H, F], [H, F], [F, H]) of its ``top_k`` experts, so
    forward is 2 * tokens * top_k * 3 H F FLOPs a layer; backward takes the
    gradient of both operands of each product, twice that. Least bytes:
    every expert's weights once a pass (backward writes their gradient
    once too) and the routed rows [tokens * top_k, H] in and out (backward:
    the rows, the output's gradient in, the rows' gradient out). The
    [tokens * top_k, 2 F] intermediate need not leave the chip's memory."""
    h, f = model["hidden_size"], model["intermediate_size"]
    routed = shapes["rows_per_chip"] * shapes["seq_len"] * model["num_experts_per_tok"]
    layers = model["num_hidden_layers"]
    fwd = 2.0 * routed * 3 * h * f
    weights = model["num_experts"] * 3 * h * f * itemsize
    rows = routed * h * itemsize
    return {"fwd": {"flops": layers * fwd,
                    "bytes": layers * (weights + 2 * rows)},
            "bwd": {"flops": layers * 2 * fwd,
                    "bytes": layers * (2 * weights + 3 * rows)}}


def weight_bytes(model, itemsize: int = 2) -> int:
    """Bytes of the weights one decode tick has to read: every layer's
    matrices and the output head (the embedding is a gather of a few rows)."""
    h = model["hidden_size"]
    per_layer = layer_matmul_params(model, active_only=False) + 2 * h * 2
    return ((model["num_hidden_layers"] * per_layer + h * model["vocab_size"])
            * itemsize)


def kv_bytes_per_token(model, itemsize: int = 2) -> int:
    _, _, n_kv, hd = _dims(model)
    return 2 * model["num_hidden_layers"] * n_kv * hd * itemsize


def decode_tick_bytes(model, live_tokens: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode tick: the weights once, and the keys
    and values of the tokens the active slots hold."""
    return weight_bytes(model, itemsize) + live_tokens * kv_bytes_per_token(
        model, itemsize)
