"""The family of routed decoders with a dense start and a shared expert
(DeepSeekMoE's layout, as ``models/moe_lm.py`` builds it): attention as
``gqa_decoder``; the first ``first_k_dense_replace`` layers a SwiGLU MLP of
``intermediate_size``; every later layer ``num_experts`` routed experts of
``moe_intermediate_size`` beside ``num_shared_experts`` always-on ones held
as one MLP.

Leaves beyond ``gqa_decoder``'s, in a routed layer:

    layers.<i>.shared_gate_up [H, 2 S F]  (gate | up)   layers.<i>.shared_down [S F, H]
"""

from __future__ import annotations

from ..refs.shared_expert_moe import (logits_at, loss0_expected,  # noqa: F401
                                      loss_and_grads)
from . import gqa_decoder


def _dense(model, i):
    return i < model["first_k_dense_replace"]


def _as_gqa(model, routed: bool):
    """The configuration as ``gqa_decoder`` reads one kind of layer."""
    m = dict(model)
    if routed:
        m["intermediate_size"] = model["moe_intermediate_size"]
    else:
        m.pop("num_experts", None)
    return m


def leaf_shapes(model: dict) -> dict:
    h = model["hidden_size"]
    wide = model["num_shared_experts"] * model["moe_intermediate_size"]
    dense = gqa_decoder.leaf_shapes(_as_gqa(model, False))
    routed = gqa_decoder.leaf_shapes(_as_gqa(model, True))
    out = {n: dense[n] for n in ("embed", "head", "final_norm")}
    for i in range(model["num_hidden_layers"]):
        p = f"layers.{i}."
        src = dense if _dense(model, i) else routed
        out.update({n: v for n, v in src.items() if n.startswith(p)})
        if not _dense(model, i):
            out[p + "shared_gate_up"] = ((h, 2 * wide), "matrix")
            out[p + "shared_down"] = ((wide, h), "matrix")
    return out


# -- required work ------------------------------------------------------------

def _layers(model):
    k = min(model["first_k_dense_replace"], model["num_hidden_layers"])
    return k, model["num_hidden_layers"] - k


def _shared_params(model):
    return 3 * model["hidden_size"] * (model["num_shared_experts"]
                                       * model["moe_intermediate_size"])


def train_flops_per_token(model, seq_len: int) -> float:
    """As ``gqa_decoder`` counts it, layer kind by layer kind, plus the
    shared expert every token goes through in a routed layer."""
    dense, routed = _layers(model)
    one = lambda m: gqa_decoder.train_flops_per_token(
        dict(m, num_hidden_layers=1, vocab_size=0), seq_len)
    head = 6.0 * model["hidden_size"] * model["vocab_size"]
    return (head + dense * one(_as_gqa(model, False))
            + routed * (one(_as_gqa(model, True)) + 6.0 * _shared_params(model)))


def decode_tick_bytes(model, live_tokens: float, itemsize: int = 2) -> float:
    dense, routed = _layers(model)
    one = lambda m: gqa_decoder.weight_bytes(
        dict(m, num_hidden_layers=1, vocab_size=0), itemsize)
    weights = (model["hidden_size"] * model["vocab_size"] * itemsize
               + dense * one(_as_gqa(model, False))
               + routed * (one(_as_gqa(model, True))
                           + _shared_params(model) * itemsize))
    return weights + live_tokens * gqa_decoder.kv_bytes_per_token(model, itemsize)


def expert_gemm(model, shapes) -> dict:
    """The routed experts' products of one step: the routed layers only,
    at the experts' own width; the shared expert is a plain matmul."""
    return gqa_decoder.expert_gemm(
        dict(_as_gqa(model, True), num_hidden_layers=_layers(model)[1]), shapes)
