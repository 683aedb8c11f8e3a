"""The family of decoder-only transformers with grouped-query attention: a
fused q|k|v projection, rotary embedding, and in EVERY layer either a SwiGLU
MLP or (``num_experts`` set) a softmax-routed block of experts.

Canonical leaves (shapes are [in, out]; the fused layouts are part of the
benchmark's convention and the references split them the same way):

    embed [V, H]   head [H, V]   final_norm [H]
    layers.<i>.attn_norm [H]     layers.<i>.mlp_norm [H]
    layers.<i>.qkv [H, (n_q + 2 n_kv) * hd]    columns q | k | v
    layers.<i>.o [n_q * hd, H]
    dense:  layers.<i>.gate_up [H, 2 I]  (gate | up)   layers.<i>.down [I, H]
    routed: layers.<i>.router [H, E] (float32)
            layers.<i>.experts_gate_up [E, H, 2 F]      layers.<i>.experts_down [E, F, H]

The reference is ``refs/decoder.py`` (with ``refs/olmoe.py`` for the routed
block), the required work ``counts.py``: this module only gathers them
under the names ``families/__init__.py`` asks for.
"""

from __future__ import annotations

from ..counts import (decode_tick_bytes, expert_gemm,  # noqa: F401
                      flash_attention, flash_bytes, flash_flops,
                      kv_bytes_per_token, layer_matmul_params, param_count,
                      train_flops_per_token, weight_bytes)
from ..refs.decoder import (logits_at, loss0_expected,  # noqa: F401
                            loss_and_grads)


def leaf_shapes(model: dict) -> dict:
    """Canonical name -> (shape, kind) for a configuration's ``model`` group.
    ``kind`` is "norm" (ones, float32), "router" (normal, float32) or
    "matrix" (normal, the configuration's dtype)."""
    h, v = model["hidden_size"], model["vocab_size"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or h // n_q
    out = {"embed": ((v, h), "matrix"), "head": ((h, v), "matrix"),
           "final_norm": ((h,), "norm")}
    experts = model.get("num_experts", 0)
    for i in range(model["num_hidden_layers"]):
        p = f"layers.{i}."
        out[p + "attn_norm"] = ((h,), "norm")
        out[p + "mlp_norm"] = ((h,), "norm")
        out[p + "qkv"] = ((h, (n_q + 2 * n_kv) * hd), "matrix")
        out[p + "o"] = ((n_q * hd, h), "matrix")
        if experts:
            f = model["intermediate_size"]
            out[p + "router"] = ((h, experts), "router")
            out[p + "experts_gate_up"] = ((experts, h, 2 * f), "matrix")
            out[p + "experts_down"] = ((experts, f, h), "matrix")
        else:
            m = model["intermediate_size"]
            out[p + "gate_up"] = ((h, 2 * m), "matrix")
            out[p + "down"] = ((m, h), "matrix")
    return out
