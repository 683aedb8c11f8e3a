"""The family of decoders with latent (MLA) attention in every layer, a dense
first ``first_k_dense_replace`` layers and then sigmoid-routed experts with a
selection bias beside a shared expert (DeepSeek-V2/V3's layout, under the
keys GLM-4.7-Flash publishes: ``n_routed_experts``, ``n_shared_experts``,
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``).

Canonical leaves (shapes are [in, out]; n = heads, r = kv_lora_rank):

    embed [V, H]   head [H, V]   final_norm [H]
    layers.<i>.attn_norm [H]     layers.<i>.mlp_norm [H]
    layers.<i>.q_a [H, q_lora_rank]        layers.<i>.q_a_norm [q_lora_rank]
    layers.<i>.q_b [q_lora_rank, n (nope + rope)]   per head [nope | rope]
    layers.<i>.kv_a [H, r + rope]          columns [c | k_r]
    layers.<i>.kv_a_norm [r]
    layers.<i>.kv_b [r, n (nope + v)]      per head [k_nope | v]
    layers.<i>.o [n v, H]
    dense:  layers.<i>.gate_up [H, 2 I]  (gate | up)   layers.<i>.down [I, H]
    routed: layers.<i>.router [H, E] (float32)   layers.<i>.router_bias [E] (float32)
            layers.<i>.experts_gate_up [E, H, 2 F]     layers.<i>.experts_down [E, F, H]
            layers.<i>.shared_gate_up [H, 2 S F]       layers.<i>.shared_down [S F, H]

The reference is ``refs/mla_moe.py``. Required work, below, counts what the
mathematics needs in the form a serving deployment runs it: a decode tick
reads every weight but the embedding once, the routed experts only as far
as the tick's rows are expected to hit them, and ONE cached row a live
token a layer.
"""

from __future__ import annotations

from ..refs.mla_moe import (logits_at, loss0_expected,  # noqa: F401
                            loss_and_grads)


def _dims(model):
    return (model["hidden_size"], model["num_attention_heads"],
            model["q_lora_rank"], model["kv_lora_rank"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"])


def _layers(model):
    k = min(model["first_k_dense_replace"], model["num_hidden_layers"])
    return k, model["num_hidden_layers"] - k


def leaf_shapes(model: dict) -> dict:
    h, n, q_rank, r, nope, rope, vd = _dims(model)
    v, e = model["vocab_size"], model["n_routed_experts"]
    f = model["moe_intermediate_size"]
    wide = model["n_shared_experts"] * f
    out = {"embed": ((v, h), "matrix"), "head": ((h, v), "matrix"),
           "final_norm": ((h,), "norm")}
    for i in range(model["num_hidden_layers"]):
        p = f"layers.{i}."
        out.update({
            p + "attn_norm": ((h,), "norm"), p + "mlp_norm": ((h,), "norm"),
            p + "q_a": ((h, q_rank), "matrix"),
            p + "q_a_norm": ((q_rank,), "norm"),
            p + "q_b": ((q_rank, n * (nope + rope)), "matrix"),
            p + "kv_a": ((h, r + rope), "matrix"),
            p + "kv_a_norm": ((r,), "norm"),
            p + "kv_b": ((r, n * (nope + vd)), "matrix"),
            p + "o": ((n * vd, h), "matrix")})
        if i < model["first_k_dense_replace"]:
            m = model["intermediate_size"]
            out[p + "gate_up"] = ((h, 2 * m), "matrix")
            out[p + "down"] = ((m, h), "matrix")
        else:
            out.update({
                p + "router": ((h, e), "router"),
                p + "router_bias": ((e,), "router"),
                p + "experts_gate_up": ((e, h, 2 * f), "matrix"),
                p + "experts_down": ((e, f, h), "matrix"),
                p + "shared_gate_up": ((h, 2 * wide), "matrix"),
                p + "shared_down": ((wide, h), "matrix")})
    return out


# -- required work ------------------------------------------------------------

def attention_params(model) -> int:
    """The five projections of one layer's attention."""
    h, n, q_rank, r, nope, rope, vd = _dims(model)
    return (h * q_rank + q_rank * n * (nope + rope) + h * (r + rope)
            + r * n * (nope + vd) + n * vd * h)


def expert_params(model) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def param_count(model) -> int:
    """All parameters, norms and the selection bias included."""
    h, e = model["hidden_size"], model["n_routed_experts"]
    dense, routed = _layers(model)
    norms = 2 * h + model["q_lora_rank"] + model["kv_lora_rank"]
    return (2 * model["vocab_size"] * h + h
            + dense * (attention_params(model) + norms
                       + 3 * h * model["intermediate_size"])
            + routed * (attention_params(model) + norms + h * e + e
                        + (e + model["n_shared_experts"])
                        * expert_params(model)))


def experts_hit(model, rows: int) -> float:
    """Routed experts that ``rows`` tokens choosing top-k of E at random are
    expected to reach: E (1 - (1 - k/E)^rows)."""
    e, k = model["n_routed_experts"], model["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def weight_bytes(model, rows: int | None = None, itemsize: int = 2) -> float:
    """Bytes of weights a decode tick of ``rows`` rows reads: everything but
    the embedding (a gather of a few rows), the routed experts as far as
    the rows are expected to hit them (``rows`` None: all of them). The
    router and its bias are float32."""
    h, e = model["hidden_size"], model["n_routed_experts"]
    dense, routed = _layers(model)
    norms = 4 * (2 * h + model["q_lora_rank"] + model["kv_lora_rank"])
    hit = e if rows is None else experts_hit(model, rows)
    per_dense = itemsize * (attention_params(model)
                            + 3 * h * model["intermediate_size"]) + norms
    per_routed = (itemsize * (attention_params(model)
                              + (hit + model["n_shared_experts"])
                              * expert_params(model))
                  + 4 * (h * e + e) + norms)
    return (itemsize * h * model["vocab_size"] + 4 * h
            + dense * per_dense + routed * per_routed)


def kv_bytes_per_token(model, itemsize: int = 2) -> int:
    """One row ``[c_kv | k_r]`` a layer."""
    return (model["num_hidden_layers"]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * itemsize)


def decode_tick_bytes(model, live_tokens: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode tick of ``engine.max_batch`` rows:
    the weights (``weight_bytes``) and one cached row a live token a layer."""
    return (weight_bytes(model, model["engine"]["max_batch"], itemsize)
            + live_tokens * kv_bytes_per_token(model, itemsize))


def train_flops_per_token(model, seq_len: int) -> float:
    """Required FLOPs to train on one token, forward and backward: 6 per
    weight the token is multiplied with (the head included, the embedding
    gather not; of the routed experts the ``num_experts_per_tok`` chosen
    ones, plus the router and the shared expert), plus causal attention in
    the expanded form: QK^T over nope + rope and PV over v, 6 x n x
    (nope + rope + v) / 2 x (s + 1) a layer (``counts.train_flops_per_token``
    has the derivation for equal head sizes)."""
    h, n, _, _, nope, rope, vd = _dims(model)
    dense, routed = _layers(model)
    weights = (h * model["vocab_size"]
               + dense * (attention_params(model)
                          + 3 * h * model["intermediate_size"])
               + routed * (attention_params(model)
                           + h * model["n_routed_experts"]
                           + (model["num_experts_per_tok"]
                              + model["n_shared_experts"])
                           * expert_params(model)))
    return (6.0 * weights + model["num_hidden_layers"] * 3.0 * n
            * (nope + rope + vd) * (seq_len + 1))


def latent_attention_decode(model, shapes, itemsize: int = 2) -> dict:
    """The absorbed attention of ONE decode tick (every layer) over
    ``shapes["live_tokens"]`` cached rows in all, ``shapes["rows"]`` of them
    new: per cached row and head 2 (r + rope) FLOPs of scores and 2 r of
    values; each cached row read once, the queries in and the contexts out."""
    _, n, _, r, _, rope, _ = _dims(model)
    layers, live, rows = (model["num_hidden_layers"], shapes["live_tokens"],
                          shapes["rows"])
    return {"fwd": {
        "flops": layers * live * n * 2.0 * (2 * r + rope),
        "bytes": layers * itemsize * (live * (r + rope)
                                      + rows * n * (2 * r + rope))}}


def expert_gemm_decode(model, shapes, itemsize: int = 2) -> dict:
    """The routed experts' products of ONE decode tick of ``shapes["rows"]``
    rows (routed layers only): 2 x rows x top-k x 3 H F FLOPs a layer; the
    weights of the experts the rows are expected to hit, and the routed
    rows in and out."""
    h, k = model["hidden_size"], model["num_experts_per_tok"]
    rows, routed = shapes["rows"], _layers(model)[1]
    return {"fwd": {
        "flops": routed * 2.0 * rows * k * expert_params(model),
        "bytes": routed * itemsize * (experts_hit(model, rows)
                                      * expert_params(model)
                                      + 2 * rows * k * h)}}
