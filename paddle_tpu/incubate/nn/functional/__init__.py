"""paddle_tpu.incubate.nn.functional — fused-op API surface.

Reference: python/paddle/incubate/nn/functional/{fused_rms_norm.py,
fused_layer_norm.py,fused_rotary_position_embedding.py,fused_matmul_bias.py,
fused_transformer.py,masked_multihead_attention.py,
block_multihead_attention.py} and their phi fusion kernels
(paddle/phi/kernels/fusion/gpu/*).

TPU-native: "fused" here means *fusable by XLA* — each function is written
as one jit-friendly expression so XLA emits a single fused loop (plus Pallas
fast paths where they exist: flash attention, and the fused rms/layernorm
custom-vjp in paddle_tpu.ops). The paged/block KV-cache decode attention is
implemented natively on dense block pools with gather — the TPU analogue of
block_multi_head_attention_kernel.cu.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ....ops import norm as _norm_ops
from ....ops.rope import fused_rotary_position_embedding  # re-export
from ....nn import functional as F

__all__ = [
    "fused_rms_norm", "fused_layer_norm", "fused_rotary_position_embedding",
    "fused_matmul_bias", "fused_linear", "fused_bias_act",
    "fused_linear_activation", "swiglu",
    "masked_multihead_attention", "block_multihead_attention",
    "memory_efficient_attention", "variable_length_memory_efficient_attention",
]

swiglu = F.swiglu


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon: float = 1e-6,
                   begin_norm_axis: int = -1, bias=None, residual=None,
                   quant_scale: float = -1, **_ignored):
    """reference: incubate/nn/functional/fused_rms_norm.py — optional
    bias+residual add fused in front of the norm; returns (out, residual_out)
    when residual is given, matching the reference's two-output contract."""
    if begin_norm_axis not in (-1, x.ndim - 1):
        raise NotImplementedError("rms_norm fuses over the last axis on TPU")
    if bias is not None:
        x = x + bias
    if residual is not None:
        x = x + residual
        residual_out = x
    out = _norm_ops.rms_norm(x, norm_weight, epsilon)
    if norm_bias is not None:
        out = out + norm_bias
    if residual is not None:
        return out, residual_out
    return out


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon: float = 1e-5,
                     begin_norm_axis: int = -1, bias=None, residual=None,
                     **_ignored):
    """reference: incubate/nn/functional/fused_layer_norm.py"""
    if begin_norm_axis not in (-1, x.ndim - 1):
        raise NotImplementedError("layer_norm fuses over the last axis on TPU")
    if bias is not None:
        x = x + bias
    if residual is not None:
        x = x + residual
        residual_out = x
    out = _norm_ops.layer_norm(x, norm_weight, norm_bias, epsilon)
    if residual is not None:
        return out, residual_out
    return out


def fused_matmul_bias(x, y, bias=None, transpose_x: bool = False,
                      transpose_y: bool = False, name=None):
    """reference: fused_matmul_bias.py (cublasLt epilogue fusion) — XLA
    fuses the bias add into the matmul epilogue on its own."""
    if transpose_x:
        x = jnp.swapaxes(x, -1, -2)
    if transpose_y:
        y = jnp.swapaxes(y, -1, -2)
    out = jnp.matmul(x, y)
    if bias is not None:
        out = out + bias
    return out


def fused_linear(x, weight, bias=None, transpose_weight: bool = False,
                 name=None):
    return fused_matmul_bias(x, weight, bias, transpose_y=transpose_weight)


_ACTS = {
    "gelu": lambda x: F.gelu(x, approximate=True),
    "relu": F.relu,
    "silu": F.silu,
    "swish": F.silu,
    "sigmoid": F.sigmoid,
    "tanh": F.tanh,
    "identity": lambda x: x,
    "none": lambda x: x,
}


def fused_bias_act(x, bias=None, act_method: str = "gelu",
                   dequant_scales=None, shift=None, smooth=None, **_ignored):
    """reference: fused_bias_act kernel (phi fusion fused_bias_act_kernel.cu):
    out = act(x + bias), with the geglu/swiglu gated variants splitting the
    last dim in half."""
    if bias is not None:
        x = x + bias
    m = act_method.lower()
    if m in ("swiglu", "geglu"):
        gate, up = jnp.split(x, 2, axis=-1)
        act = F.silu if m == "swiglu" else (lambda v: F.gelu(v, approximate=True))
        return act(gate) * up
    try:
        return _ACTS[m](x)
    except KeyError:
        raise ValueError(f"unknown act_method {act_method!r}") from None


def fused_linear_activation(x, y, bias=None, trans_x: bool = False,
                            trans_y: bool = False, activation: str = "gelu"):
    """reference: fused_linear_activation (gemm + epilogue act)."""
    return fused_bias_act(fused_matmul_bias(x, y, None, trans_x, trans_y),
                          bias, act_method=activation)


# ---------------------------------------------------------------------------
# decode attention with KV caches
# ---------------------------------------------------------------------------

def _gqa_expand(k, num_q_heads):
    """[..., kv_heads, d] → repeat to num_q_heads."""
    kv_heads = k.shape[-2]
    if kv_heads == num_q_heads:
        return k
    rep = num_q_heads // kv_heads
    return jnp.repeat(k, rep, axis=-2)


def masked_multihead_attention(x, cache_kv, seq_lens=None, src_mask=None,
                               out_scale: float = -1, num_head: Optional[int] = None,
                               head_dim: Optional[int] = None, **_ignored):
    """Single-token decode attention over a dense KV cache (reference:
    incubate/nn/functional/masked_multihead_attention.py; kernel
    phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu).

    Args:
        x: [B, 3*H*D] fused qkv for the new token (reference layout) or
           [B, H, D] plain q with cache already containing k/v for this step.
        cache_kv: [2, B, H_kv, T_max, D] running cache; the new token's k/v
           (from x when fused) are written at position ``seq_lens``.
        seq_lens: [B] number of valid cache entries *before* this token.
    Returns:
        (out [B, H*D], updated cache_kv) — functional cache update.
    """
    two, B, H_kv, T_max, D = cache_kv.shape
    assert two == 2
    if x.ndim == 2:  # fused qkv layout [B, 3*H*D]
        HD = x.shape[-1] // 3
        H = num_head or (HD // (head_dim or D))
        qkv = x.reshape(B, 3, H, HD // H)
        q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        # GQA: fold extra q heads later; cache heads are H_kv
        k_new = k_new[:, :H_kv]
        v_new = v_new[:, :H_kv]
    else:
        raise ValueError("x must be the fused [B, 3*H*D] qkv of one step")
    if seq_lens is None:
        seq_lens = jnp.zeros((B,), jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)

    # scatter the new kv at each batch row's seq_len position
    b_idx = jnp.arange(B)
    k_cache = cache_kv[0].at[b_idx, :, seq_lens, :].set(k_new)
    v_cache = cache_kv[1].at[b_idx, :, seq_lens, :].set(v_new)

    H = q.shape[1]
    k_full = _gqa_expand(jnp.swapaxes(k_cache, 1, 2), H)   # [B, T, H, D]
    v_full = _gqa_expand(jnp.swapaxes(v_cache, 1, 2), H)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                        k_full.astype(jnp.float32)) * scale
    t_idx = jnp.arange(T_max)[None, None, :]
    valid = t_idx <= seq_lens[:, None, None]               # includes new token
    logits = jnp.where(valid, logits, -jnp.inf)
    if src_mask is not None:
        logits = logits + src_mask.reshape(B, 1, -1)[:, :, :T_max]
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p, v_full.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, -1)
    return out, jnp.stack([k_cache, v_cache])


def block_multihead_attention(qkv, key_cache, value_cache, seq_lens_decoder,
                              block_tables, num_heads: Optional[int] = None,
                              head_dim: Optional[int] = None, **_ignored):
    """Paged-KV-cache decode attention (reference:
    incubate/nn/functional/block_multihead_attention.py; kernel
    phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu — the
    vLLM-style PagedAttention).

    Cache layout: ``key_cache``/``value_cache`` are HEAD-MAJOR block pools
    [H_kv, num_blocks, block_size, D] (the TPU-native layout the Pallas
    paged kernel streams — consecutive pages of a kv head are contiguous
    and page blocks are Mosaic (sublane, lane)-legal; the reference's CUDA
    kernel uses [max_block_nums, kv_num_heads, block_size, head_size]);
    ``block_tables`` [B, max_blocks] maps each sequence's logical block i
    to a pool block id (−1 = unused); ``seq_lens_decoder`` [B] counts
    tokens already cached per sequence.

    One decode step: writes the new token's k/v into the right block slot,
    attends q over the sequence's gathered pages. Returns
    (out [B, H*D], key_cache, value_cache) functionally.
    """
    H_kv, num_blocks, block_size, D = key_cache.shape
    B, max_blocks = block_tables.shape
    HD3 = qkv.shape[-1]
    H = num_heads or (HD3 // 3 // (head_dim or D))
    q, k_new, v_new = jnp.split(qkv.reshape(B, 3, -1), 3, axis=1)
    q = q.reshape(B, H, -1)
    k_new = k_new.reshape(B, H, -1)[:, :H_kv, :D]
    v_new = v_new.reshape(B, H, -1)[:, :H_kv, :D]

    seq_lens = jnp.asarray(seq_lens_decoder, jnp.int32)
    # locate the physical slot of the new token
    logical_block = seq_lens // block_size
    offset = seq_lens % block_size
    b_idx = jnp.arange(B)
    phys_block = block_tables[b_idx, logical_block]        # [B]
    # pool[h, phys_block[b], offset[b]] = new[b, h]
    key_cache = key_cache.at[:, phys_block, offset].set(
        jnp.swapaxes(k_new, 0, 1))
    value_cache = value_cache.at[:, phys_block, offset].set(
        jnp.swapaxes(v_new, 0, 1))

    # TPU fast path: the Pallas paged-decode kernel copies each row's live
    # pages from the pools in HBM itself, never gathering [B, T] into HBM
    from ....ops.registry import backend_kind
    from ....ops.pallas.paged_attention import (paged_decode_attention,
                                                paged_decode_supported)
    if backend_kind() == "tpu" and paged_decode_supported(
            q.reshape(B, H, -1), key_cache):
        out = paged_decode_attention(q.reshape(B, H, -1), key_cache,
                                     value_cache, block_tables, seq_lens)
        return out.reshape(B, -1), key_cache, value_cache

    # gather each sequence's pages: [H_kv, B, max_blocks, block_size, D]
    safe_tables = jnp.maximum(block_tables, 0)
    k_pages = key_cache[:, safe_tables]
    v_pages = value_cache[:, safe_tables]
    T = max_blocks * block_size
    k_seq = jnp.moveaxis(k_pages.reshape(H_kv, B, T, D), 0, 2)  # [B,T,H_kv,D]
    v_seq = jnp.moveaxis(v_pages.reshape(H_kv, B, T, D), 0, 2)
    k_seq = _gqa_expand(k_seq, H)
    v_seq = _gqa_expand(v_seq, H)

    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                        k_seq.astype(jnp.float32)) * scale
    t_idx = jnp.arange(T)[None, None, :]
    valid = t_idx <= seq_lens[:, None, None]
    logits = jnp.where(valid, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p, v_seq.astype(jnp.float32))
    return out.astype(qkv.dtype).reshape(B, -1), key_cache, value_cache


def memory_efficient_attention(query, key, value, attn_bias=None, p: float = 0.0,
                               scale: Optional[float] = None,
                               training: bool = True):
    """reference: incubate/nn/memory_efficient_attention.py — on TPU the
    flash-attention path IS the memory-efficient path.

    ``attn_bias`` accepts the attn_bias.AttentionBias hierarchy and routes
    each structure to its cheapest form: LowerTriangular -> the kernel's
    causal flag; BlockDiagonal(Causal) -> SEGMENT IDS (packed varlen, no
    dense bias in HBM); anything else materializes a dense additive bias
    exactly like the reference."""
    from ....ops.attention import flash_attention
    from ..attn_bias import (AttentionBias, BlockDiagonalMask,
                             LowerTriangularMask,
                             LowerTriangularMaskWithTensorBias)
    causal = False
    segment_ids = None
    dropout_p = p if training else 0.0
    if isinstance(attn_bias, AttentionBias):
        if isinstance(attn_bias, BlockDiagonalMask) and (
                not attn_bias.causal
                or attn_bias.q_seqinfo is attn_bias.k_seqinfo):
            # causal blocks need aligned q/k layouts for the kernel's global
            # causal mask to equal the per-block triangles; unequal layouts
            # fall through to the dense materialization below
            segment_ids = attn_bias.to_segment_ids()
            q_seg, kv_seg = segment_ids
            segment_ids = (jnp.broadcast_to(q_seg, (query.shape[0],
                                                    query.shape[1])),
                           jnp.broadcast_to(kv_seg, (key.shape[0],
                                                     key.shape[1])))
            causal = attn_bias.causal
            attn_bias = None
        elif type(attn_bias) is LowerTriangularMask and \
                query.shape[1] == key.shape[1]:
            # the kernel's causal flag is bottom-right aligned (FA
            # convention); the mask's own semantics are TOP-LEFT triu —
            # identical only for square shapes, so rectangular falls
            # through to the dense materialization
            causal = True
            attn_bias = None
        elif isinstance(attn_bias, LowerTriangularMaskWithTensorBias) and \
                query.shape[1] == key.shape[1]:
            causal = True
            attn_bias = jnp.asarray(attn_bias._bias)
        else:
            attn_bias = attn_bias.materialize(
                (query.shape[0], 1, query.shape[1], key.shape[1]),
                dtype=jnp.float32)
    return flash_attention(query, key, value, attn_mask=attn_bias,
                           dropout_p=dropout_p, causal=causal, scale=scale,
                           segment_ids=segment_ids)


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale: Optional[float] = None):
    """Var-len batch attention via length masking (reference:
    variable_length_memory_efficient_attention.py). query [B, H, S, D]."""
    B, H, S, D = query.shape
    scale = scale or (1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32)))
    logits = jnp.einsum("bhsd,bhtd->bhst", query.astype(jnp.float32),
                        key.astype(jnp.float32)) * scale
    t_idx = jnp.arange(key.shape[2])
    valid_kv = t_idx[None, :] < jnp.asarray(kv_seq_lens)[:, None]  # [B, T]
    logits = jnp.where(valid_kv[:, None, None, :], logits, -jnp.inf)
    if mask is not None:
        logits = logits + mask
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", p, value.astype(jnp.float32))
    s_idx = jnp.arange(S)
    valid_q = s_idx[None, :] < jnp.asarray(seq_lens)[:, None]
    out = jnp.where(valid_q[:, None, :, None], out, 0.0)
    return out.astype(query.dtype)
