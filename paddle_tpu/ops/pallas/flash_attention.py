"""Pallas TPU flash attention (forward + backward), with segment + dropout
support.

Reference analogue: paddle/phi/kernels/gpu/flash_attn_kernel.cu (FA2 via
dynload — flash_attn_fwd/bwd, incl. the varlen entry at :91, in-kernel
dropout via the philox args at :91-117) and its python surface
python/paddle/nn/functional/flash_attention.py. Re-designed for the TPU
memory hierarchy instead of translated: the kernel streams K/V blocks
through VMEM with the online-softmax recurrence (running max m, denominator
l) carried in VMEM scratch across the innermost sequential grid dimension,
keeping the [sq, sk] score matrix out of HBM entirely; fp32 accumulation on
the MXU via preferred_element_type.

TPU layout (the round-2 fix): Mosaic requires the last two dims of every
block to be (sublane, lane) = (8k, 128k) aligned or equal to the array
dims, so the kernel computes in [b, h, s, d] — blocks are
(1, 1, block_q, d). The public API keeps the paddle/FA convention
[b, s, h, d]; the transposes sit at the pallas boundary where XLA fuses
them. Per-row logsumexp rides in a [b, h, s, LSE_LANES] array (scalar
broadcast across a small lane dim) for the same reason.

GQA: h_kv <= h mapped via BlockSpec index arithmetic — no materialized head
expansion in the forward, and dk/dv are accumulated AT KV-HEAD RESOLUTION
inside the backward kernel by folding the query-head group into the
innermost sequential grid dim.

Varlen / packed sequences: integer ``segment_ids`` ([b, sq] / [b, sk])
mask cross-segment attention inside the kernel — the TPU equivalent of the
reference's cu_seqlens varlen API (flash_attn_kernel.cu:91).

Dropout: in-kernel counter-based PRNG — each score cell hashes its global
(batch, head, q-pos, k-pos) coordinates with the seed (murmur3 finalizer,
plain uint32 vector ops), so the forward and both backward kernels
regenerate the identical keep-mask from one scalar seed on any backend and
under any block-size choice — the TPU analogue of FA2's philox offset
replay (flash_attn_kernel.cu dropout path). No O(s^2) mask ever hits HBM.

Backward = two kernels (dq; dk+dv) using the saved per-row logsumexp, plus
a delta = rowsum(out * dout) precomputed in XLA.

Falls back to the XLA composition (ops/attention.py) for arbitrary dense
masks or block-indivisible sequence lengths.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from .per_shard import active_axes, per_shard, qkv_layout

try:  # pltpu only imports cleanly on TPU-enabled jaxlib builds
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except ImportError:  # pragma: no cover
    pltpu = None
    _HAS_PLTPU = False

from ..registry import register_kernel


def _tpu_params(*semantics):
    """Megacore: mark independent grid dims parallel; only the innermost
    (k/q accumulation) dim is sequential ("arbitrary")."""
    if pltpu is None:
        return None
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30  # large-negative instead of -inf: avoids inf-inf=nan in exp
LSE_LANES = 8    # lane width for per-row scalars (lse/delta); Mosaic wants
                 # the last block dim == the array dim, 8 keeps HBM cost low


def _block_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _causal_mask(qi, ki, offset, block_q, block_k):
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return (cols + ki * block_k) <= (rows + qi * block_q + offset)


def _mask_scores(s, causal, qs_ref, ks_ref, qi, ki, offset, block_q, block_k):
    """Apply causal and/or segment masking to a [bq, bk] score block.

    qs_ref: [1, block_q, LSE_LANES] tile; ks_ref: [1, LSE_LANES, block_k]
    tile (segment ids lane/sublane-broadcast outside the kernel) — all
    reads stay 2-D, which Mosaic vectorizes cleanly."""
    mask = None
    if causal:
        mask = _causal_mask(qi, ki, offset, block_q, block_k)
    if qs_ref is not None:
        qseg = qs_ref[0, :, :1]            # [bq, 1]
        kseg = ks_ref[0, :1, :]            # [1, bk]
        seg = qseg == kseg
        mask = seg if mask is None else (mask & seg)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    return s


def _dropout_keep(seed_ref, bi, h, qi, ki, dropout_p, block_q, block_k, sk):
    """Regenerable keep-mask for one [block_q, block_k] score block.

    Counter-based: each (batch, head, query-pos, key-pos) CELL hashes its
    global coordinates with the seed through the murmur3 finalizer — plain
    uint32 vector ops, so the same bits come out of Mosaic on TPU and of
    the interpreters on CPU, and out of the forward, dq and dkv kernels
    regardless of grid order or autotuned block sizes. (pltpu.prng_* was
    rejected: the TPU-interpret simulator stubs it to zeros, which would
    make dropout untestable off-hardware.)"""
    rows = jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 1)
    cell = ((qi * block_q).astype(jnp.uint32) + rows) * jnp.uint32(sk) \
        + (ki * block_k).astype(jnp.uint32) + cols
    key = (seed_ref[0].astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
           + bi.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
           + h.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    x = cell ^ key
    # murmur3 fmix32: full-avalanche mixing of the 32-bit cell id
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    threshold = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return x >= threshold                                # P(keep) = 1 - p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, has_seg, dropout_p, sq, sk,
                block_q, block_k):
    """Grid: (b, h, nq, nk) — nk innermost/sequential; scratch carries the
    online-softmax state across nk iterations. All tensor blocks are
    [1, 1, block, d]-shaped over [b, h, s, d] arrays."""
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    if has_seg:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        qs_ref = ks_ref = None
    bi = pl.program_id(0)
    hi = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip blocks strictly above the diagonal (bottom-right aligned)
    offset = sk - sq
    first_masked_col = qi * block_q + offset + block_q  # col >= this masked

    @pl.when(jnp.logical_not(causal) | (ki * block_k < first_masked_col))
    def _compute():
        q = q_ref[0, 0, :, :]                      # [bq, d]
        k = k_ref[0, 0, :, :]                      # [bk, d]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        s = _mask_scores(s, causal, qs_ref, ks_ref, qi, ki, offset,
                         block_q, block_k)
        m_prev = m_scr[:, :1]                      # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        # masked entries must be EXACTLY zero even when the whole row is
        # masked (m_new == NEG_INF would make exp(s - m_new) = 1, turning
        # a fully-masked row into a mean over V)
        p = jnp.where(s <= NEG_INF * 0.5, 0.0,
                      jnp.exp(s - m_new))          # [bq, bk]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            # l accumulates the true softmax denominator; dropout scales the
            # numerator only (dropout(P)·V == (Σ p·M/(1-r)·v)/l)
            keep = _dropout_keep(seed_ref, bi, hi, qi, ki, dropout_p,
                                 block_q, block_k, sk)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(safe_l), (block_q, LSE_LANES))


def _seg_inputs(q_seg, kv_seg):
    """Lift [b, s] segment ids into lane/sublane-broadcast 3-D arrays whose
    blocks satisfy the Mosaic (8, 128) rule: q as [b, sq, LSE_LANES]
    (lane-broadcast), kv as [b, LSE_LANES, sk] (sublane-broadcast)."""
    qs = jnp.broadcast_to(q_seg[:, :, None],
                          (*q_seg.shape, LSE_LANES))
    ks = jnp.broadcast_to(kv_seg[:, None, :],
                          (kv_seg.shape[0], LSE_LANES, kv_seg.shape[1]))
    return qs, ks


def _qseg_spec(block_q, index_map):
    return _block_spec((1, block_q, LSE_LANES), index_map)


def _kseg_spec(block_k, index_map):
    return _block_spec((1, LSE_LANES, block_k), index_map)


def _fwd(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal,
         block_q, block_k, interpret):
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    group = h // h_kv
    nq = sq // block_q
    nk = sk // block_k
    grid = (b, h, nq, nk)
    has_seg = q_seg is not None

    qt = jnp.swapaxes(q, 1, 2)                     # [b, h, sq, d]
    kt = jnp.swapaxes(k, 1, 2)                     # [b, h_kv, sk, d]
    vt = jnp.swapaxes(v, 1, 2)

    q_spec = _block_spec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = _block_spec((1, 1, block_k, d),
                          lambda bi, hi, qi, ki: (bi, hi // group, ki, 0))
    o_spec = q_spec
    lse_spec = _block_spec((1, 1, block_q, LSE_LANES),
                           lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    in_specs = []
    inputs = []
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(seed)
    in_specs += [q_spec, kv_spec, kv_spec]
    inputs += [qt, kt, vt]
    if has_seg:
        qs, ks = _seg_inputs(q_seg, kv_seg)
        in_specs += [
            _qseg_spec(block_q, lambda bi, hi, qi, ki: (bi, qi, 0)),
            _kseg_spec(block_k, lambda bi, hi, qi, ki: (bi, 0, ki))]
        inputs += [qs, ks]

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               has_seg=has_seg, dropout_p=dropout_p,
                               sq=sq, sk=sk, block_q=block_q, block_k=block_k)
    scratch = [pltpu.VMEM((block_q, 128), jnp.float32),
               pltpu.VMEM((block_q, 128), jnp.float32),
               pltpu.VMEM((block_q, d), jnp.float32)]
    out_t, lse4 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[o_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, sq, LSE_LANES), jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=_tpu_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*inputs)
    return jnp.swapaxes(out_t, 1, 2), lse4[..., 0]   # [b,sq,h,d], [b,h,sq]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale, causal, has_seg, dropout_p, sq, sk,
                   block_q, block_k):
    """Grid (b, h, nq, nk): accumulate dq over kv blocks."""
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        qs_ref = ks_ref = None
    bi = pl.program_id(0)
    hi = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    offset = sk - sq
    first_masked_col = qi * block_q + offset + block_q

    @pl.when(jnp.logical_not(causal) | (ki * block_k < first_masked_col))
    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :1]                 # [bq, 1]
        delta = delta_ref[0, 0, :, :1]             # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask_scores(s, causal, qs_ref, ks_ref, qi, ki, offset,
                         block_q, block_k)
        # masked entries exactly zero (a fully-masked row has lse=NEG_INF;
        # exp(NEG_INF - NEG_INF) = 1 would corrupt dq/dk/dv)
        p = jnp.where(s <= NEG_INF * 0.5, 0.0,
                      jnp.exp(s - lse))            # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _dropout_keep(seed_ref, bi, hi, qi, ki, dropout_p,
                                 block_q, block_k, sk)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, has_seg, dropout_p, sq, sk,
                    block_q, block_k, group, nq):
    """Grid (b, h_kv, nk, nq*group): accumulate dk/dv at KV-HEAD resolution.

    The innermost sequential dim enumerates (query-head-in-group, q-block)
    pairs, so the GQA group sum happens in the VMEM accumulator instead of
    as a group-times-larger fp32 intermediate in HBM (round-1 weak item:
    FA2 accumulates at kv-head resolution; flash_attn_kernel.cu)."""
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        qs_ref = ks_ref = None
    bi = pl.program_id(0)
    hkv = pl.program_id(1)
    ki = pl.program_id(2)
    qg = pl.program_id(3)
    nqg = pl.num_programs(3)
    qi = qg % nq          # q-block index (group-major enumeration)
    h = hkv * group + qg // nq   # semantic query head for dropout replay

    @pl.when(qg == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    offset = sk - sq
    # causal: this (ki, qi) pair contributes unless the whole block is
    # masked: masked iff min col in block > max row+offset in block
    max_row = qi * block_q + block_q - 1 + offset

    @pl.when(jnp.logical_not(causal) | (ki * block_k <= max_row))
    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :1]
        delta = delta_ref[0, 0, :, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask_scores(s, causal, qs_ref, ks_ref, qi, ki, offset,
                         block_q, block_k)
        # masked entries exactly zero (a fully-masked row has lse=NEG_INF;
        # exp(NEG_INF - NEG_INF) = 1 would corrupt dq/dk/dv)
        p = jnp.where(s <= NEG_INF * 0.5, 0.0,
                      jnp.exp(s - lse))            # [bq, bk]
        pd = p
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _dropout_keep(seed_ref, bi, h, qi, ki, dropout_p,
                                 block_q, block_k, sk)
            inv = 1.0 / (1.0 - dropout_p)
            pd = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        dv_scr[:] += jax.lax.dot_general(pd.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale              # [bq, bk]
        dk_scr[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(qg == nqg - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(dropout_p, scale, causal, block_q, block_k, interpret, res, dout):
    q, k, v, q_seg, kv_seg, seed, out, lse = res
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    group = h // h_kv
    has_seg = q_seg is not None
    delta = jnp.sum(out.astype(jnp.float32) * dout.astype(jnp.float32),
                    axis=-1)                        # [b, sq, h]
    delta = jnp.moveaxis(delta, -1, 1)              # [b, h, sq]

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    dot = jnp.swapaxes(dout, 1, 2)                  # [b, h, sq, d]
    lse4 = jnp.broadcast_to(lse[..., None], (b, h, sq, LSE_LANES))
    delta4 = jnp.broadcast_to(delta[..., None], (b, h, sq, LSE_LANES))

    nq, nk = sq // block_q, sk // block_k
    q_spec = _block_spec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = _block_spec((1, 1, block_k, d),
                          lambda bi, hi, qi, ki: (bi, hi // group, ki, 0))
    lse_spec = _block_spec((1, 1, block_q, LSE_LANES),
                           lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    dq_inputs = [qt, kt, vt, dot, lse4, delta4]
    dq_specs = [q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec]
    if dropout_p > 0.0:
        dq_inputs.insert(0, seed)
        dq_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_seg:
        qs, ks = _seg_inputs(q_seg, kv_seg)
        dq_specs += [
            _qseg_spec(block_q, lambda bi, hi, qi, ki: (bi, qi, 0)),
            _kseg_spec(block_k, lambda bi, hi, qi, ki: (bi, 0, ki))]
        dq_inputs += [qs, ks]

    dq_t = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          has_seg=has_seg, dropout_p=dropout_p, sq=sq, sk=sk,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, nq, nk),
        in_specs=dq_specs,
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_tpu_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_inputs)[0]

    # dk/dv accumulated at kv-head resolution: grid (b, h_kv, nk, nq*group);
    # the q-head for inner index qg is hkv*group + qg//nq (group-major)
    q_spec2 = _block_spec(
        (1, 1, block_q, d),
        lambda bi, hi, ki, qg: (bi, hi * group + qg // nq, qg % nq, 0))
    kv_spec2 = _block_spec((1, 1, block_k, d),
                           lambda bi, hi, ki, qg: (bi, hi, ki, 0))
    kvout_spec = kv_spec2
    lse_spec2 = _block_spec(
        (1, 1, block_q, LSE_LANES),
        lambda bi, hi, ki, qg: (bi, hi * group + qg // nq, qg % nq, 0))

    dkv_inputs = [qt, kt, vt, dot, lse4, delta4]
    dkv_specs = [q_spec2, kv_spec2, kv_spec2, q_spec2, lse_spec2, lse_spec2]
    if dropout_p > 0.0:
        dkv_inputs.insert(0, seed)
        dkv_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_seg:
        qs, ks = _seg_inputs(q_seg, kv_seg)
        dkv_specs += [
            _qseg_spec(block_q, lambda bi, hi, ki, qg: (bi, qg % nq, 0)),
            _kseg_spec(block_k, lambda bi, hi, ki, qg: (bi, 0, ki))]
        dkv_inputs += [qs, ks]

    dk_t, dv_t = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          has_seg=has_seg, dropout_p=dropout_p, sq=sq, sk=sk,
                          block_q=block_q, block_k=block_k, group=group,
                          nq=nq),
        grid=(b, h_kv, nk, nq * group),
        in_specs=dkv_specs,
        out_specs=[kvout_spec, kvout_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h_kv, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h_kv, sk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_tpu_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*dkv_inputs)

    dq = jnp.swapaxes(dq_t, 1, 2)
    dk = jnp.swapaxes(dk_t, 1, 2)
    dv = jnp.swapaxes(dv_t, 1, 2)

    import numpy as _np
    if has_seg:
        # int cotangents are symbolically zero (float0) in jax
        zseg = (_np.zeros(q_seg.shape, jax.dtypes.float0),
                _np.zeros(kv_seg.shape, jax.dtypes.float0))
    else:
        zseg = (None, None)
    dseed = (_np.zeros(seed.shape, jax.dtypes.float0)
             if seed is not None else None)
    return (dq, dk, dv) + zseg + (dseed,)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_attention(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal,
                     block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal,
                  block_q, block_k, interpret)
    return out


def _flash_fwd_rule(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal,
                    block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal,
                    block_q, block_k, interpret)
    return out, (q, k, v, q_seg, kv_seg, seed, out, lse)


def _flash_bwd_rule(dropout_p, scale, causal, block_q, block_k, interpret,
                    res, dout):
    return _bwd(dropout_p, scale, causal, block_q, block_k, interpret, res,
                dout)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _normalize_segments(segment_ids, b, sq, sk):
    """segment_ids: [b, s] (self-attn) or (q_seg [b, sq], kv_seg [b, sk])."""
    if segment_ids is None:
        return None, None
    if isinstance(segment_ids, (tuple, list)):
        q_seg, kv_seg = segment_ids
    else:
        q_seg = kv_seg = segment_ids
    q_seg = jnp.asarray(q_seg, jnp.int32)
    kv_seg = jnp.asarray(kv_seg, jnp.int32)
    if q_seg.shape != (b, sq) or kv_seg.shape != (b, sk):
        raise ValueError(f"segment_ids shapes {q_seg.shape}/{kv_seg.shape} "
                         f"do not match (b={b}, sq={sq}, sk={sk})")
    return q_seg, kv_seg


def pallas_supported(q, k, v, attn_mask, dropout_p, causal=False,
                     block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                     segment_ids=None, interpret=False) -> bool:
    """Static-shape gate encoding the Mosaic lowering rules for OUR block
    layout (the round-2 failure was selecting configs Mosaic rejects):
    blocks are [1, 1, block, d] over [b, h, s, d] arrays, so block_q/block_k
    need 8-alignment (sublane dim of the q/kv tiles), and when segment ids
    are present block_k additionally needs 128-alignment or to equal sk
    (it is the LANE dim of the kv-segment tile). ``interpret`` relaxes the
    alignment rules (no Mosaic involved) so CPU tests can run small blocks."""
    from ..registry import pallas_disabled
    if not _HAS_PLTPU or pallas_disabled():
        return False
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, sk)
    # causal with sq > sk would leave fully-masked query rows whose
    # online-softmax state never initializes — keep those on the XLA path
    ok = (attn_mask is None
          and 0.0 <= dropout_p < 1.0
          and sq % bq == 0 and sk % bk == 0
          and not (causal and sq > sk)
          and h % h_kv == 0)
    if not ok:
        return False
    if interpret:
        return True
    ok = (bq % 8 == 0 and bk % 8 == 0 and d in (32, 64, 128, 256))
    if ok and segment_ids is not None:
        ok = bk % 128 == 0 or bk == sk
    return ok


def flash_attention_pallas(q, k, v, attn_mask=None, dropout_p: float = 0.0,
                           causal: bool = False, scale: Optional[float] = None,
                           segment_ids=None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: bool = False,
                           dropout_seed=None):
    """TPU flash attention; shapes the static gate (pallas_supported)
    rejects take the XLA path, supported ones compile or fail loudly.

    ``segment_ids`` ([b, s] ints, or a (q_seg, kv_seg) pair) restricts
    attention to equal-id positions — packed-sequence (varlen) and padding
    masking without a dense mask (reference varlen entry:
    flash_attn_kernel.cu:91).

    ``dropout_p`` > 0 runs IN-KERNEL dropout from a counter-based PRNG
    (reference: the philox dropout path of flash_attn_kernel.cu) — the
    O(s^2) keep-mask is regenerated block-wise in VMEM, never stored.
    ``dropout_seed`` (int or int32 array) pins the mask; defaults to the
    framework RNG stream.

    ``block_q``/``block_k`` default to the autotune database's choice for
    this (shape, dtype, device) — see ops/pallas/autotune.py and
    tools/tune_kernels.py (reference: phi/kernels/autotune/cache.h)."""
    from ..attention import _sdpa_xla
    if block_q is None or block_k is None:
        from .autotune import flash_attention_config
        tq, tk = flash_attention_config(q.shape[1], k.shape[1], q.shape[3],
                                        str(q.dtype), causal)
        block_q = block_q if block_q is not None else tq
        block_k = block_k if block_k is not None else tk
    # under a device mesh the kernel runs per shard (per_shard.py): batch
    # over the data axes, heads over "tp" — when both divide
    act = active_axes()
    divides = True
    if act is not None:
        mesh, free, sizes = act
        b_ax, h_ax, nb, nh = qkv_layout(free, sizes)
        divides = not (q.shape[0] % nb or q.shape[2] % nh
                       or k.shape[2] % nh)
    if not divides or not pallas_supported(
            q, k, v, attn_mask, dropout_p, causal, block_q, block_k,
            segment_ids=segment_ids, interpret=interpret):
        if segment_ids is not None:
            # one shared segment->mask fold lives in _sdpa_xla
            segment_ids = _normalize_segments(segment_ids, q.shape[0],
                                              q.shape[1], k.shape[1])
        return _sdpa_xla(q, k, v, attn_mask=attn_mask, dropout_p=dropout_p,
                         causal=causal, scale=scale,
                         segment_ids=segment_ids)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    q_seg, kv_seg = _normalize_segments(segment_ids, q.shape[0], q.shape[1],
                                        k.shape[1])
    seed = None
    if dropout_p > 0.0:
        if dropout_seed is None:
            from ...core.rng import rng_tracker, GLOBAL_STREAM
            key = rng_tracker().next_key(GLOBAL_STREAM)
            seed = jax.random.randint(key, (1,), 0, 2**31 - 1, jnp.int32)
        else:
            seed = jnp.asarray(dropout_seed, jnp.int32).reshape((1,))
    def local(q, k, v, q_seg, kv_seg, seed):
        return _flash_attention(q, k, v, q_seg, kv_seg, seed, dropout_p,
                                scale, causal, bq, bk, interpret)

    if act is None:
        return local(q, k, v, q_seg, kv_seg, seed)
    # one dropout seed for all shards: each draws the same mask pattern
    # over its own rows
    qkv, seg = P(b_ax, None, h_ax, None), P(b_ax, None)
    return per_shard(local, mesh, free, (qkv, qkv, qkv, seg, seg, P(None)),
                     qkv)(q, k, v, q_seg, kv_seg, seed)


@register_kernel("flash_attention", "tpu")
def _flash_attention_tpu(q, k, v, attn_mask=None, dropout_p: float = 0.0,
                         causal: bool = False, scale: Optional[float] = None,
                         segment_ids=None):
    return flash_attention_pallas(q, k, v, attn_mask=attn_mask,
                                  dropout_p=dropout_p, causal=causal,
                                  scale=scale, segment_ids=segment_ids)


# ---------------------------------------------------------------------------
# block-level entry points (building blocks for ring attention — the ring
# composes per-device flash blocks and hand-writes the ring VJP, so it needs
# the raw fwd (with lse) and bwd kernels rather than the custom_vjp wrapper)
# ---------------------------------------------------------------------------

def flash_fwd_block(q, k, v, scale, causal, block_q, block_k,
                    interpret=False, q_seg=None, kv_seg=None):
    """Forward flash block returning (out [b,sq,h,d], lse [b,h,sq]).

    ``q_seg`` [b, sq] / ``kv_seg`` [b, sk] restrict attention to
    equal-id positions (the ring's packed-sequence path); a q row whose
    segment has no match in this kv block comes back with lse=NEG_INF,
    which the ring's normalized merge treats as weight zero."""
    return _fwd(q, k, v, q_seg, kv_seg, None, 0.0, scale, causal,
                block_q, block_k, interpret)


def flash_bwd_block(q, k, v, out, lse, dout, scale, causal, block_q, block_k,
                    interpret=False, q_seg=None, kv_seg=None):
    """Backward flash block given the GLOBAL (out, lse) of the full
    attention (delta = rowsum(out*dout) is computed inside, as FA2 does).
    Returns (dq, dk, dv) for this q/kv block pair."""
    res = (q, k, v, q_seg, kv_seg, None, out, lse)
    outs = _bwd(0.0, scale, causal, block_q, block_k, interpret, res, dout)
    return outs[0], outs[1], outs[2]
