"""Pallas TPU paged-KV decode attention (vLLM-style PagedAttention).

Reference analogue: paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu (the paged decode kernel behind
incubate block_multihead_attention). TPU redesign: one Pallas kernel whose
grid walks each sequence's pages via a SCALAR-PREFETCHED block table — the
BlockSpec index_map reads the table to stream the right physical page from
HBM into VMEM, so the gather never materializes [B, max_pages*page_size]
in HBM (which is what the XLA composition's jnp.take does). Online softmax
(running max/denominator in VMEM scratch) across pages; the GQA query-head
group is processed together per kv head ([group, d] x [page, d] MXU
contractions).

Pool layout is HEAD-MAJOR: k/v pools are [H_kv, num_pages, page_size, D]
(round-3 fix). Mosaic requires each block's last two dims to be
(sublane, lane)-aligned or equal to the array dims, so the streamed page
block must be (page_size, D)-shaped in the trailing dims — the round-2
token-major layout [num_pages, page_size, H_kv, D] put (H_kv, D) last and
was rejected at lowering for any H_kv > 1. Head-major is also what the
page stream wants: consecutive pages of one kv head are contiguous.

Semantics match incubate.nn.functional.block_multihead_attention: scores
over positions 0..seq_len INCLUSIVE (the new token was just written at
offset seq_len).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except ImportError:  # pragma: no cover
    pltpu = None
    _HAS_PLTPU = False

NEG_INF = -1e30


def _decode_kernel(*args, scale, page_size, group, n_fetch, quant):
    """Grid (B, H_kv, max_pages // n_fetch); innermost sequential over page
    GROUPS. Each step streams ``n_fetch`` (possibly scattered) pages via
    n_fetch independent block specs — one page per spec, since a single
    BlockSpec can only address one pool offset — amortizing the per-step
    grid/DMA-issue overhead that made the one-page-per-step version
    latency-bound (~8us/step measured on v5).

    ``quant``: int8 pools with per-page fp32 scales (ISSUE 17). The scale
    arrays ride in as two extra SCALAR-PREFETCH refs (SMEM, indexed by the
    physical page id the table already prefetches); int8 K/V pages widen
    to the query dtype in VMEM (int8 is exact in bf16) and the page's
    scale multiplies the f32 scores / weighted-V accumulator — the same
    epilogue placement as int8_matmul's _kernel, so the fused dequant
    costs one scalar multiply per page, not a dequantized page in HBM."""
    if quant:
        tables_ref, lens_ref, kscale_ref, vscale_ref, q_ref = args[:5]
        refs = args[5:]
    else:
        tables_ref, lens_ref, q_ref = args[:3]
        refs = args[3:]
        kscale_ref = vscale_ref = None
    k_refs = refs[:n_fetch]
    v_refs = refs[n_fetch:2 * n_fetch]
    o_ref = refs[2 * n_fetch]
    m_scr, l_scr, acc_scr = refs[2 * n_fetch + 1:]
    b = pl.program_id(0)
    pg = pl.program_id(2)
    npg = pl.num_programs(2)
    seq_len = lens_ref[b]

    @pl.when(pg == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # group fully past the sequence (and unmapped table slots) is skipped
    @pl.when(pg * n_fetch * page_size <= seq_len)
    def _compute():
        q = q_ref[0, 0, :, :]                     # [group, d]
        for i in range(n_fetch):
            p = pg * n_fetch + i
            k = k_refs[i][0, 0, :, :]             # [page, d]
            v = v_refs[i][0, 0, :, :]
            k_scale = scale
            if quant:
                pid = tables_ref[b, p]
                k = k.astype(q.dtype)             # widen int8 in VMEM
                v = v.astype(q.dtype)
                k_scale = scale * kscale_ref[pid]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * k_scale  # [grp, page]
            pos = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(pos <= seq_len, s, NEG_INF)
            m_prev = m_scr[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(s - m_new)
            l_scr[:] = jnp.broadcast_to(
                alpha * l_scr[:, :1] + jnp.sum(pr, axis=-1, keepdims=True),
                l_scr.shape)
            pv = jax.lax.dot_general(
                pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if quant:
                pv = pv * vscale_ref[pid]
            acc_scr[:] = acc_scr[:] * alpha + pv
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(pg == npg - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens,
                           scale: Optional[float] = None,
                           k_scales=None, v_scales=None,
                           interpret: bool = False):
    """One decode step of attention over a paged KV cache.

    q:            [B, H, D] — the new token's queries
    k/v_pages:    [H_kv, num_pages, page_size, D] head-major block pools
    block_tables: [B, max_pages] int32; logical page i -> pool id (-1 unused)
    seq_lens:     [B] int32 tokens already cached (new token at this offset)
    k/v_scales:   [num_pages] fp32 per-page dequant scales for int8 pools
                  (both or neither; ISSUE 17)

    Returns [B, H, D].
    """
    B, H, D = q.shape
    H_kv, num_pages, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    group = H // H_kv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    quant = k_scales is not None
    if quant != (v_scales is not None):
        raise ValueError("k_scales and v_scales must be given together")
    # pages streamed per grid step (divisor of max_pages)
    n_fetch = next((n for n in (8, 4, 2, 1) if max_pages % n == 0), 1)

    tables = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    lens = jnp.asarray(seq_lens, jnp.int32)
    qg = q.reshape(B, H_kv, group, D)
    n_pref = 4 if quant else 2

    def page_spec(i):
        # index maps receive all scalar-prefetch refs after the grid ids;
        # only the table is read (scales are consumed in the kernel body)
        return pl.BlockSpec(
            (1, 1, page_size, D),
            lambda b, h, pg, tables, *rest, i=i: (
                h, tables[b, pg * n_fetch + i], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,
        grid=(B, H_kv, max_pages // n_fetch),
        in_specs=[
            pl.BlockSpec((1, 1, group, D),
                         lambda b, h, pg, *rest: (b, h, 0, 0)),
            *[page_spec(i) for i in range(n_fetch)],
            *[page_spec(i) for i in range(n_fetch)],
        ],
        out_specs=pl.BlockSpec((1, 1, group, D),
                               lambda b, h, pg, *rest: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((group, 128), jnp.float32),
                        pltpu.VMEM((group, 128), jnp.float32),
                        pltpu.VMEM((group, D), jnp.float32)],
    )
    prefetch = (tables, lens)
    if quant:
        prefetch += (jnp.asarray(k_scales, jnp.float32),
                     jnp.asarray(v_scales, jnp.float32))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=page_size,
                          group=group, n_fetch=n_fetch, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H_kv, group, D), q.dtype),
        compiler_params=_tpu_params(),
        interpret=interpret,
        name="paged_attention_decode",
    )(*prefetch, qg, *([k_pages] * n_fetch), *([v_pages] * n_fetch))
    return out.reshape(B, H, D)


def _tpu_params():
    if pltpu is None:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def paged_decode_xla(q, k_pages, v_pages, block_tables, seq_lens,
                     scale: Optional[float] = None,
                     k_scales=None, v_scales=None):
    """XLA gather composition with identical semantics to the kernel —
    the fallback for unsupported shapes/backends and the test oracle.
    Int8 pools (``k_scales``/``v_scales`` [num_pages]) dequantize in the
    gather: convert + per-page scale."""
    B, H, D = q.shape
    H_kv, _, page_size, _ = k_pages.shape
    T = block_tables.shape[1] * page_size
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    safe = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)

    def gather(pages, pscales):
        g = pages[:, safe]                    # [H_kv, B, mp, page, D]
        if pscales is not None:
            g = (g.astype(jnp.float32)
                 * pscales[safe][None, :, :, None, None])
        return jnp.moveaxis(g.reshape(H_kv, B, T, D), 0, 2)
    ks = gather(k_pages, k_scales)
    vs = gather(v_pages, v_scales)
    ks = jnp.repeat(ks, H // H_kv, axis=2)
    vs = jnp.repeat(vs, H // H_kv, axis=2)
    lens = jnp.asarray(seq_lens, jnp.int32)
    lg = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                    ks.astype(jnp.float32)) * scale
    lg = jnp.where(jnp.arange(T)[None, None, :] <= lens[:, None, None],
                   lg, -jnp.inf)
    p = jax.nn.softmax(lg, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p, vs.astype(jnp.float32))
    return out.astype(q.dtype)


_FORCED_IMPL = [None]  # None = auto; "dense" | "paged" (context-aware dispatch)


class force_decode_impl:
    """Trace-time override of the paged-decode attention path.

    ``"dense"`` routes decode through the XLA gather composition
    (``paged_decode_xla`` — the dense contiguous-attention cost profile),
    ``"paged"``/None keeps the auto choice (Pallas kernel on TPU when
    supported). The serving engine wraps each decode-block TRACE in this
    scope to bake the measured dense/paged crossover into the executable
    (inference/serving.py; crossover from autotune.paged_decode_crossover):
    the bench sweep shows dense ahead at short contexts and the paged
    kernel 1.45-3.6x ahead at 8K-16K, so one static choice per compiled
    block is exactly the right granularity."""

    def __init__(self, impl):
        if impl not in (None, "dense", "paged"):
            raise ValueError(f"impl must be None|'dense'|'paged', "
                             f"got {impl!r}")
        self.impl = impl

    def __enter__(self):
        _FORCED_IMPL.append(self.impl)
        return self

    def __exit__(self, *exc):
        _FORCED_IMPL.pop()
        return False


def forced_decode_impl():
    return _FORCED_IMPL[-1]


def paged_decode_supported(q, k_pages) -> bool:
    """Mosaic-rule gate for the head-major pool layout: page blocks are
    (1, 1, page_size, D) == the trailing array dims, and the q/out blocks
    are (1, 1, group, D) == theirs, so only divisibility and a sane D
    remain to check."""
    from ..registry import pallas_disabled
    if not _HAS_PLTPU or pallas_disabled():
        return False
    B, H, D = q.shape
    H_kv = k_pages.shape[0]
    page_size = k_pages.shape[2]
    # int8 pages need the int8 sublane multiple (32); floats need 8
    sublane = 32 if k_pages.dtype == jnp.int8 else 8
    return (H % H_kv == 0 and D in (32, 64, 128, 256)
            and page_size % sublane == 0)


__all__ = ["paged_decode_attention", "paged_decode_supported",
           "paged_decode_xla", "force_decode_impl", "forced_decode_impl"]
