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


def _decode_kernel(*args, scale, page_size, group, heads, n_fetch, quant):
    """Grid (B, H_kv // heads, max_pages // n_fetch); innermost sequential
    over page GROUPS. Each step streams ``n_fetch`` (possibly scattered)
    pages of ``heads`` KV heads via n_fetch independent block specs — one
    page per spec, since a single BlockSpec can only address one pool
    offset; the heads of a page ride in ONE spec as a strided block
    [heads, 1, page, d]. What a call costs on v5e is its specs, ~0.085 us
    for each spec of each step whether or not its block changed (index
    map, compare, DMA issue): a step per (row, KV head) with 16 one-head
    specs ran ~1.1 us a step, live or dead, 0.72-0.85 ms a call at the
    serving shape (B=32, 8 KV heads, 16 pages of 128), a fraction of the
    time its bytes need. Carrying every head in a spec divides the specs
    a call by ``heads``: 0.22-0.43 ms (tools/tune_kernels.py
    --paged-decode).

    One online-softmax update per head and STEP, not per page: the n_fetch
    QK^T products are independent, the running max is taken over all of
    them, and the n_fetch PV products accumulate under that one max — a
    chain of one dependent softmax stage a step where a per-page update
    had n_fetch, all in one basic block so that the scheduler can keep
    several products in flight (a branch per page serializes them:
    measured 2x slower on v5e).

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
        pids = [tables_ref[b, pg * n_fetch + i] if quant else None
                for i in range(n_fetch)]
        valid = [(pg * n_fetch + i) * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (group, page_size), 1) <= seq_len
            for i in range(n_fetch)]
        for h in range(heads):
            q = q_ref[0, h, :, :]                     # [group, d]
            ss = []
            for i in range(n_fetch):
                k = k_refs[i][h, 0, :, :]             # [page, d]
                k_scale = scale
                if quant:
                    k = k.astype(q.dtype)             # widen int8 in VMEM
                    k_scale = scale * kscale_ref[pids[i]]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * k_scale
                ss.append(jnp.where(valid[i], s, NEG_INF))  # [grp, page]
            m_prev = m_scr[h, :, :1]
            m_new = m_prev
            for s in ss:
                m_new = jnp.maximum(m_new,
                                    jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_scr[h, :, :1]
            acc = acc_scr[h] * alpha
            for i, s in enumerate(ss):
                v = v_refs[i][h, 0, :, :]
                if quant:
                    v = v.astype(q.dtype)
                pr = jnp.exp(s - m_new)
                pv = jax.lax.dot_general(
                    pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if quant:
                    pv = pv * vscale_ref[pids[i]]
                l_new = l_new + jnp.sum(pr, axis=-1, keepdims=True)
                acc = acc + pv
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])
            acc_scr[h] = acc
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(pg == npg - 1)
    def _finalize():
        l = l_scr[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


# VMEM the double-buffered K and V page blocks of one grid step may take
_PAGE_BLOCKS_BYTES = 8 << 20


def _step_shape(H_kv, max_pages, page_bytes):
    """(KV heads, pages) a grid step carries: every head a spec can hold
    (up to 8: the unrolled products a step grow with it), then as many
    pages as divide the table, within 32 page blocks a step and the VMEM
    budget."""
    for heads in (8, 4, 2, 1):
        for n_fetch in (8, 4, 2, 1):
            blocks = heads * n_fetch
            if (H_kv % heads == 0 and max_pages % n_fetch == 0
                    and blocks <= 32
                    and 4 * blocks * page_bytes <= _PAGE_BLOCKS_BYTES):
                return heads, n_fetch
    return 1, 1


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
    # KV heads and pages streamed per grid step (divisors of H_kv and of
    # max_pages)
    heads, n_fetch = _step_shape(H_kv, max_pages,
                                 page_size * D * k_pages.dtype.itemsize)

    tables = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    lens = jnp.asarray(seq_lens, jnp.int32)
    qg = q.reshape(B, H_kv, group, D)
    n_pref = 4 if quant else 2

    def page_spec(i):
        # index maps receive all scalar-prefetch refs after the grid ids;
        # the table and the lengths are read (scales are consumed in the
        # kernel body). A group past the row's length asks for the block
        # of the row's LAST live group again: an unchanged block index is
        # not fetched anew, so dead groups cost a grid step and no DMA
        return pl.BlockSpec(
            (heads, 1, page_size, D),
            lambda b, h, pg, tables, lens, *rest, i=i: (
                h, tables[b, jnp.minimum(
                    pg, lens[b] // (n_fetch * page_size)) * n_fetch + i],
                0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,
        grid=(B, H_kv // heads, max_pages // n_fetch),
        in_specs=[
            pl.BlockSpec((1, heads, group, D),
                         lambda b, h, pg, *rest: (b, h, 0, 0)),
            *[page_spec(i) for i in range(n_fetch)],
            *[page_spec(i) for i in range(n_fetch)],
        ],
        out_specs=pl.BlockSpec((1, heads, group, D),
                               lambda b, h, pg, *rest: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((heads, group, 128), jnp.float32),
                        pltpu.VMEM((heads, group, 128), jnp.float32),
                        pltpu.VMEM((heads, group, D), jnp.float32)],
    )
    prefetch = (tables, lens)
    if quant:
        prefetch += (jnp.asarray(k_scales, jnp.float32),
                     jnp.asarray(v_scales, jnp.float32))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=page_size,
                          group=group, heads=heads, n_fetch=n_fetch,
                          quant=quant),
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

    GQA-grouped: the table span is gathered once, in the dtype it is
    stored in and at the KV head count ([H_kv, B, T, D]), and the
    ``group`` query heads of one KV head contract against it together.
    Scores, softmax and both accumulations are float32
    (``preferred_element_type``); K and V are never repeated to the query
    head count nor widened. Int8 pools (``k_scales``/``v_scales``
    [num_pages]) widen to the query dtype for the products (int8 is exact
    in bf16) and the page's scale multiplies the float32 scores / the
    softmax weights — the kernel's epilogue placement."""
    B, H, D = q.shape
    H_kv, num_pages, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    T = max_pages * page_size
    group = H // H_kv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    safe = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    lens = jnp.asarray(seq_lens, jnp.int32)
    quant = k_scales is not None
    cd = q.dtype if quant else jnp.promote_types(q.dtype, k_pages.dtype)

    def per_page(x, pscales):
        # [H_kv, B, group, T] f32 times the scale of the page t lies in
        x = x.reshape(H_kv, B, group, max_pages, page_size)
        x = x * pscales[safe].astype(jnp.float32)[None, :, None, :, None]
        return x.reshape(H_kv, B, group, T)

    def gather(pages):
        # one [page, D] slice per (kv head, row, table slot), in the
        # stored dtype: the result is born [H_kv, B, T, D], where taking
        # pages[:, safe] makes XLA gather table-major and then transpose
        flat = pages.reshape(H_kv * num_pages, page_size, D)
        idx = jnp.arange(H_kv, dtype=jnp.int32)[:, None, None] * num_pages
        return flat[idx + safe[None]].reshape(H_kv, B, T, D)

    ks, vs = gather(k_pages), gather(v_pages)
    qg = jnp.moveaxis(q.reshape(B, H_kv, group, D), 1, 0)
    lg = jnp.einsum("kbgd,kbtd->kbgt", qg.astype(cd), ks.astype(cd),
                    preferred_element_type=jnp.float32) * scale
    if quant:
        lg = per_page(lg, k_scales)
    lg = jnp.where(jnp.arange(T)[None, None, None, :]
                   <= lens[None, :, None, None], lg, -jnp.inf)
    p = jax.nn.softmax(lg, axis=-1)
    if quant:
        p = per_page(p, v_scales)
    out = jnp.einsum("kbgt,kbtd->kbgd", p.astype(cd), vs.astype(cd),
                     preferred_element_type=jnp.float32)
    return jnp.moveaxis(out, 0, 1).reshape(B, H, D).astype(q.dtype)


_FORCED_IMPL = [None]  # None = auto; "dense" | "paged" (context-aware dispatch)


class force_decode_impl:
    """Trace-time override of the paged-decode attention path.

    ``"dense"`` routes decode through the XLA gather composition
    (``paged_decode_xla``: the whole table span, K/V read in the stored
    dtype per KV head), ``"paged"``/None keeps the auto choice (Pallas
    kernel on TPU when supported, which streams mapped pages only). The
    serving engine wraps each decode-block TRACE in this scope to bake
    its choice into the executable (inference/serving.py; crossover from
    autotune.paged_decode_crossover, whose docstring holds the v5e
    readings: the kernel is 3-4.6x ahead at every context, so by default
    an engine never asks for "dense")."""

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
