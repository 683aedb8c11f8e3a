"""Pallas TPU paged-KV decode attention (vLLM-style PagedAttention).

Reference analogue: paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu (the paged decode kernel behind
incubate block_multihead_attention). TPU redesign: one Pallas kernel that
leaves the pools in HBM and walks each sequence's LIVE pages itself — the
block table and the lengths sit in SMEM (scalar prefetch), and every live
page is one async copy into a ring of VMEM buffers, issued several chunks
ahead of the products, so the gather never materializes
[B, max_pages*page_size] in HBM (which is what the XLA composition's
jnp.take does) and a call costs what its live K/V bytes cost. Online
softmax (running max/denominator in VMEM scratch) across chunks of pages;
the GQA query-head group is processed together per kv head
([group, d] x [chunk*page, d] MXU contractions).

Pool layout is HEAD-MAJOR: k/v pools are [H_kv, num_pages, page_size, D]
(round-3 fix). Mosaic requires a buffer's last two dims to be
(sublane, lane)-aligned, so a fetched page must be (page_size, D)-shaped
in the trailing dims — the round-2 token-major layout
[num_pages, page_size, H_kv, D] put (H_kv, D) last and was rejected at
lowering for any H_kv > 1. Head-major also lets ``pool[h0:h0+heads, page]``
be ONE strided copy that carries every KV head of a page.

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


def _decode_kernel(*refs, scale, page_size, max_pages, rows, heads, chunk,
                   quant):
    """Grid (B // rows, H_kv // heads); a step walks ITS rows' live pages
    itself. The pools stay in HBM. A work item is one CHUNK of ``chunk``
    consecutive table slots of one row: each live page of it is one
    strided copy ``pool[h0:h0+heads, pid]`` -> ``[heads, page, D]`` of a
    ring of ``n_buf`` VMEM chunk buffers (all KV heads of a page ride in
    one descriptor), a dead page gets no descriptor, and a row has
    ``seq_len // page_size + 1`` live pages and so ``cdiv`` of that many
    items. Two cursors walk the same (row, chunk) sequence: the fetch
    cursor runs ``n_buf - 1`` items ahead of the compute cursor, across
    row boundaries, so a row's first chunk is in flight long before its
    turn.

    One online-softmax update per head and item: one QK^T product over the
    chunk's ``[chunk * page, D]``, the tail masked by position, one PV
    product, float32 scores, state and accumulators (carried by the loop),
    all heads of an item in one basic block so that the scheduler keeps
    several products in flight. Slots of a chunk that were not fetched
    hold what the buffer held before (zeros from the step's start, or an
    older page): their scores are masked, and weight 0 times a finite
    stale value is 0.

    On v5e (tools/tune_kernels.py --paged-decode, PR 32; in brackets the
    kernel this one replaced, which fetched through one BlockSpec a page
    and paid ~0.085 us for each spec of each grid step, live or dead): 128
    rows of 2 KV heads over 24-page tables 0.27 ms a call on ragged
    lengths (0.63) where the live bytes need 0.15, 0.59 on full tables
    (0.78; 0.49); 32 rows of 8 KV heads over 16-page tables 0.20 (0.32;
    0.10) and 0.41 (0.43; 0.33). The copies alone run at ~90% of HBM's
    rate; what is left above the bytes' time is the instruction stream:
    an item's chain of products and softmax stage, ~0.65-0.8 us whatever
    it holds, and ~0.035 us to issue and wait for each descriptor, which
    do not overlap with each other.

    ``quant``: int8 pools with per-page fp32 scales (ISSUE 17). The scale
    arrays ride in as two extra SCALAR-PREFETCH refs (SMEM, indexed by the
    physical page id); int8 K/V pages widen to the query dtype in VMEM
    (int8 is exact in bf16) and the page's scale multiplies the float32
    scores / softmax weights of its columns, where ``paged_decode_xla``
    puts it: no dequantized page anywhere."""
    if quant:
        tables_ref, lens_ref, kscale_ref, vscale_ref, *refs = refs
    else:
        tables_ref, lens_ref, *refs = refs
    q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, ksem, vsem = refs
    group, D = q_ref.shape[2:]
    n_buf = kbuf.shape[0]
    span = chunk * page_size
    row0 = pl.program_id(0) * rows
    row_end = row0 + rows
    h0 = pl.program_id(1) * heads

    def live_pages(b):
        # of a row inside the step; the fetch cursor may stand one past it
        b = jnp.minimum(b, row_end - 1)
        return jnp.clip(lens_ref[b] // page_size + 1, 1, max_pages)

    def advance(b, c):
        last = (c + 1) * chunk >= live_pages(b)
        return jnp.where(last, b + 1, b), jnp.where(last, 0, c + 1)

    def page_id(b, c, i):
        # a stale length may reach past a freed row's mapped slots (-1)
        slot = jnp.minimum(c * chunk + i, max_pages - 1)
        return jnp.maximum(tables_ref[b * max_pages + slot], 0)

    def copies(b, c, slot, do):
        """``do`` on the K and the V copy of every live page of an item."""
        def page(i, _):
            dst = (slot, slice(None),
                   pl.ds(pl.multiple_of(i * page_size, page_size), page_size))
            src = (pl.ds(h0, heads), page_id(b, c, i))
            do(pltpu.make_async_copy(k_hbm.at[src], kbuf.at[dst],
                                     ksem.at[slot]))
            do(pltpu.make_async_copy(v_hbm.at[src], vbuf.at[dst],
                                     vsem.at[slot]))
        jax.lax.fori_loop(
            0, jnp.minimum(live_pages(b) - c * chunk, chunk), page, None)

    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)
    fetch = (row0, jnp.int32(0))
    for slot in range(n_buf - 1):
        pl.when(fetch[0] < row_end)(functools.partial(
            copies, *fetch, slot, lambda cp: cp.start()))
        fetch = advance(*fetch)

    def item(carry):
        w, b, c, fb, fc, m, l, acc = carry

        @pl.when(fb < row_end)
        def _prefetch():        # into the slot the item before this one left
            copies(fb, fc, (w + n_buf - 1) % n_buf, lambda cp: cp.start())

        slot = w % n_buf
        copies(b, c, slot, lambda cp: cp.wait())
        cols = jax.lax.broadcasted_iota(jnp.int32, (group, span), 1)
        valid = c * span + cols <= lens_ref[b]
        k_scale, v_scale = scale, None
        if quant:
            k_scale = jnp.zeros((group, span), jnp.float32)
            v_scale = jnp.zeros((group, span), jnp.float32)
            for i in range(chunk):
                here = cols // page_size == i
                pid = page_id(b, c, i)
                k_scale = jnp.where(here, scale * kscale_ref[pid], k_scale)
                v_scale = jnp.where(here, vscale_ref[pid], v_scale)
        # a row's first item starts from the empty state
        m = jnp.where(c == 0, NEG_INF, m)
        l = jnp.where(c == 0, 0.0, l)
        acc = jnp.where(c == 0, 0.0, acc)
        state = []
        for h in range(heads):
            q = q_ref[b - row0, h]                        # [group, d]
            k, v = kbuf[slot, h], vbuf[slot, h]           # [span, d]
            if quant:                                 # widen int8 in VMEM
                k, v = k.astype(q.dtype), v.astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * k_scale
            s = jnp.where(valid, s, NEG_INF)              # [group, span]
            m_new = jnp.maximum(m[h], jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m[h] - m_new)
            pr = jnp.exp(s - m_new)
            l_new = alpha * l[h] + jnp.sum(pr, axis=-1, keepdims=True)
            if quant:
                pr = pr * v_scale
            pv = jax.lax.dot_general(
                pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            state.append((m_new, l_new, acc[h] * alpha + pv))
        m, l, acc = (jnp.stack(x) for x in zip(*state))
        nb, nc = advance(b, c)

        @pl.when(nb != b)
        def _finalize():
            o_ref[b - row0] = (acc / l).astype(o_ref.dtype)
        return (w + 1, nb, nc, *advance(fb, fc), m, l, acc)

    jax.lax.while_loop(
        lambda carry: carry[1] < row_end, item,
        (jnp.int32(0), row0, jnp.int32(0), *fetch,
         jnp.zeros((heads, group, 1), jnp.float32),
         jnp.zeros((heads, group, 1), jnp.float32),
         jnp.zeros((heads, group, D), jnp.float32)))


# VMEM an item's K and V, and the q and out blocks of a grid step, may take
# (of a scoped 16 MiB on v5e)
_ITEM_BYTES = 2 << 20
_QO_BYTES = 4 << 20
# chunk buffers in the ring: the item being multiplied and two in flight
# (on v5e two read 3% slower than three, and four to eight as three)
_N_BUF = 3


def _walk_shape(B, H_kv, group, D, page_bytes, q_itemsize):
    """(rows, heads, chunk) of a grid step, from what the call can see.
    ``heads``: every KV head a descriptor can carry, up to 8 (the unrolled
    products of an item grow with it). ``chunk``: pages an item multiplies
    together, up to 8, within ``_ITEM_BYTES`` of K and V. What an item costs
    is a chain (product, softmax stage, product) whose latency hardly
    grows with its width: on v5e, 128 rows of 2 KV heads over 24-page
    tables read 0.57 / 0.48 / 0.34 / 0.29 ms a call at 1 / 2 / 4 / 8
    pages an item, 32 rows of 8 KV heads 0.22 / 0.21 / 0.17 at 1 / 2 / 4
    (tools/tune_kernels.py --paged-decode, ragged lengths; PR 32).
    ``rows``: as many rows as keep the q and out blocks (double-buffered,
    the group padded to a sublane tile) inside their budget."""
    heads = max(h for h in (8, 4, 2, 1) if H_kv % h == 0
                and (h == 1 or 2 * h * page_bytes <= _ITEM_BYTES))
    page = 2 * heads * page_bytes                 # K and V of one page
    chunk = max(c for c in (8, 4, 2, 1) if c == 1 or c * page <= _ITEM_BYTES)
    tile = 32 // q_itemsize                       # sublanes of a packed tile
    # q and out, two buffers each
    row = 4 * heads * -(-group // tile) * tile * D * q_itemsize
    rows = max(r for r in range(1, B + 1)
               if B % r == 0 and (r == 1 or r * row <= _QO_BYTES))
    return rows, heads, chunk


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
    rows, heads, chunk = _walk_shape(
        B, H_kv, group, D, page_size * D * k_pages.dtype.itemsize,
        q.dtype.itemsize)
    chunk = min(chunk, max_pages)

    # the table rides flat: a 2-D SMEM array pads its rows to 128 words
    prefetch = (jnp.asarray(block_tables, jnp.int32).reshape(-1),
                jnp.asarray(seq_lens, jnp.int32))
    if quant:
        prefetch += (jnp.asarray(k_scales, jnp.float32),
                     jnp.asarray(v_scales, jnp.float32))
    qo_spec = pl.BlockSpec((rows, heads, group, D),
                           lambda r, h, *prefetch: (r, h, 0, 0))
    ring = (_N_BUF, heads, chunk * page_size, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B // rows, H_kv // heads),
        in_specs=[qo_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=qo_spec,
        scratch_shapes=[pltpu.VMEM(ring, k_pages.dtype),
                        pltpu.VMEM(ring, v_pages.dtype),
                        pltpu.SemaphoreType.DMA((_N_BUF,)),
                        pltpu.SemaphoreType.DMA((_N_BUF,))],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=page_size,
                          max_pages=max_pages, rows=rows, heads=heads,
                          chunk=chunk, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H_kv, group, D), q.dtype),
        compiler_params=_tpu_params(),
        interpret=interpret,
        name="paged_attention_decode",
    )(*prefetch, q.reshape(B, H_kv, group, D), k_pages, v_pages)
    return out.reshape(B, H, D)


def _tpu_params():
    if pltpu is None:
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


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
    readings: the kernel is 3-8x ahead at every context, so by default
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
    """Mosaic-rule gate for the head-major pool layout: a page lands in
    its chunk buffer at a multiple of page_size rows, which must be whole
    sublane tiles of the pool's dtype, and the q/out blocks' trailing
    dims (group, D) equal the arrays', so only divisibility and a sane D
    remain to check."""
    from ..registry import pallas_disabled
    if not _HAS_PLTPU or pallas_disabled():
        return False
    B, H, D = q.shape
    H_kv = k_pages.shape[0]
    page_size = k_pages.shape[2]
    # rows of a packed tile: 8 of float32, 16 of bf16, 32 of int8
    sublane = 32 // k_pages.dtype.itemsize
    return (H % H_kv == 0 and D in (32, 64, 128, 256)
            and page_size % sublane == 0)


__all__ = ["paged_decode_attention", "paged_decode_supported",
           "paged_decode_xla", "force_decode_impl", "forced_decode_impl"]
