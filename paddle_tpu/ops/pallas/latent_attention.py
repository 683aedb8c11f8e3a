"""Pallas TPU decode attention over a paged LATENT cache (MLA, absorbed form).

A latent-attention layer (DeepSeek-V2's MLA, arXiv:2405.04434) caches ONE
row a token: the normalised low-rank latent ``c_kv`` and, behind it, the
rotary key ``k_r`` that every head shares: ``[c_kv | k_r]``, ``W`` wide
(576 = 512 + 64 at GLM-4.7-Flash's sizes, which the model pads with zeros to
640: whole lane tiles). With the up-projection absorbed
into the query (``q~_h = [q_nope_h W_uk,h^T | q_rope_h]``) a cached row is
the key of every head as it stands, and its first ``R`` numbers are every
head's value: attention of ``H`` query heads over rows, no per-head K or V.

So the pool is ``[1, num_pages, page_size, W]`` (the leading 1 keeps pages
on axis 1, where the engine's page copy looks for them) and the kernel
fetches a page ONCE for both uses and for all heads: scores
``q~ [H, W] x page^T``, weights times ``page[:, :R]``. Grid (rows, page
groups); a step streams ``n_fetch`` pages through as many block specs, each
indexed through the scalar-prefetched block table, with one online-softmax
update a step (as ``paged_attention.py``, whose reasons hold here too).

Semantics as ``paged_decode_attention``: positions 0..seq_len INCLUSIVE
(the new token's row was just written at offset seq_len).
"""

from __future__ import annotations

import functools

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


def _kernel(tables_ref, lens_ref, q_ref, *refs, scale, page_size, rank,
            n_fetch):
    page_refs = refs[:n_fetch]
    o_ref = refs[n_fetch]
    m_scr, l_scr, acc_scr = refs[n_fetch + 1:]
    b, pg, npg = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    seq_len = lens_ref[b]
    heads = q_ref.shape[1]

    @pl.when(pg == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # a group wholly past the row's length is skipped (its block index
    # repeats the last live group's, so it costs a grid step and no DMA)
    @pl.when(pg * n_fetch * page_size <= seq_len)
    def _compute():
        q = q_ref[0]                                    # [H, W]
        ss = []
        for i in range(n_fetch):
            rows = page_refs[i][0, 0]                   # [page, W]
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            pos = (pg * n_fetch + i) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (heads, page_size), 1)
            ss.append(jnp.where(pos <= seq_len, s, NEG_INF))
        m_prev = m_scr[:, :1]
        m_new = m_prev
        for s in ss:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1]
        acc = acc_scr[:] * alpha
        for i, s in enumerate(ss):
            vals = page_refs[i][0, 0, :, :rank]         # [page, R]
            pr = jnp.exp(s - m_new)
            l_new = l_new + jnp.sum(pr, axis=-1, keepdims=True)
            acc = acc + jax.lax.dot_general(
                pr.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc

    @pl.when(pg == npg - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _pages_per_step(max_pages: int) -> int:
    """Pages a grid step streams: the most of 8, 4, 2, 1 that divides the
    table (8 blocks of [128, 640] bf16, double-buffered, take 2.6 MB of
    VMEM)."""
    return next(n for n in (8, 4, 2, 1) if max_pages % n == 0)


def latent_decode_attention(q, pages, block_tables, seq_lens, rank: int,
                            scale: float, interpret: bool = False):
    """One decode step of absorbed latent attention over a paged cache.

    q:            [B, H, W] — the new token's absorbed queries
    pages:        [1, num_pages, page_size, W] — rows ``[c_kv | k_r]``
    block_tables: [B, max_pages] int32; logical page i -> pool id
    seq_lens:     [B] int32 tokens already cached (new row at this offset)
    rank:         R: a row's first R numbers are its value

    Returns [B, H, R]: softmax(q rows^T * scale) rows[:, :R].
    """
    B, H, W = q.shape
    _, num_pages, page_size, _ = pages.shape
    max_pages = block_tables.shape[1]
    n_fetch = _pages_per_step(max_pages)
    tables = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    lens = jnp.asarray(seq_lens, jnp.int32)

    def page_spec(i):
        return pl.BlockSpec(
            (1, 1, page_size, W),
            lambda b, pg, tables, lens, i=i: (
                0, tables[b, jnp.minimum(
                    pg, lens[b] // (n_fetch * page_size)) * n_fetch + i],
                0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages // n_fetch),
        in_specs=[pl.BlockSpec((1, H, W), lambda b, pg, *_: (b, 0, 0)),
                  *[page_spec(i) for i in range(n_fetch)]],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, pg, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, 128), jnp.float32),
                        pltpu.VMEM((H, 128), jnp.float32),
                        pltpu.VMEM((H, rank), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_size=page_size,
                          rank=rank, n_fetch=n_fetch),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=(pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
            if not interpret else None),
        interpret=interpret,
        name="latent_attention_decode",
    )(tables, lens, q, *([pages] * n_fetch))


def latent_decode_xla(q, pages, block_tables, seq_lens, rank: int,
                      scale: float):
    """XLA gather composition with the kernel's semantics: its oracle in
    tests, and the path off the TPU. The table span is gathered once, in
    the dtype it is stored in ([B, T, W]); scores, softmax and both
    accumulations are float32."""
    B, H, W = q.shape
    _, num_pages, page_size, _ = pages.shape
    T = block_tables.shape[1] * page_size
    safe = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    lens = jnp.asarray(seq_lens, jnp.int32)
    cd = jnp.promote_types(q.dtype, pages.dtype)
    rows = pages[0][safe].reshape(B, T, W).astype(cd)
    lg = jnp.einsum("bhw,btw->bht", q.astype(cd), rows,
                    preferred_element_type=jnp.float32) * scale
    lg = jnp.where(jnp.arange(T)[None, None, :] <= lens[:, None, None],
                   lg, -jnp.inf)
    p = jax.nn.softmax(lg, axis=-1)
    out = jnp.einsum("bht,btr->bhr", p.astype(cd), rows[..., :rank],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def latent_decode_supported(q, pages, rank: int) -> bool:
    """Mosaic's rules for this layout: every block's trailing dims equal
    the array's, so what is left is a page of whole sublane tiles, rows of
    whole lane tiles and a value slice that ends on one."""
    from ..registry import pallas_disabled
    if not _HAS_PLTPU or pallas_disabled():
        return False
    return (pages.shape[2] % 16 == 0 and rank % 128 == 0
            and pages.shape[3] % 128 == 0)


__all__ = ["latent_decode_attention", "latent_decode_xla",
           "latent_decode_supported"]
