"""Fused vocab-projection + cross-entropy loss head (logits never exist).

Reference analogue: c_softmax_with_cross_entropy_op.cu (the reference fuses
the softmax+CE over model-parallel-sharded logits); prior art for the FULL
fusion — projection INCLUDED — is Liger-kernel's fused_linear_cross_entropy
and Apple's Cut Cross-Entropy. At Llama-3's 128K vocab the fp32 logits
tensor ``[B, S, V]`` is the single largest activation of a training step
(B*S*128256*4 bytes); even the tensor-parallel CE path only shards it. This
module computes

    loss = CE(hidden @ W, labels)

blockwise over the vocab dimension so the logits tensor NEVER materializes:
peak loss-head memory drops from O(N*V) to O(N*block_v) with N = B*S (the
Pallas backward: a bounded slab of blocks, below).

Design:

- The primitive is ``lse_and_target(hidden, w, labels) -> (lse, tgt)``:
  per-row log-sum-exp of the logits and the logit at the label (0 when the
  label is outside ``[0, V)`` — which encodes both ignore_index and a TP
  shard's out-of-range labels with one rule). ``nll = lse - tgt``; any
  reduction/weighting composes outside, and the TP composition in
  parallel/mp_layers.py combines per-shard (lse, tgt) with pmax/psum.
- Forward: online log-sum-exp over vocab blocks (running max m, running
  denominator s — the flash-attention recurrence applied to the class dim)
  plus a masked target-logit accumulation, fp32 throughout.
- Backward (custom_vjp): RECOMPUTES each block's logits from the saved
  per-row lse — softmax p = exp(logits - lse) — forms the block's logits
  cotangent ``dlog = g_lse * p + g_tgt * onehot`` ONCE and feeds both
  ``dhidden += dlog @ W_j^T`` and ``dW_j = hidden^T @ dlog`` from it: three
  products, four a step with the forward's, one more than a backward that
  kept the logits.
- Two interchangeable implementations behind one numerics contract:
  a Pallas TPU kernel set (forward; dhidden; dW — each streaming vocab
  blocks through VMEM with fp32 accumulators) and a pure-XLA ``lax.scan``
  over vocab blocks that keeps the same O(block) memory on CPU/GPU and is
  the test oracle. ``ops/pallas/autotune.py`` picks block sizes
  (TuneDB-consulted like flash_attention).
- The Pallas backward hands ``dlog`` from the dhidden kernel to the dW
  kernel through HBM, rounded to W's dtype as both products take it, ONE
  SLAB of vocab blocks at a time (all rows of ``per_slab * block_v``
  columns, at most SLAB_BYTES: all of it would be the logits tensor again).
  One ``lax.scan`` runs the two kernels a slab. What the slabs cost: a
  slab's dW columns are finished by one call, but dhidden sums over ALL
  columns, so it crosses slabs as a float32 [N, H] array the dhidden kernel
  reads and writes in place (``input_output_aliases``) once a slab; the
  slab itself is written once and read once; a split that is not even
  leaves dead grid steps in the last slab (no product, no fetch). Peak
  memory is O(N * block_v * per_slab) + one float32 [N, H].

Vocab not divisible by the block size: W is padded to the block multiple
and padded columns are masked to NEG_INF inside the kernels (their softmax
weight is exactly 0 in the backward recompute).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from .per_shard import active_axes, batch_spec, per_shard, shards

try:  # pltpu only imports cleanly on TPU-enabled jaxlib builds
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except ImportError:  # pragma: no cover
    pltpu = None
    _HAS_PLTPU = False

from ..registry import register_kernel

NEG_INF = -1e30  # large-negative instead of -inf: avoids inf-inf=nan in exp
LANES = 8        # lane width for per-row scalars (lse/tgt/labels tiles)


# Scoped-VMEM limit handed to Mosaic for all three kernels. The compiler's
# default (16 MiB on v5e) is below what the backward kernels allocate at
# real widths — 19.55 MiB at (H=4096, bn=256, bv=256), 20.00 MiB at
# (H=1536, bn=256, bv=1024) — so the limit is explicit, and the block
# chooser and support gate budget against it (kernel_vmem_bytes).
VMEM_LIMIT = 48 * 2 ** 20
# what kernel_vmem_bytes may reach: two thirds of the limit, the rest is
# Mosaic's own (relayouts, spills) that the estimate does not itemize
VMEM_BUDGET = 32 * 2 ** 20
# what one slab of stored logit cotangents may take in HBM (the backward
# keeps ONE alive): a few thousand vocab columns of all rows at a training
# step's row count, the whole vocabulary at a small one. On a v5e at OLMoE's
# shape 256, 384 and 512 MiB read the same kernel times (PERF.md section 6,
# PR 35: the sum that crosses slabs hides in the pipeline), so the least
SLAB_BYTES = 256 * 2 ** 20


def _tpu_params(*semantics):
    if pltpu is None:
        return None
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=VMEM_LIMIT)


def _block_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _pad_vocab(w, block_v: int):
    """Pad W's vocab (last) dim up to a block multiple; padded columns are
    masked in-kernel so they contribute exactly 0."""
    v = w.shape[-1]
    vp = -(-v // block_v) * block_v
    if vp == v:
        return w
    return jnp.pad(w, ((0, 0), (0, vp - v)))


# ---------------------------------------------------------------------------
# XLA fallback: lax.scan over vocab blocks (same O(block_v) memory)
# ---------------------------------------------------------------------------

def _fwd_xla(h, w, labels, block_v):
    n, hd = h.shape
    v = w.shape[1]
    wp = _pad_vocab(w, block_v)
    nb = wp.shape[1] // block_v

    def body(carry, j):
        m, s, t = carry
        wj = jax.lax.dynamic_slice(wp, (0, j * block_v), (hd, block_v))
        logits = jax.lax.dot_general(
            h, wj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [n, block_v]
        cols = j * block_v + jnp.arange(block_v, dtype=jnp.int32)[None, :]
        logits = jnp.where(cols < v, logits, NEG_INF)
        t = t + jnp.sum(jnp.where(cols == labels[:, None], logits, 0.0), -1)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.where(logits <= NEG_INF * 0.5, 0.0,
                      jnp.exp(logits - m_new[:, None]))
        s = s * jnp.exp(m - m_new) + jnp.sum(p, axis=-1)
        return (m_new, s, t), None

    carry = (jnp.full((n,), NEG_INF, jnp.float32),
             jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    (m, s, t), _ = jax.lax.scan(body, carry,
                                jnp.arange(nb, dtype=jnp.int32))
    safe = jnp.where(s == 0.0, 1.0, s)
    return m + jnp.log(safe), t


def _bwd_xla(h, w, labels, lse, g_lse, g_tgt, block_v):
    n, hd = h.shape
    v = w.shape[1]
    wp = _pad_vocab(w, block_v)
    nb = wp.shape[1] // block_v

    def body(dh, j):
        wj = jax.lax.dynamic_slice(wp, (0, j * block_v), (hd, block_v))
        logits = jax.lax.dot_general(
            h, wj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        cols = j * block_v + jnp.arange(block_v, dtype=jnp.int32)[None, :]
        logits = jnp.where(cols < v, logits, NEG_INF)
        p = jnp.where(logits <= NEG_INF * 0.5, 0.0,
                      jnp.exp(logits - lse[:, None]))
        dlog = g_lse[:, None] * p \
            + jnp.where(cols == labels[:, None], g_tgt[:, None], 0.0)
        dh = dh + jax.lax.dot_general(
            dlog, wj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dwj = jax.lax.dot_general(
            h, dlog, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [hd, block_v]
        return dh, dwj.astype(w.dtype)

    dh0 = jnp.zeros((n, hd), jnp.float32)
    dh, dw_blocks = jax.lax.scan(body, dh0,
                                 jnp.arange(nb, dtype=jnp.int32))
    dw = jnp.moveaxis(dw_blocks, 0, 1).reshape(hd, nb * block_v)[:, :v]
    return dh.astype(h.dtype), dw


# ---------------------------------------------------------------------------
# Pallas TPU kernels
# ---------------------------------------------------------------------------

def _lift_rows(x, dtype):
    """[n] per-row scalars -> lane-broadcast [n, LANES] tiles (Mosaic wants
    the last block dim aligned or equal to the array dim)."""
    return jnp.broadcast_to(jnp.asarray(x, dtype)[:, None],
                            (x.shape[0], LANES))


def _fwd_kernel(h_ref, w_ref, lab_ref, lse_ref, tgt_ref, m_scr, s_scr, t_scr,
                *, vocab, block_v):
    """Grid (nN, nV) — nV innermost/sequential; scratch carries the online
    log-sum-exp state (m, s) and the target-logit accumulator across it."""
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        s_scr[:] = jnp.zeros_like(s_scr)
        t_scr[:] = jnp.zeros_like(t_scr)

    h = h_ref[...]
    wb = w_ref[...]
    logits = jax.lax.dot_general(
        h, wb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bn, bv]
    cols = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(cols < vocab, logits, NEG_INF)
    lab = lab_ref[:, :1]                                 # [bn, 1]
    t_new = t_scr[:, :1] + jnp.sum(
        jnp.where(cols == lab, logits, 0.0), axis=-1, keepdims=True)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.where(logits <= NEG_INF * 0.5, 0.0, jnp.exp(logits - m_new))
    s_new = jnp.exp(m_prev - m_new) * s_scr[:, :1] \
        + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    s_scr[:] = jnp.broadcast_to(s_new, s_scr.shape)
    t_scr[:] = jnp.broadcast_to(t_new, t_scr.shape)

    @pl.when(vi == nv - 1)
    def _finalize():
        s = s_scr[:, :1]
        safe = jnp.where(s == 0.0, 1.0, s)
        lse_ref[...] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(safe),
                                        lse_ref.shape)
        tgt_ref[...] = jnp.broadcast_to(t_scr[:, :1], tgt_ref.shape)


def _fwd_pallas(h, w, labels, block_n, block_v, interpret):
    n, hd = h.shape
    v = w.shape[1]
    wp = _pad_vocab(w, block_v)
    nb = wp.shape[1] // block_v
    nn = n // block_n
    lab2 = _lift_rows(labels, jnp.int32)

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, vocab=v, block_v=block_v),
        grid=(nn, nb),
        in_specs=[
            _block_spec((block_n, hd), lambda ni, vi: (ni, 0)),
            _block_spec((hd, block_v), lambda ni, vi: (0, vi)),
            _block_spec((block_n, LANES), lambda ni, vi: (ni, 0)),
        ],
        out_specs=[_block_spec((block_n, LANES), lambda ni, vi: (ni, 0)),
                   _block_spec((block_n, LANES), lambda ni, vi: (ni, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((n, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_n, 128), jnp.float32),
                        pltpu.VMEM((block_n, 128), jnp.float32),
                        pltpu.VMEM((block_n, 128), jnp.float32)],
        compiler_params=_tpu_params("parallel", "arbitrary"),
        interpret=interpret,
        name="fused_vocab_ce_fwd",
    )(h, wp, lab2)
    return out[0][:, 0], out[1][:, 0]


def _slab_block(slab_ref, vi, per_slab, n_blocks):
    """The vocab block a slab's ``vi``-th step stands for, and whether it
    exists: the last slab of a ragged split runs past the last block."""
    vb = slab_ref[0] * per_slab + vi
    return vb, vb < n_blocks


def _bwd_dh_kernel(slab_ref, h_ref, w_ref, lab_ref, lse_ref, glse_ref,
                   gtgt_ref, dh_in_ref, dh_ref, dlog_ref, *, vocab, block_v,
                   per_slab, n_blocks):
    """Grid (nN, per_slab) over ONE slab of vocab blocks: recompute a block's
    softmax from the saved lse, form the logits cotangent
    ``dlog = g_lse * p + g_tgt * onehot`` ONCE, write it out (rounded as the
    products take it) for the dW kernel and add ``dlog @ W_j^T`` to the
    float32 dhidden the slabs before this one left (aliased in and out)."""
    vi = pl.program_id(1)
    vb, live = _slab_block(slab_ref, vi, per_slab, n_blocks)

    @pl.when(vi == 0)
    def _carry_in():
        dh_ref[...] = dh_in_ref[...]

    @pl.when(live)
    def _block():
        wb = w_ref[...]
        logits = jax.lax.dot_general(
            h_ref[...], wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        cols = vb * block_v + jax.lax.broadcasted_iota(jnp.int32,
                                                       logits.shape, 1)
        logits = jnp.where(cols < vocab, logits, NEG_INF)
        p = jnp.where(logits <= NEG_INF * 0.5, 0.0,
                      jnp.exp(logits - lse_ref[:, :1]))
        dlog = (glse_ref[:, :1] * p
                + jnp.where(cols == lab_ref[:, :1], gtgt_ref[:, :1], 0.0)
                ).astype(wb.dtype)
        dlog_ref[...] = dlog
        dh_ref[...] += jax.lax.dot_general(
            dlog, wb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def _bwd_dw_kernel(slab_ref, h_ref, dlog_ref, dw_in_ref, dw_ref, acc_scr, *,
                   per_slab, n_blocks):
    """Grid (per_slab, nN): one product a step, ``h^T @ dlog`` over the rows
    of a stored slab, into a [hd, block_v] float32 accumulator; the slab's
    columns of dW are written into the array the slabs before it filled
    (``dw_in_ref``: the same buffer, aliased, never read)."""
    del dw_in_ref
    ni = pl.program_id(1)
    _, live = _slab_block(slab_ref, pl.program_id(0), per_slab, n_blocks)

    @pl.when(live)                 # a dead step leaves the last block be
    def _block():
        @pl.when(ni == 0)
        def _init():
            acc_scr[:] = jnp.zeros_like(acc_scr)

        acc_scr[:] += jax.lax.dot_general(
            h_ref[...], dlog_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(ni == pl.num_programs(1) - 1)
        def _finalize():
            dw_ref[...] = acc_scr[:].astype(dw_ref.dtype)


def _bwd_pallas(h, w, labels, lse, g_lse, g_tgt, block_n, block_v, interpret):
    """One ``lax.scan`` over slabs of vocab blocks: the dhidden kernel leaves
    a slab of ``dlog`` [n, per_slab * block_v] behind, the dW kernel reads it.
    Both results are carried through the scan and written in place: dW a
    slab's columns a call, dhidden as a float32 sum over the slabs."""
    n, hd = h.shape
    v = w.shape[1]
    wp = _pad_vocab(w, block_v)
    itemsize = w.dtype.itemsize
    n_blocks = wp.shape[1] // block_v
    per_slab = slab_blocks(n, block_v, itemsize, n_blocks)
    dw_n = dw_block_n(n, block_n, block_v, hd, itemsize)
    lab2 = _lift_rows(labels, jnp.int32)
    lse2 = _lift_rows(lse, jnp.float32)
    glse2 = _lift_rows(g_lse, jnp.float32)
    gtgt2 = _lift_rows(g_tgt, jnp.float32)
    statics = dict(per_slab=per_slab, n_blocks=n_blocks)

    # the last slab of a ragged split runs past the last vocab block: a dead
    # step stays on a block already in VMEM (no fetch, no write-back)
    def vocab_block(slab, vi):
        return jnp.minimum(_slab_block(slab, vi, **statics)[0], n_blocks - 1)

    def live_row(slab, vi, ni):
        return jnp.where(_slab_block(slab, vi, **statics)[1], ni, 0)

    row = lambda ni, vi, slab: (ni, 0)
    rows_hd = _block_spec((block_n, hd), row)
    rows_lanes = _block_spec((block_n, LANES), row)
    dh_call = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, vocab=v, block_v=block_v,
                          **statics),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block_n, per_slab),
            in_specs=[rows_hd,
                      _block_spec((hd, block_v), lambda ni, vi, slab:
                                  (0, vocab_block(slab, vi))),
                      rows_lanes, rows_lanes, rows_lanes, rows_lanes,
                      rows_hd],
            out_specs=[rows_hd,
                       _block_spec((block_n, block_v),
                                   lambda ni, vi, slab: (ni, vi))]),
        out_shape=[jax.ShapeDtypeStruct((n, hd), jnp.float32),
                   jax.ShapeDtypeStruct((n, per_slab * block_v), w.dtype)],
        input_output_aliases={7: 0},
        compiler_params=_tpu_params("parallel", "arbitrary"),
        interpret=interpret,
        name="fused_vocab_ce_bwd_dh",
    )
    # dW: grid transposed (vocab blocks parallel, rows sequential) so the
    # [hd, block_v] fp32 accumulator lives in VMEM across the row sweep
    dw_call = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, **statics),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(per_slab, n // dw_n),
            in_specs=[_block_spec((dw_n, hd), lambda vi, ni, slab:
                                  (live_row(slab, vi, ni), 0)),
                      _block_spec((dw_n, block_v), lambda vi, ni, slab:
                                  (live_row(slab, vi, ni), vi)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=_block_spec((hd, block_v), lambda vi, ni, slab:
                                  (0, vocab_block(slab, vi))),
            scratch_shapes=[pltpu.VMEM((hd, block_v), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(wp.shape, w.dtype),
        input_output_aliases={3: 0},
        compiler_params=_tpu_params("parallel", "arbitrary"),
        interpret=interpret,
        name="fused_vocab_ce_bwd_dw",
    )

    def slab_step(carry, slab):
        dh, dw = carry
        dh, dlog = dh_call(slab, h, wp, lab2, lse2, glse2, gtgt2, dh)
        return (dh, dw_call(slab, h, dlog, dw)), None

    (dh, dw), _ = jax.lax.scan(
        slab_step,
        (jnp.zeros((n, hd), jnp.float32), jnp.zeros(wp.shape, w.dtype)),
        jnp.arange(-(-n_blocks // per_slab), dtype=jnp.int32)[:, None])
    return dh.astype(h.dtype), dw[:, :v]


# ---------------------------------------------------------------------------
# custom_vjp primitive
# ---------------------------------------------------------------------------

def _fwd_impl(h, w, labels, block_n, block_v, impl, interpret):
    if impl == "pallas":
        return _fwd_pallas(h, w, labels, block_n, block_v, interpret)
    return _fwd_xla(h, w, labels, block_v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def lse_and_target(h, w, labels, block_n=128, block_v=512, impl="xla",
                   interpret=False):
    """Per-row (logsumexp(h @ w), logit-at-label) over vocab blocks.

    h: [N, H]; w: [H, V]; labels: [N] int32 — a label outside ``[0, V)``
    contributes 0 to ``tgt`` (encodes ignore_index and TP-shard-local
    out-of-range labels). Returns (lse [N] f32, tgt [N] f32); the logits
    tensor is never materialized, in either the forward or the recompute
    backward."""
    return _fwd_impl(h, w, labels, block_n, block_v, impl, interpret)


def _lse_fwd_rule(h, w, labels, block_n, block_v, impl, interpret):
    lse, tgt = _fwd_impl(h, w, labels, block_n, block_v, impl, interpret)
    return (lse, tgt), (h, w, labels, lse)


def _lse_bwd_rule(block_n, block_v, impl, interpret, res, g):
    h, w, labels, lse = res
    g_lse, g_tgt = g
    if impl == "pallas":
        dh, dw = _bwd_pallas(h, w, labels, lse, g_lse, g_tgt,
                             block_n, block_v, interpret)
    else:
        dh, dw = _bwd_xla(h, w, labels, lse, g_lse, g_tgt, block_v)
    # int labels: symbolically-zero (float0) cotangent
    dlab = np.zeros(labels.shape, jax.dtypes.float0)
    return dh, dw, dlab


lse_and_target.defvjp(_lse_fwd_rule, _lse_bwd_rule)


# ---------------------------------------------------------------------------
# support gates + public entry
# ---------------------------------------------------------------------------

def _dh_vmem_bytes(block_n, block_v, hd, itemsize) -> int:
    """The dhidden kernel: h and W blocks, the [block_n, block_v] fp32
    temporaries of the softmax recompute, the dlog block it writes out, the
    per-row scalar tiles padded to 128 lanes, and the fp32 dhidden block in
    and out plus the product added to it."""
    io = 2 * (block_n * hd + hd * block_v) * itemsize   # h + W blocks
    temps = 4 * block_n * block_v * 4         # logits, p, dlog, column iota
    rows = 6 * 2 * block_n * 128 * 4          # labels/lse/g_lse/g_tgt tiles
    dlog = 2 * block_n * block_v * itemsize
    dh = 5 * block_n * hd * 4
    return io + temps + rows + dlog + dh


def _dw_vmem_bytes(block_n, block_v, hd, itemsize) -> int:
    """The dW kernel: h and dlog blocks (h once more, transposed for the
    product), the fp32 [hd, block_v] accumulator plus the product added to
    it, and the dW block it writes."""
    io = (3 * block_n * hd + 2 * block_n * block_v) * itemsize
    return io + 2 * hd * block_v * 4 + 2 * hd * block_v * itemsize


def kernel_vmem_bytes(block_n, block_v, hd, itemsize) -> int:
    """Upper bound on the scoped VMEM Mosaic allocates for one
    (block_n, block_v) config, over the two backward kernels (the forward
    holds strictly less than the dhidden kernel). Counts what the compiler
    holds, not just what the kernel names: every blocked operand and output
    TWICE (the pipeline double-buffers them) and a matmul's result beside
    the accumulator it is added to. The ONE formula shared by the support
    gate, the default block chooser and the dW kernel's row block, so the
    chooser never picks what the gate rejects."""
    return max(_dh_vmem_bytes(block_n, block_v, hd, itemsize),
               _dw_vmem_bytes(block_n, block_v, hd, itemsize))


def dw_block_n(n, block_n, block_v, hd, itemsize) -> int:
    """Row block of the dW kernel: it holds no softmax temporaries, so it
    sweeps the rows in the largest multiple of ``block_n`` (up to 4x) that
    divides N and fits the same budget — fewer, longer products a vocab
    block."""
    return next((m * block_n for m in (4, 2)
                 if n % (m * block_n) == 0
                 and _dw_vmem_bytes(m * block_n, block_v, hd, itemsize)
                 <= VMEM_BUDGET), block_n)


def slab_blocks(n, block_v, itemsize, n_blocks) -> int:
    """Vocab blocks a slab of the backward: as many as keep the stored dlog
    slab [N, blocks * block_v] within SLAB_BYTES, evened out over the slabs
    that takes (the gate refuses a shape of which not one block fits)."""
    fit = SLAB_BYTES // (n * block_v * itemsize)
    n_slabs = -(-n_blocks // fit)
    return -(-n_blocks // n_slabs)


def default_blocks(n, hd, dtype_str) -> Tuple[Optional[int], int]:
    """VMEM-fitting (block_n, block_v) defaults: the largest row block
    dividing N (None → no Pallas), then the largest 128-multiple vocab
    block that keeps the shared estimate under budget, shrinking the row
    block if even bv=128 won't fit."""
    itemsize = {"float32": 4}.get(dtype_str, 2)
    for bn in (256, 128, 64, 32, 16, 8):
        if n % bn:
            continue
        bv = next((c for c in (2048, 1024, 512, 256, 128)
                   if kernel_vmem_bytes(bn, c, hd, itemsize)
                   <= VMEM_BUDGET), None)
        if bv is not None:
            return bn, bv
    return None, 512


def fused_ce_supported(n, hd, v, dtype, block_n, block_v,
                       interpret=False) -> bool:
    """Static gate encoding the Mosaic lowering rules for this block
    layout: row blocks are [block_n, H] (H is the full lane dim), vocab
    blocks [H, block_v]; the dhidden kernel's fp32 [block_n, H] blocks or
    the dW kernel's fp32 [H, block_v] accumulator pace VMEM, whichever is
    larger. One vocab block of ALL rows must fit the backward's dlog slab.
    ``interpret`` relaxes alignment so CPU tests can run tiny blocks."""
    from ..registry import pallas_disabled
    if not _HAS_PLTPU or pallas_disabled():
        return False
    if block_n is None or block_v is None:
        return False
    itemsize = jnp.dtype(dtype).itemsize
    if n % block_n or n * block_v * itemsize > SLAB_BYTES:
        return False
    if interpret:
        return True
    return (block_n % 8 == 0 and block_v % 128 == 0 and hd % 128 == 0
            and kernel_vmem_bytes(block_n, block_v, hd, itemsize)
            <= VMEM_BUDGET)


def resolve_impl(n, hd, v, dtype, block_n, block_v,
                 interpret=False) -> str:
    """'pallas' when the static gate accepts these shapes and the backend
    is a TPU (or interpret mode is asked for), else 'xla'. A supported
    kernel is compiled as-is: if Mosaic refuses it the jit fails."""
    from ..registry import backend_kind
    if not fused_ce_supported(n, hd, v, dtype, block_n, block_v, interpret):
        return "xla"
    if interpret or backend_kind() == "tpu":
        return "pallas"
    return "xla"


def fused_linear_cross_entropy(hidden, w, labels, ignore_index: int = -100,
                               reduction: str = "mean",
                               block_n: Optional[int] = None,
                               block_v: Optional[int] = None,
                               impl: Optional[str] = None,
                               interpret: bool = False):
    """CE(hidden @ w, labels) without materializing the logits.

    hidden: [..., H]; w: [H, V]; labels: [...] int ids (``ignore_index``
    rows contribute 0 loss and don't count toward the mean). ``reduction``:
    'mean' (token-weighted, fp32 — the causal-LM head convention), 'sum',
    or 'none' (per-token nll, shaped like ``labels``).

    Numerically interchangeable with
    ``F.cross_entropy((hidden @ w).astype(f32), labels)`` to fp32
    tolerance; peak memory is O(N * block_v) (the Pallas backward: one
    SLAB_BYTES slab of blocks) instead of O(N * V)."""
    lead = hidden.shape[:-1]
    hd = hidden.shape[-1]
    v = w.shape[-1]
    n = int(np.prod(lead)) if lead else 1
    h2 = hidden.reshape(n, hd)
    lab = labels.reshape(n).astype(jnp.int32)
    valid = lab != ignore_index
    safe = jnp.where(valid, lab, -1)          # out of range -> tgt = 0
    # under a device mesh the Pallas kernels run per shard (per_shard.py):
    # rows split with the batch dimension, W whole on every device (its
    # cotangent is summed over the data axes by the region's transpose)
    act = active_axes()
    n_local = n
    if act is not None:
        mesh, free, sizes = act
        rows = batch_spec(free)
        if lead and lead[0] % shards(rows, sizes) == 0:
            n_local = n // shards(rows, sizes)
        else:                        # rows do not divide: GSPMD's job
            act, impl = None, impl or "xla"
    if block_n is None or block_v is None:
        from .autotune import fused_vocab_ce_config
        tn, tv = fused_vocab_ce_config(n_local, hd, v, str(hidden.dtype))
        block_n = block_n if block_n is not None else tn
        block_v = block_v if block_v is not None else tv
    if impl is None:
        impl = resolve_impl(n_local, hd, v, hidden.dtype, block_n, block_v,
                            interpret)

    def local(h2, w, safe):
        return lse_and_target(h2, w, safe, block_n, block_v, impl, interpret)

    if act is not None and impl == "pallas":
        lse, tgt = per_shard(
            local, mesh, free, (P(rows, None), P(None, None), P(rows)),
            (P(rows), P(rows)))(h2, w, safe)
    else:
        lse, tgt = local(h2, w, safe)
    nll = jnp.where(valid, lse - tgt, 0.0)
    if reduction == "none":
        return nll.reshape(lead)
    if reduction == "sum":
        return jnp.sum(nll)
    cnt = jnp.sum(valid.astype(jnp.float32))
    return jnp.sum(nll) / jnp.maximum(cnt, 1.0)


@register_kernel("fused_vocab_ce", "tpu")
def _fused_ce_tpu(hidden, w, labels, **kw):
    return fused_linear_cross_entropy(hidden, w, labels, **kw)


@register_kernel("fused_vocab_ce", "any")
def _fused_ce_any(hidden, w, labels, **kw):
    kw.setdefault("impl", "xla")
    return fused_linear_cross_entropy(hidden, w, labels, **kw)


__all__ = ["fused_linear_cross_entropy", "lse_and_target",
           "fused_ce_supported", "resolve_impl"]
