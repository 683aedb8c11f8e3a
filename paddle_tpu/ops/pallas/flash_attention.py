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

Only the blocks causal attention requires (PR 42): ``flash_plan`` sorts the
(nq, nk) rectangle of a head's blocks into DEAD (wholly above the causal
line: no compute, and its index map names the row's last live block again,
so the pipeline copies nothing), INTERIOR (wholly visible: ``exp(s - m)``
straight, no mask is formed) and EDGE (the causal line or the keys' true end
crosses it: masked by position). Blocks need not divide the lengths: the
call pads to whole blocks beside the transposes it makes anyway. Blocks are
WIDE (the rule: one for a whole length up to 2,048) and a step runs its block
in row parts: VMEM holds a part's scores, not the block's, and a part of a
block on the causal line multiplies only the keys up to its own last row.

Backward = ONE kernel using the saved per-row logsumexp, plus a delta =
rowsum(out * dout) precomputed in XLA: S, P and dP are formed once a block
(five products, one exponential); dq accumulates in VMEM along a row of
blocks and the whole float32 dk and dv of the current KV head in VMEM
scratch, written when the KV head changes. Where they do not fit
(``ONE_PASS_VMEM``) the two-kernel form runs (dq; dk+dv), and the plan says
which.

Falls back to the XLA composition (ops/attention.py) for arbitrary dense
masks.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
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
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=VMEM_LIMIT)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30  # large-negative instead of -inf: avoids inf-inf=nan in exp
LSE_LANES = 8    # lane width for per-row scalars (lse/delta); Mosaic wants
                 # the last block dim == the array dim, 8 keeps HBM cost low
# scoped VMEM the kernels may ask for (a v5e core has 128 MiB; the default
# scope of 16 MiB holds 1024 x 1024 blocks of the forward, not of the backward)
VMEM_LIMIT = 64 << 20
# what the one-pass backward may keep resident for a KV head's dK and dV
ONE_PASS_VMEM = 32 << 20
# a block runs in row parts of at most this many rows: a part's temporaries,
# not the block's, are what VMEM has to hold, so blocks can be wide (few grid
# steps, K and V fetched once for all parts), and a part of a block ON the
# causal line multiplies only the keys up to its own last row. Read on a v5e
# (PR 42, PERF.md section 6): the backward (five products, more float32
# temporaries a row) wins at 256 rows at every block size; the forward wins
# at 512 once a block is wider than 1024 rows, and runs a narrower one whole
# (two parts of a 896-row block cost 13% more than the block).
FWD_WHOLE_ROWS = 1024
FWD_PART_ROWS = 512
BWD_PART_ROWS = 256


class FlashPlan(NamedTuple):
    """How one call runs: its blocks, the (nq, nk) rectangle of grid steps
    a head, how many of them fall in each class, and the backward's form.
    The kernels build their grids and their branches FROM this."""
    block_q: int
    block_k: int
    nq: int
    nk: int
    interior: int     # wholly visible: no mask is formed
    edge: int         # the causal line or the keys' true end crosses it
    dead: int         # wholly above the causal line: no compute, no copy
    fwd_parts: int    # row parts a block runs in, forward
    bwd_parts: int    # ... and backward
    backward: str     # "one_pass" | "two_pass"
    group: int        # query heads a KV head: the backward's grid runs
                      # group x nq rows of blocks a KV head


def _block_class(qi, ki, *, sq, sk, block_q, block_k, causal):
    """(dead, interior) of block (qi, ki) from indices and static shapes
    alone — ints, numpy arrays and traced scalars alike. The causal line is
    bottom-right aligned (``offset = sk - sq``: chunked prefill, the ring's
    blocks); rows past ``sq`` are zero padding and need no mask, keys past
    ``sk`` do."""
    col_lo = ki * block_k
    col_hi = col_lo + block_k - 1
    inside = col_hi < sk
    if not causal:
        return col_lo < 0, inside
    row_lo = qi * block_q + (sk - sq)         # in key coordinates
    return col_lo > row_lo + block_q - 1, inside & (col_hi <= row_lo)


def _last_live_k(qi, nk, *, sq, sk, block_q, block_k, **_):
    """Last key block a causal query block sees (dead blocks follow it)."""
    return jnp.minimum((qi * block_q + (sk - sq) + block_q - 1) // block_k,
                       nk - 1)


def _first_live_q(ki, *, sq, sk, block_q, block_k, **_):
    """First query block that sees a causal key block (dead ones lead)."""
    return jnp.maximum(ki * block_k - (sk - sq), 0) // block_q


def flash_plan(sq: int, sk: int, d: int, causal: bool, group: int = 1, *,
               block_q: Optional[int] = None, block_k: Optional[int] = None,
               dtype: str = "bfloat16",
               vmem_budget: int = ONE_PASS_VMEM) -> FlashPlan:
    """The plan of a call with ``sq`` queries on ``sk`` keys of head size
    ``d``, ``group`` query heads a KV head. Blocks default to the tune
    DB's or the rule's (``autotune.flash_attention_config``) and are
    clipped to the lengths; they need not divide them (the call pads to
    whole blocks). The backward runs in one pass while a KV head's whole
    float32 dK and dV, and their output blocks, fit ``vmem_budget``."""
    if block_q is None or block_k is None:
        from .autotune import flash_attention_config
        tq, tk = flash_attention_config(sq, sk, d, dtype, causal)
        block_q, block_k = block_q or tq, block_k or tk
    bq, bk = min(block_q, sq), min(block_k, sk)
    nq, nk = -(-sq // bq), -(-sk // bk)
    dead, interior = _block_class(
        np.arange(nq)[:, None], np.arange(nk)[None, :], sq=sq, sk=sk,
        block_q=bq, block_k=bk, causal=causal)
    dead, interior = (np.broadcast_to(x, (nq, nk)) for x in (dead, interior))
    n_dead, n_int = int(np.sum(dead)), int(np.sum(interior & ~dead))
    itemsize = np.dtype(dtype).itemsize
    resident = nk * bk * d * (2 * 4 + 2 * 2 * itemsize)
    return FlashPlan(bq, bk, nq, nk, n_int, nq * nk - n_int - n_dead, n_dead,
                     1 if bq <= FWD_WHOLE_ROWS else -(-bq // FWD_PART_ROWS),
                     -(-bq // BWD_PART_ROWS),
                     "one_pass" if resident <= vmem_budget else "two_pass",
                     group)


def _note_plan(plan):
    """Leave the plan in the ``build_log`` row of the program being built."""
    from ...core import compile_cache
    compile_cache.note("flash_plan", plan._asdict())


def _block_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _by_class(plan, dead, interior, step):
    """Run ``step(edge)`` for the class this block is in. A class the plan
    holds no block of gets no code, and a dead block no step at all."""
    if plan.edge == plan.dead == 0:
        step(False)
    elif plan.interior == plan.dead == 0:
        step(True)
    else:
        if plan.interior:
            pl.when(interior)(lambda: step(False))
        if plan.edge:
            pl.when(jnp.logical_not(dead | interior))(lambda: step(True))


def _scores(q, k, scale, edge, qs_ref, ks_ref, qi, ki, row0=0, *, sq, sk,
            block_q, block_k, causal):
    """Scaled float32 scores of rows ``row0...`` of a block (``q``) on its
    first keys (``k``), masked where the block's class needs it: an edge
    block by position against the causal line (which, for a true row, also
    ends at the keys' true length) or against that length alone; any block
    by segment ids where there are some.

    qs_ref: [1, block_q, LSE_LANES] tile; ks_ref: [1, LSE_LANES, block_k]
    tile (segment ids lane/sublane-broadcast outside the kernel) — all
    reads stay 2-D, which Mosaic vectorizes cleanly."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = None
    if edge:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            mask = cols - rows <= (qi * block_q + row0 + (sk - sq)
                                   - ki * block_k)
        else:
            mask = cols < sk - ki * block_k
    if qs_ref is not None:
        seg = (qs_ref[0, row0:row0 + s.shape[0], :1]
               == ks_ref[0, :1, :s.shape[1]])            # [rows,1] == [1,keys]
        mask = seg if mask is None else (mask & seg)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    return s


def _block_parts(plan, n, edge, dropout_p, *, sq, sk, causal, **_):
    """[(first row, end row, keys)] of the ``n`` row parts a block's step
    runs in, cut at whole 128s. Where every edge block lies ON the causal
    line (equal blocks, an offset of whole blocks) a part of one multiplies
    the keys before its own end row only: the square above the line holds
    no work. (Dropout's keep-mask is drawn a whole block at a time: such a
    call runs whole blocks.)"""
    bq, bk = plan.block_q, plan.block_k
    if n == 1 or bq % 128 or dropout_p > 0.0:
        return [(0, bq, bk)]
    cuts = [-(-(i * bq) // (n * 128)) * 128 for i in range(n)] + [bq]
    on_line = edge and causal and bq == bk and (sk - sq) % bk == 0
    return [(a, b, b if on_line else bk) for a, b in zip(cuts, cuts[1:])]


def _dropout_keep(seed_ref, bi, h, qi, ki, dropout_p, block_q, block_k, sk):
    """Regenerable keep-mask for one [block_q, block_k] score block.

    Counter-based: each (batch, head, query-pos, key-pos) CELL hashes its
    global coordinates with the seed through the murmur3 finalizer — plain
    uint32 vector ops, so the same bits come out of Mosaic on TPU and of
    the interpreters on CPU, and out of the forward and the backward
    kernels regardless of grid order or autotuned block sizes.
    (pltpu.prng_* was rejected: the TPU-interpret simulator stubs it to
    zeros, which would make dropout untestable off-hardware.)"""
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

def _fwd_kernel(*refs, scale, has_seg, dropout_p, plan, geom):
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
    block_q, block_k = plan.block_q, plan.block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def step(edge):
        for a, b, keys in _block_parts(plan, plan.fwd_parts, edge, dropout_p,
                                       **geom):
            part(edge, slice(a, b), keys)

    def part(edge, rows, keys):
        q = q_ref[0, 0, rows, :]                   # [rows, d]
        k = k_ref[0, 0, :keys, :]                  # [keys, d]
        v = v_ref[0, 0, :keys, :]
        s = _scores(q, k, scale, edge, qs_ref, ks_ref, qi, ki, rows.start,
                    **geom)
        m_prev = m_scr[rows, :1]                   # [rows, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)            # [rows, 1]
        p = jnp.exp(s - m_new)                     # [rows, keys]
        if has_seg:
            # only segment ids can mask a WHOLE row of every block so far
            # (the causal line and the keys' end leave key 0, in block 0,
            # to every row): m_new == NEG_INF would then make
            # exp(s - m_new) = 1 and turn the row into a mean over V
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        l_new = alpha * l_scr[rows, :1] + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            # l accumulates the true softmax denominator; dropout scales the
            # numerator only (dropout(P)·V == (Σ p·M/(1-r)·v)/l)
            keep = _dropout_keep(seed_ref, bi, hi, qi, ki, dropout_p,
                                 block_q, block_k, geom["sk"])
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[rows, :] = jnp.broadcast_to(m_new, (m_new.shape[0], 128))
        l_scr[rows, :] = jnp.broadcast_to(l_new, (l_new.shape[0], 128))

    _by_class(plan, *_block_class(qi, ki, **geom), step)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(safe_l), (block_q, LSE_LANES))


def _seg_inputs(q_seg, kv_seg, plan):
    """Lift [b, s] segment ids into lane/sublane-broadcast 3-D arrays whose
    blocks satisfy the Mosaic (8, 128) rule: q as [b, sq, LSE_LANES]
    (lane-broadcast), kv as [b, LSE_LANES, sk] (sublane-broadcast), both
    padded to whole blocks (a padded key is masked by position)."""
    q_seg = _pad_to(q_seg, 1, plan.nq * plan.block_q)
    kv_seg = _pad_to(kv_seg, 1, plan.nk * plan.block_k)
    qs = jnp.broadcast_to(q_seg[:, :, None],
                          (*q_seg.shape, LSE_LANES))
    ks = jnp.broadcast_to(kv_seg[:, None, :],
                          (kv_seg.shape[0], LSE_LANES, kv_seg.shape[1]))
    return qs, ks


def _qseg_spec(block_q, index_map):
    return _block_spec((1, block_q, LSE_LANES), index_map)


def _kseg_spec(block_k, index_map):
    return _block_spec((1, LSE_LANES, block_k), index_map)


def _pad_to(x, axis, n):
    """Zero-pad ``axis`` of ``x`` up to ``n``."""
    if x.shape[axis] == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad)


def _heads_first(x, n):
    """[b, s, h, d] -> [b, h, n, d], zero rows past ``s``: the kernels'
    layout, whole blocks long. XLA fuses the pad into the transpose's copy."""
    return _pad_to(jnp.swapaxes(x, 1, 2), 2, n)


def _geom(plan, sq, sk, causal):
    return dict(sq=sq, sk=sk, block_q=plan.block_q, block_k=plan.block_k,
                causal=causal)


def _kv_index(plan, geom, group):
    """Index map of a K/V block under grid (b, head, q block, k block),
    ``group`` heads of the grid a KV head. A dead step names the row's last
    live block again, so the pipeline sees an unchanged index and copies
    nothing."""
    if not plan.dead:
        return lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)
    return lambda bi, hi, qi, ki: (
        bi, hi // group,
        jnp.minimum(ki, _last_live_k(qi, plan.nk, **geom)), 0)


def _fwd(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal, plan,
         interpret):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q, block_k, nq, nk = plan[:4]
    has_seg = q_seg is not None
    geom = _geom(plan, sq, sk, causal)
    _note_plan(plan)

    qt = _heads_first(q, nq * block_q)             # [b, h, sq', d]
    kt = _heads_first(k, nk * block_k)             # [b, h_kv, sk', d]
    vt = _heads_first(v, nk * block_k)

    q_spec = _block_spec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_idx = _kv_index(plan, geom, plan.group)
    kv_spec = _block_spec((1, 1, block_k, d), kv_idx)
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
        in_specs += [
            _qseg_spec(block_q, lambda bi, hi, qi, ki: (bi, qi, 0)),
            _kseg_spec(block_k, lambda *ids: (ids[0], 0, kv_idx(*ids)[2]))]
        inputs += _seg_inputs(q_seg, kv_seg, plan)

    kernel = functools.partial(_fwd_kernel, scale=scale, has_seg=has_seg,
                               dropout_p=dropout_p, plan=plan, geom=geom)
    scratch = [pltpu.VMEM((block_q, 128), jnp.float32),
               pltpu.VMEM((block_q, 128), jnp.float32),
               pltpu.VMEM((block_q, d), jnp.float32)]
    out_t, lse4 = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[o_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, nq * block_q, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, nq * block_q, LSE_LANES),
                                        jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=_tpu_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*inputs)
    # [b, sq, h, d], [b, h, sq]
    return jnp.swapaxes(out_t[:, :, :sq], 1, 2), lse4[:, :, :sq, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_block(refs, seed_ref, qs_ref, ks_ref, edge, rows, keys, bi, h, qi,
               ki, *, scale, has_seg, dropout_p, geom):
    """Rows ``rows`` of a block of the backward on its first ``keys`` keys,
    their scores formed ONCE: returns (q, k, do, P for dV, dS), P and dS
    in the operands' dtype."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs
    q = q_ref[0, 0, rows, :]
    k = k_ref[0, 0, :keys, :]
    v = v_ref[0, 0, :keys, :]
    do = do_ref[0, 0, rows, :]
    lse = lse_ref[0, 0, rows, :1]                  # [rows, 1]
    delta = delta_ref[0, 0, rows, :1]              # [rows, 1]
    s = _scores(q, k, scale, edge, qs_ref, ks_ref, qi, ki, rows.start,
                **geom)
    p = jnp.exp(s - lse)                           # [rows, keys]
    if has_seg:
        # a row that segment ids mask everywhere has lse = NEG_INF, and
        # exp(NEG_INF - NEG_INF) = 1 would corrupt dq/dk/dv
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
    pd = p
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if dropout_p > 0.0:
        keep = _dropout_keep(seed_ref, bi, h, qi, ki, dropout_p,
                             geom["block_q"], geom["block_k"], geom["sk"])
        inv = 1.0 / (1.0 - dropout_p)
        pd = jnp.where(keep, p * inv, 0.0)
        dp = jnp.where(keep, dp * inv, 0.0)
    ds = p * (dp - delta) * scale                  # [bq, bk]
    return q, k, do, pd.astype(do.dtype), ds.astype(q.dtype)


def _split_bwd_refs(refs, has_seg, dropout_p, n_out):
    """(seed, the six tensors, q segs, k segs, outputs and scratch)."""
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    six, rest = refs[:6], refs[6:]
    qs_ref, ks_ref = (rest.pop(0), rest.pop(0)) if has_seg else (None, None)
    return seed_ref, six, qs_ref, ks_ref, rest[:n_out], rest[n_out:]


def _dot_t(a, b):
    """a^T @ b in float32: contracts the rows of two [rows, *] blocks."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _bwd_kernel(*refs, scale, has_seg, dropout_p, plan, geom, with_dkv):
    """Grid (b, h_kv, group*nq, nk), k blocks innermost: dq accumulates in
    VMEM over a row of blocks, as the forward's output does. With
    ``with_dkv`` (the one-pass backward) the WHOLE float32 dk and dv of the
    current KV head accumulate in VMEM scratch beside it, over all its
    query heads and blocks, and leave when the KV head changes: S, P and dP
    are formed once a block, five products and one exponential. Without,
    this is the dq kernel of the two-pass form."""
    seed_ref, six, qs_ref, ks_ref, outs, scr = _split_bwd_refs(
        refs, has_seg, dropout_p, 3 if with_dkv else 1)
    bi = pl.program_id(0)
    hkv = pl.program_id(1)
    qg = pl.program_id(2)
    ki = pl.program_id(3)
    last_q = qg == pl.num_programs(2) - 1
    last_k = ki == pl.num_programs(3) - 1
    qi = qg % plan.nq         # q-block index (group-major enumeration)
    h = hkv * plan.group + qg // plan.nq   # query head (dropout replay)
    dq_scr = scr[0]

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    if with_dkv:
        @pl.when((ki == 0) & (qg == 0))
        def _init_kv():
            scr[1][:] = jnp.zeros_like(scr[1])
            scr[2][:] = jnp.zeros_like(scr[2])

    def step(edge):
        for a, b, keys in _block_parts(plan, plan.bwd_parts, edge, dropout_p,
                                       **geom):
            q, k, do, pd, ds = _bwd_block(
                six, seed_ref, qs_ref, ks_ref, edge, slice(a, b), keys, bi,
                h, qi, ki, scale=scale, has_seg=has_seg, dropout_p=dropout_p,
                geom=geom)
            dq_scr[a:b, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if with_dkv:
                at = pl.ds(pl.multiple_of(ki * plan.block_k, plan.block_k),
                           keys)
                scr[1][at, :] += _dot_t(ds, q)
                scr[2][at, :] += _dot_t(pd, do)

    _by_class(plan, *_block_class(qi, ki, **geom), step)

    @pl.when(last_k)
    def _finalize():
        outs[0][0, 0, :, :] = dq_scr[:].astype(outs[0].dtype)

    if with_dkv:
        @pl.when(last_k & last_q)
        def _finalize_kv():
            outs[1][0, 0, :, :] = scr[1][:].astype(outs[1].dtype)
            outs[2][0, 0, :, :] = scr[2][:].astype(outs[2].dtype)


def _bwd_dkv_kernel(*refs, scale, has_seg, dropout_p, plan, geom):
    """The two-pass form's second kernel, where a KV head's whole dk and dv
    do not fit VMEM. Grid (b, h_kv, nk, nq*group): accumulate one block of
    dk/dv at KV-HEAD resolution.

    The innermost sequential dim enumerates (query-head-in-group, q-block)
    pairs, so the GQA group sum happens in the VMEM accumulator instead of
    as a group-times-larger fp32 intermediate in HBM (round-1 weak item:
    FA2 accumulates at kv-head resolution; flash_attn_kernel.cu)."""
    seed_ref, six, qs_ref, ks_ref, (dk_ref, dv_ref), (dk_scr, dv_scr) = \
        _split_bwd_refs(refs, has_seg, dropout_p, 2)
    bi = pl.program_id(0)
    hkv = pl.program_id(1)
    ki = pl.program_id(2)
    qg = pl.program_id(3)
    qi = qg % plan.nq
    h = hkv * plan.group + qg // plan.nq

    @pl.when(qg == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def step(edge):
        for a, b, keys in _block_parts(plan, plan.bwd_parts, edge, dropout_p,
                                       **geom):
            q, _, do, pd, ds = _bwd_block(
                six, seed_ref, qs_ref, ks_ref, edge, slice(a, b), keys, bi,
                h, qi, ki, scale=scale, has_seg=has_seg, dropout_p=dropout_p,
                geom=geom)
            dk_scr[:keys, :] += _dot_t(ds, q)
            dv_scr[:keys, :] += _dot_t(pd, do)

    _by_class(plan, *_block_class(qi, ki, **geom), step)

    @pl.when(qg == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(dropout_p, scale, causal, plan, interpret, res, dout):
    q, k, v, q_seg, kv_seg, seed, out, lse = res
    b, sq, h, d = q.shape
    sk, h_kv, group = k.shape[1], k.shape[2], plan.group
    block_q, block_k, nq, nk = plan[:4]
    sq_p, sk_p = nq * block_q, nk * block_k
    has_seg = q_seg is not None
    geom = _geom(plan, sq, sk, causal)
    delta = jnp.sum(out.astype(jnp.float32) * dout.astype(jnp.float32),
                    axis=-1)                        # [b, sq, h]
    delta = jnp.moveaxis(delta, -1, 1)              # [b, h, sq]

    # zero rows past sq (q, dout, delta; lse 0 there keeps p finite) add
    # nothing to dk and dv; keys past sk are masked by position
    qt = _heads_first(q, sq_p)
    kt = _heads_first(k, sk_p)
    vt = _heads_first(v, sk_p)
    dot = _heads_first(dout, sq_p)                  # [b, h, sq', d]
    lse4, delta4 = (jnp.broadcast_to(_pad_to(x, 2, sq_p)[..., None],
                                     (b, h, sq_p, LSE_LANES))
                    for x in (lse, delta))
    inputs = [qt, kt, vt, dot, lse4, delta4]
    segs = _seg_inputs(q_seg, kv_seg, plan) if has_seg else []
    front = [seed] if dropout_p > 0.0 else []
    front_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(front)
    kernel_kw = dict(scale=scale, has_seg=has_seg, dropout_p=dropout_p,
                     plan=plan, geom=geom)

    # grid (b, h_kv, group*nq, nk): the q-head of step qg is
    # hkv*group + qg//nq (group-major), its q block qg % nq
    def q_idx(bi, hi, qg, ki):
        return (bi, hi * group + qg // nq, qg % nq, 0)

    kv_idx = _kv_index(plan, geom, 1)
    q_spec = _block_spec((1, 1, block_q, d), q_idx)
    kv_spec = _block_spec(
        (1, 1, block_k, d),
        lambda bi, hi, qg, ki: kv_idx(bi, hi, qg % nq, ki))
    lse_spec = _block_spec((1, 1, block_q, LSE_LANES), q_idx)
    specs = [q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec]
    if has_seg:
        specs += [
            _qseg_spec(block_q, lambda bi, hi, qg, ki: (bi, qg % nq, 0)),
            _kseg_spec(block_k, lambda bi, hi, qg, ki:
                       (bi, 0, kv_idx(bi, hi, qg % nq, ki)[2]))]
    one_pass = plan.backward == "one_pass"
    dkv_shapes = [jax.ShapeDtypeStruct((b, h_kv, sk_p, d), k.dtype),
                  jax.ShapeDtypeStruct((b, h_kv, sk_p, d), v.dtype)]
    out_specs, out_shape = [q_spec], [
        jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype)]
    scratch = [pltpu.VMEM((block_q, d), jnp.float32)]
    if one_pass:
        # a KV head's whole dk and dv: one block, written when it changes
        out_specs += [_block_spec((1, 1, sk_p, d),
                                  lambda bi, hi, qg, ki: (bi, hi, 0, 0))] * 2
        out_shape += dkv_shapes
        scratch += [pltpu.VMEM((sk_p, d), jnp.float32)] * 2
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, with_dkv=one_pass, **kernel_kw),
        grid=(b, h_kv, group * nq, nk),
        in_specs=front_specs + specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_tpu_params(
            "parallel", "parallel",
            "arbitrary" if one_pass else "parallel", "arbitrary"),
        interpret=interpret,
        name="flash_attention_bwd" if one_pass else "flash_attention_bwd_dq",
    )(*front, *inputs, *segs)
    if one_pass:
        dq_t, dk_t, dv_t = outs
    else:
        dq_t, (dk_t, dv_t) = outs[0], _bwd_dkv(
            front, front_specs, inputs, segs, dkv_shapes, kernel_kw,
            interpret)

    dq = jnp.swapaxes(dq_t[:, :, :sq], 1, 2)
    dk = jnp.swapaxes(dk_t[:, :, :sk], 1, 2)
    dv = jnp.swapaxes(dv_t[:, :, :sk], 1, 2)

    if has_seg:
        # int cotangents are symbolically zero (float0) in jax
        zseg = (np.zeros(q_seg.shape, jax.dtypes.float0),
                np.zeros(kv_seg.shape, jax.dtypes.float0))
    else:
        zseg = (None, None)
    dseed = (np.zeros(seed.shape, jax.dtypes.float0)
             if seed is not None else None)
    return (dq, dk, dv) + zseg + (dseed,)


def _bwd_dkv(front, front_specs, inputs, segs, dkv_shapes, kernel_kw,
             interpret):
    """dk/dv of the two-pass form, accumulated at kv-head resolution: grid
    (b, h_kv, nk, nq*group). The dead steps of a key block LEAD its row of
    query blocks; they name its first live block, which the first live
    step then finds already there."""
    plan, geom = kernel_kw["plan"], kernel_kw["geom"]
    group = plan.group
    block_q, block_k, nq, nk = plan[:4]
    d = inputs[0].shape[-1]

    def qi_of(ki, qg):
        if not plan.dead:
            return qg % nq
        return jnp.maximum(qg % nq, _first_live_q(ki, **geom))

    def q_idx(bi, hi, ki, qg):
        return (bi, hi * group + qg // nq, qi_of(ki, qg), 0)

    q_spec = _block_spec((1, 1, block_q, d), q_idx)
    kv_spec = _block_spec((1, 1, block_k, d),
                          lambda bi, hi, ki, qg: (bi, hi, ki, 0))
    lse_spec = _block_spec((1, 1, block_q, LSE_LANES), q_idx)
    specs = [q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec]
    if segs:
        specs += [
            _qseg_spec(block_q,
                       lambda bi, hi, ki, qg: (bi, qi_of(ki, qg), 0)),
            _kseg_spec(block_k, lambda bi, hi, ki, qg: (bi, 0, ki))]
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kernel_kw),
        grid=(inputs[0].shape[0], dkv_shapes[0].shape[1], nk, nq * group),
        in_specs=front_specs + specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=dkv_shapes,
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_tpu_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*front, *inputs, *segs)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_attention(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal,
                     plan, interpret):
    out, _ = _fwd(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal,
                  plan, interpret)
    return out


def _flash_fwd_rule(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal,
                    plan, interpret):
    out, lse = _fwd(q, k, v, q_seg, kv_seg, seed, dropout_p, scale, causal,
                    plan, interpret)
    return out, (q, k, v, q_seg, kv_seg, seed, out, lse)


def _flash_bwd_rule(dropout_p, scale, causal, plan, interpret, res, dout):
    return _bwd(dropout_p, scale, causal, plan, interpret, res, dout)


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
    (it is the LANE dim of the kv-segment tile). Blocks need NOT divide the
    lengths: the call pads to whole blocks and the edge blocks mask the
    keys' true end. ``interpret`` relaxes the alignment rules (no Mosaic
    involved) so CPU tests can run small blocks."""
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
                           dropout_seed=None,
                           vmem_budget: int = ONE_PASS_VMEM):
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
    tools/tune_kernels.py (reference: phi/kernels/autotune/cache.h); how
    the call then runs is ``flash_plan``'s to say. ``vmem_budget`` is what
    the one-pass backward may keep resident (a KV head's whole dk and dv):
    past it the two-pass form runs."""
    from ..attention import _sdpa_xla
    plan = flash_plan(q.shape[1], k.shape[1], q.shape[3], causal,
                      q.shape[2] // max(k.shape[2], 1), block_q=block_q,
                      block_k=block_k, dtype=str(q.dtype),
                      vmem_budget=vmem_budget)
    block_q, block_k = plan.block_q, plan.block_k
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
                                scale, causal, plan, interpret)

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

def _block_plan(q, k, causal, block_q, block_k):
    return flash_plan(q.shape[1], k.shape[1], q.shape[3], causal,
                      q.shape[2] // k.shape[2], block_q=block_q,
                      block_k=block_k, dtype=str(q.dtype))


def flash_fwd_block(q, k, v, scale, causal, block_q, block_k,
                    interpret=False, q_seg=None, kv_seg=None):
    """Forward flash block returning (out [b,sq,h,d], lse [b,h,sq]).

    ``q_seg`` [b, sq] / ``kv_seg`` [b, sk] restrict attention to
    equal-id positions (the ring's packed-sequence path); a q row whose
    segment has no match in this kv block comes back with lse=NEG_INF,
    which the ring's normalized merge treats as weight zero."""
    return _fwd(q, k, v, q_seg, kv_seg, None, 0.0, scale, causal,
                _block_plan(q, k, causal, block_q, block_k), interpret)


def flash_bwd_block(q, k, v, out, lse, dout, scale, causal, block_q, block_k,
                    interpret=False, q_seg=None, kv_seg=None):
    """Backward flash block given the GLOBAL (out, lse) of the full
    attention (delta = rowsum(out*dout) is computed inside, as FA2 does).
    Returns (dq, dk, dv) for this q/kv block pair."""
    res = (q, k, v, q_seg, kv_seg, None, out, lse)
    outs = _bwd(0.0, scale, causal,
                _block_plan(q, k, causal, block_q, block_k), interpret, res,
                dout)
    return outs[0], outs[1], outs[2]
