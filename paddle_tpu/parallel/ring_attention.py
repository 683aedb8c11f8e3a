"""Ring attention: exact attention over sequence-sharded K/V.

Long-context requirement (SURVEY.md §5): the reference snapshot has no ring
attention (verified absent; FA2 + Megatron SP only) but the TPU build treats
"scale sequence length" as first-class. Design: shard_map over the "sep"
axis; each device holds q/k/v shards [b, s/n, h, d]; K/V shards rotate
around the ring with jax.lax.ppermute (ICI neighbor exchange) while each
device folds every block into its running online-softmax state.

Round-3 upgrade (was: dense [s_l, s_l] XLA scores per step): each ring step
now runs the Pallas FLASH kernel on the local (q-block, kv-block) pair —
flash_fwd_block returns the block's normalized output + logsumexp, and the
running state merges NORMALIZED partials:

    lse' = logaddexp(lse, lse_i)
    out' = out * exp(lse - lse') + out_i * exp(lse_i - lse')

Causal steps dispatch on the kv block's ORIGIN via lax.switch:
  src < my  -> full block, flash with causal=False
  src == my -> diagonal block, flash with causal=True
  src > my  -> fully masked: SKIPPED (no FLOPs — round 2 exp-suppressed
               these, wasting ~2x causal compute)

The backward is a hand-written ring (custom_vjp), as published ring/blockwise
attention does: dq accumulates locally while (k, v, dk, dv) rotate together
— after n steps each dk/dv shard has circled home carrying every device's
contribution. Each step reuses the flash backward kernels with the GLOBAL
(out, lse) residuals, so no dense [s_l, s_l] score matrix is ever
materialized in either direction.

The dense-XLA path remains as fallback for shapes the kernel doesn't
support (indivisible blocks) and runs under interpret on CPU test meshes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .mesh import current_mesh

NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask):
    """One q-block vs one kv-block, returning (unnormalized acc, m, l).
    q: [b, sq, h, d]; k/v: [b, sk, h_kv, d]; mask [sq, sk] or
    [b, sq, sk] (dense fallback path). GQA kv heads broadcast here — at
    the block, so the rotating ring shards stay h_kv-sized (the flash
    path leaves broadcasting to the kernel the same way)."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        s = jnp.where(mask[:, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [b,h,sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                                   # [b,h,sq]
    acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)      # [b,sq,h,d]
    return acc, m, l


def _merge(state, acc, m, l):
    """Fold a new block's (acc, m, l) into the running online-softmax state
    (dense fallback path)."""
    acc0, m0, l0 = state
    m_new = jnp.maximum(m0, m)
    a0 = jnp.exp(m0 - m_new)
    a1 = jnp.exp(m - m_new)
    acc_new = acc0 * a0.transpose(0, 2, 1)[..., None] + acc * a1.transpose(0, 2, 1)[..., None]
    l_new = l0 * a0 + l * a1
    return acc_new, m_new, l_new


def _flash_blocks_ok(sl: int, h: int, h_kv: int, d: int,
                     has_seg: bool = False,
                     interpret: bool = False) -> tuple:
    """Pick (block_q, block_k) for the per-device flash blocks, or None if
    the local shapes can't satisfy the kernel's divisibility rules. With
    segment ids on real hardware, block_k must additionally be
    128-aligned or equal to the local length (Mosaic lane rule for the
    kv-segment tile)."""
    if h % h_kv:
        return None
    bq = next((c for c in (512, 256, 128, 64, 32, 16, 8) if sl % c == 0),
              None)
    bk = bq
    if bq is None or d not in (32, 64, 128, 256):
        return None
    if has_seg and not interpret and bk % 128 and bk != sl:
        # bk was already the LARGEST candidate dividing sl, so a
        # 128-multiple cannot divide sl either — no recovery possible
        return None
    return bq, bk


def _merge_norm(out0, lse0, out1, lse1):
    """Merge two NORMALIZED partial attentions given their logsumexps.
    out: [b, sl, h, d] f32; lse: [b, h, sl] f32."""
    lse_new = jnp.logaddexp(lse0, lse1)
    # a fully-skipped state has lse=NEG_INF: exp(NEG_INF - lse_new) -> 0
    w0 = jnp.exp(lse0 - lse_new)
    w1 = jnp.exp(lse1 - lse_new)
    wt = lambda w: jnp.moveaxis(w, 1, 2)[..., None]     # -> [b, sl, h, 1]
    return out0 * wt(w0) + out1 * wt(w1), lse_new


def _ring_flash(pos_l, q_l, k_l, v_l, qseg_l, kseg_l, axis, n, causal,
                scale, bq, bk, interpret):
    """shard_map-local ring attention on flash blocks with a hand-written
    ring VJP. All inputs are the per-device shards [b, sl, h(_kv), d];
    ``qseg_l``/``kseg_l`` [b, sl] (or None) carry packed-sequence segment
    ids — kseg rotates WITH its k/v block, and the kernel masks
    cross-segment pairs in VMEM (no dense mask in HBM). ``pos_l`` is the
    device's [1] shard of ``arange(n)`` over the ring axis — the ring
    index arrives as DATA because ``jax.lax.axis_index`` under a
    partially-manual legacy shard_map lowers to a bare PartitionId the
    SPMD partitioner rejects (jax < 0.6; same program either way on
    modern releases)."""
    from ..ops.pallas.flash_attention import (flash_bwd_block,
                                              flash_fwd_block)

    has_seg = qseg_l is not None
    perm = [(i, (i + 1) % n) for i in range(n)]          # rotate rightward
    # the flash-path shard_map runs check_vma=False (pallas_call out_shapes
    # carry no vma annotation), so no pcast bookkeeping is needed
    vary = lambda x: x

    def step_fwd(my, t, q_l, k_cur, v_cur, ks_cur):
        """(out_i f32, lse_i) for the kv block that originated on device
        (my - t) mod n; fully-masked causal blocks are skipped."""
        segs = dict(q_seg=qseg_l, kv_seg=ks_cur) if has_seg else {}

        def full(_):
            o, s = flash_fwd_block(q_l, k_cur, v_cur, scale, False, bq, bk,
                                   interpret, **segs)
            return o.astype(jnp.float32), s

        def diag(_):
            o, s = flash_fwd_block(q_l, k_cur, v_cur, scale, True, bq, bk,
                                   interpret, **segs)
            return o.astype(jnp.float32), s

        def skip(_):
            b, sl, h, d = q_l.shape
            return (jnp.zeros((b, sl, h, d), jnp.float32),
                    jnp.full((b, h, sl), NEG_INF, jnp.float32))

        if not causal:
            return full(None)
        src = (my - t) % n
        case = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
        return jax.lax.switch(case, (full, diag, skip), None)

    # a dummy [b, 0] int array stands in for absent segs so the scan
    # carry structure is static either way
    def _seg0(x):
        return jnp.zeros((x.shape[0], 0), jnp.int32)

    @jax.custom_vjp
    def ring(pos_l, q_l, k_l, v_l, qs_l, ks_l):
        out, lse = _ring_fwd(pos_l, q_l, k_l, v_l, qs_l, ks_l)[0]
        return out.astype(q_l.dtype)

    def _ring_fwd(pos_l, q_l, k_l, v_l, qs_l, ks_l):
        my = pos_l[0, 0]
        b, sl, h, d = q_l.shape
        out0 = vary(jnp.zeros((b, sl, h, d), jnp.float32))
        lse0 = vary(jnp.full((b, h, sl), NEG_INF, jnp.float32))

        def body(carry, t):
            out, lse, k_cur, v_cur, ks_cur = carry
            o_i, lse_i = step_fwd(my, t, q_l, k_cur, v_cur,
                                  ks_cur if has_seg else None)
            out, lse = _merge_norm(out, lse, o_i, lse_i)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            ks_nxt = jax.lax.ppermute(ks_cur, axis, perm)
            return (out, lse, k_nxt, v_nxt, ks_nxt), None

        (out, lse, _, _, _), _ = jax.lax.scan(
            body, (out0, lse0, k_l, v_l,
                   ks_l if has_seg else _seg0(k_l)), jnp.arange(n))
        return (out, lse), None

    def ring_fwd_rule(pos_l, q_l, k_l, v_l, qs_l, ks_l):
        (out, lse), _ = _ring_fwd(pos_l, q_l, k_l, v_l, qs_l, ks_l)
        return out.astype(q_l.dtype), (pos_l, q_l, k_l, v_l, qs_l, ks_l,
                                       out, lse)

    def ring_bwd_rule(res, dout):
        pos_l, q_l, k_l, v_l, qs_l, ks_l, out, lse = res
        my = pos_l[0, 0]
        out_c = out.astype(q_l.dtype)
        dout_c = dout.astype(q_l.dtype)

        def step_bwd(t, k_cur, v_cur, ks_cur):
            # qs_l (the RESIDUAL) — never the enclosing trace's qseg_l: a
            # custom_vjp bwd rule is traced in its own context, and
            # closing over a forward-trace tracer leaks it (hit live
            # under the Trainer's donated step)
            segs = dict(q_seg=qs_l, kv_seg=ks_cur) if has_seg else {}

            def full(_):
                return flash_bwd_block(q_l, k_cur, v_cur, out_c, lse, dout_c,
                                       scale, False, bq, bk, interpret,
                                       **segs)

            def diag(_):
                return flash_bwd_block(q_l, k_cur, v_cur, out_c, lse, dout_c,
                                       scale, True, bq, bk, interpret,
                                       **segs)

            def skip(_):
                return (jnp.zeros_like(q_l), jnp.zeros_like(k_cur),
                        jnp.zeros_like(v_cur))

            if not causal:
                return full(None)
            src = (my - t) % n
            case = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            return jax.lax.switch(case, (full, diag, skip), None)

        dq0 = vary(jnp.zeros(q_l.shape, jnp.float32))
        dk0 = vary(jnp.zeros(k_l.shape, jnp.float32))
        dv0 = vary(jnp.zeros(v_l.shape, jnp.float32))

        def body(carry, t):
            dq, k_cur, v_cur, ks_cur, dk_cur, dv_cur = carry
            dq_i, dk_i, dv_i = step_bwd(t, k_cur, v_cur,
                                        ks_cur if has_seg else None)
            dq = dq + dq_i.astype(jnp.float32)
            dk_cur = dk_cur + dk_i.astype(jnp.float32)
            dv_cur = dv_cur + dv_i.astype(jnp.float32)
            # dk/dv ride WITH their kv block: after n rotations total they
            # are back on the block's home device holding every device's
            # contribution
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            ks_nxt = jax.lax.ppermute(ks_cur, axis, perm)
            dk_nxt = jax.lax.ppermute(dk_cur, axis, perm)
            dv_nxt = jax.lax.ppermute(dv_cur, axis, perm)
            return (dq, k_nxt, v_nxt, ks_nxt, dk_nxt, dv_nxt), None

        (dq, _, _, _, dk, dv), _ = jax.lax.scan(
            body, (dq0, k_l, v_l, ks_l if has_seg else _seg0(k_l),
                   dk0, dv0), jnp.arange(n))
        import numpy as _np
        zseg = lambda x: _np.zeros(x.shape, jax.dtypes.float0)
        return (zseg(pos_l), dq.astype(q_l.dtype), dk.astype(k_l.dtype),
                dv.astype(v_l.dtype), zseg(qs_l), zseg(ks_l))

    ring.defvjp(ring_fwd_rule, ring_bwd_rule)
    return ring(pos_l, q_l, k_l, v_l,
                qseg_l if has_seg else _seg0(q_l),
                kseg_l if has_seg else _seg0(k_l))


def ring_attention(q, k, v, causal: bool = True, axis: str = "sep",
                   scale: Optional[float] = None, mesh=None,
                   interpret: Optional[bool] = None, segment_ids=None):
    """Exact attention with K/V rotating over the ``axis`` ring.

    q/k/v: [b, s, h, d] GLOBAL arrays sharded (or shardable) along s over
    ``axis``. Returns [b, s, h, d] with the same sharding.

    ``segment_ids`` [b, s] enables PACKED sequences under sequence
    parallelism: ids shard along s with q (query side) and rotate around
    the ring with their k/v blocks (kv side); the flash kernel masks
    cross-segment pairs in VMEM. Causal block skipping still applies —
    packing composes with the ring at full speed.
    """
    hm = current_mesh() if mesh is None else mesh
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if hm is None or hm.axis_size(axis) <= 1:
        from ..ops.attention import _sdpa_xla
        return _sdpa_xla(q, k, v, causal=causal, scale=scale,
                         segment_ids=segment_ids)

    n = hm.axis_size(axis)
    mesh_ = hm.mesh
    if interpret is None:
        from ..ops.registry import backend_kind
        interpret = backend_kind() != "tpu"

    b, s, h, _ = q.shape
    h_kv = k.shape[2]
    sl = s // n
    has_seg = segment_ids is not None
    if has_seg:
        segment_ids = jnp.asarray(segment_ids, jnp.int32)
    blocks = _flash_blocks_ok(sl, h, h_kv, d, has_seg=has_seg,
                              interpret=interpret)

    # each device's ring index as DATA (its [1, 1] shard of a [1, n]
    # arange over the ring axis): see _ring_flash's docstring for why
    # axis_index can't be used here. Rank 2 deliberately — a rank-1
    # axis-sharded operand trips XLA's manual-subgroup sharding check
    # under the legacy partial-manual lowering.
    ring_pos = jnp.arange(n, dtype=jnp.int32)[None]

    if blocks is not None:
        bq, bk = blocks
        kw = dict(axis=axis, n=n, causal=causal, scale=scale, bq=bq,
                  bk=bk, interpret=interpret)
        if has_seg:
            fn = shard_map(
                functools.partial(_ring_flash, **kw),
                mesh=mesh_, axis_names=frozenset({axis}),
                in_specs=(P(None, axis),)
                + (P(None, axis, None, None),) * 3
                + (P(None, axis), P(None, axis)),
                out_specs=P(None, axis, None, None), check_vma=False)
            return fn(ring_pos, q, k, v, segment_ids, segment_ids)
        fn = shard_map(
            functools.partial(_ring_flash, qseg_l=None, kseg_l=None, **kw),
            mesh=mesh_, axis_names=frozenset({axis}),
            in_specs=(P(None, axis),)
            + (P(None, axis, None, None),) * 3,
            out_specs=P(None, axis, None, None), check_vma=False)
        return fn(ring_pos, q, k, v)

    # dense fallback (unnormalized online-softmax ring; correctness-grade)
    def local_fn(pos_l, q_l, k_l, v_l, qs_l, ks_l):
        my = pos_l[0, 0]
        b, sl, h, _ = q_l.shape
        rows = jax.lax.broadcasted_iota(jnp.int32, (sl, sl), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sl, sl), 1)
        diag_mask = cols <= rows                         # intra-block causal
        perm = [(i, (i + 1) % n) for i in range(n)]      # rotate kv rightward

        # the running state is per-device ("varying over the ring"): seed
        # it FROM the varying q shard instead of replicated constants —
        # data dependence is the one spelling every jax release agrees
        # marks it varying (modern vma typing and the legacy check_rep
        # tracker alike; jax.lax.pcast only exists on ≥0.7)
        zq = 0.0 * q_l.astype(jnp.float32)           # [b, sl, h, d]
        zrow = jnp.moveaxis(zq[..., 0], 1, 2)        # [b, h, sl]
        acc0 = zq
        m0 = zrow + NEG_INF
        l0 = zrow

        def step(carry, t):
            acc, m, l, k_cur, v_cur, ks_cur = carry
            src = (my - t) % n
            if causal:
                visible = src < my
                is_diag = src == my
                base = jnp.where(is_diag, diag_mask,
                                 jnp.broadcast_to(visible, diag_mask.shape))
            else:
                base = jnp.ones((sl, sl), bool)
            base = jnp.broadcast_to(base[None], (b, sl, sl))
            if has_seg:
                base = base & (qs_l[:, :, None] == ks_cur[:, None, :])
            a, bm, bl = _block_attn(q_l, k_cur, v_cur, scale, base)
            acc, m, l = _merge((acc, m, l), a, bm, bl)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            ks_nxt = jax.lax.ppermute(ks_cur, axis, perm)
            return (acc, m, l, k_nxt, v_nxt, ks_nxt), None

        (acc, m, l, _, _, _), _ = jax.lax.scan(
            step, (acc0, m0, l0, k_l, v_l, ks_l), jnp.arange(n))
        l_t = l.transpose(0, 2, 1)[..., None]            # [b,sl,h,1]
        safe = jnp.where(l_t == 0.0, 1.0, l_t)
        return (acc / safe).astype(q_l.dtype)

    fn = shard_map(local_fn, mesh=mesh_, axis_names=frozenset({axis}),
                   in_specs=(P(None, axis),)
                   + (P(None, axis, None, None),) * 3
                   + (P(None, axis), P(None, axis)),
                   out_specs=P(None, axis, None, None))
    # [b, 0] dummy when unpacked: nothing to shard, rotate, or read
    seg = segment_ids if has_seg else jnp.zeros((b, 0), jnp.int32)
    return fn(ring_pos, q, k, v, seg, seg)
