"""Pallas TPU grouped (ragged) matmul for per-expert MoE GEMMs.

Reference analogue: the grouped GEMM behind the reference's fused MoE
dispatch (incubate/nn/functional moe layers lower per-expert FFNs onto
one batched kernel instead of a Python loop over experts).

The op: rows of ``xs [m, k]`` are partitioned into ``g`` contiguous runs
by ``group_sizes [g]`` and run ``i`` multiplies its own ``w[i] [k, n]``.
Per-expert token counts are data-dependent, so the kernel cannot assume
anything divides anything — the TPU-first trick is TILE-ALIGNED PACKING:
scatter each run to a ``block_m``-aligned offset in a statically-bounded
staging buffer, so every grid row-tile belongs to exactly ONE group and
the weight for that tile is picked by a scalar-prefetched tile→group
table in the weight BlockSpec's index_map (the megablox group-metadata
idea, collapsed to its simplest alignment-by-construction form). Padding
rows are zero, multiply into zero rows, and are dropped by the final
gather — no masking in the kernel's hot loop.

Gradients: the backward pass reuses the XLA fallback's vjp (ragged_dot
is linear in both operands, so this is exact, and it guarantees the
gradcheck parity the MoE tests pin). Dispatch is gated by the TuneDB and
the static shape gate, with ``lax.ragged_dot`` for what they reject;
parallel/moe.py's ``_grouped_matmul`` is the seam that routes here.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except ImportError:  # pragma: no cover
    pltpu = None
    _HAS_PLTPU = False

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 128


def xla_grouped_matmul(xs, w, group_sizes):
    """XLA path: ``lax.ragged_dot`` (XLA-native; the round-5 v5e A/B
    measured it 1.7x faster than megablox gmm with max|diff|=0 at e=64,
    d=2048, f=1408). Returns f32 — the accumulator dtype; callers cast
    back to the activation dtype."""
    return jax.lax.ragged_dot(xs, w, group_sizes,
                              preferred_element_type=jnp.float32)


def _kernel(tg_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    # tg_ref is the scalar-prefetched tile→group table; it is consumed
    # by the weight BlockSpec's index_map, not read here
    del tg_ref

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[...]                                   # [bm, bk]
    wb = w_ref[0]                                     # [bk, bn] (this tile's expert)
    acc_ref[...] += jax.lax.dot_general(
        xb, wb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bm, bn] f32

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...]


def _pack_plan(group_sizes, m, block_m, g, nm):
    """Tile-aligned packing metadata (all int32, all traced):
    ``dest [m]`` — packed-buffer row for each source row (each group's
    run starts on a ``block_m`` boundary); ``tile_group [nm]`` — which
    group's weight each packed row-tile multiplies. Tiles past the used
    region keep group g-1: their rows are zero, their output is dead."""
    counts = group_sizes.astype(jnp.int32)
    aligned = ((counts + block_m - 1) // block_m) * block_m
    ends = jnp.cumsum(aligned)
    starts = ends - aligned
    row_ends = jnp.cumsum(counts)
    row_starts = row_ends - counts
    rid = jnp.arange(m, dtype=jnp.int32)
    gi = jnp.searchsorted(row_ends, rid, side="right").astype(jnp.int32)
    gi = jnp.minimum(gi, g - 1)
    dest = starts[gi] + (rid - row_starts[gi])
    tile_start = jnp.arange(nm, dtype=jnp.int32) * block_m
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right"),
        g - 1).astype(jnp.int32)
    return dest, tile_group


def grouped_matmul_pallas(xs, w, group_sizes, *,
                          block_m: int = DEFAULT_BLOCK_M,
                          block_n: int = DEFAULT_BLOCK_N,
                          block_k: int = DEFAULT_BLOCK_K,
                          interpret: bool = False):
    """y[m, n] f32 = per-group ``xs_run @ w[group]`` via tile-aligned
    packing + scalar-prefetched weight selection.

    xs: float [m, k]; w: float [g, k, n]; group_sizes: int [g] summing
    to m. ``k``/``n`` must divide the (clamped) blocks — the dispatch
    gate (shapes_supported) checks; ``m`` need not: the packed staging
    buffer is padded to a static ``block_m``-aligned bound."""
    if not _HAS_PLTPU:
        raise ImportError(
            "pallas.tpu is unavailable in this jax build; use "
            "xla_grouped_matmul")
    m, k = xs.shape
    g, k2, n = w.shape
    assert k == k2 and group_sizes.shape == (g,)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if n % block_n or k % block_k:
        raise ValueError(
            f"shape ({m},{k})x({g},{k},{n}) does not divide blocks "
            f"({block_m},{block_n},{block_k}); gate with shapes_supported()")
    # static bound on the packed buffer: every group wastes < block_m
    # alignment rows, so ceil(m/bm) + g tiles always suffice
    nm = (m + block_m - 1) // block_m + g
    m_pad = nm * block_m
    nn, nk = n // block_n, k // block_k

    dest, tile_group = _pack_plan(group_sizes, m, block_m, g, nm)
    xp = jnp.zeros((m_pad, k), xs.dtype).at[dest].set(xs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk, tg: (i, kk)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda i, j, kk, tg: (tg[i], kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, tg: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    yp = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, n), jnp.float32),
        compiler_params=(pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "arbitrary"))
            if not interpret else None),
        interpret=interpret,
        name="grouped_matmul",
    )(tile_group, xp, w)
    return yp[dest]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _pallas_gmm(xs, w, group_sizes, bm, bn, bk, interpret):
    return grouped_matmul_pallas(xs, w, group_sizes, block_m=bm,
                                 block_n=bn, block_k=bk,
                                 interpret=interpret)


def _pallas_gmm_fwd(xs, w, group_sizes, bm, bn, bk, interpret):
    return (_pallas_gmm(xs, w, group_sizes, bm, bn, bk, interpret),
            (xs, w, group_sizes))


def _pallas_gmm_bwd(bm, bn, bk, interpret, res, gy):
    # backward through the XLA fallback: ragged_dot is linear in both
    # operands so its vjp IS the exact gradient of the grouped matmul —
    # this is what guarantees Pallas/XLA gradcheck parity
    xs, w, group_sizes = res
    _, vjp = jax.vjp(
        lambda a, b: xla_grouped_matmul(a, b, group_sizes), xs, w)
    dxs, dw = vjp(gy.astype(jnp.float32))
    return (dxs.astype(xs.dtype), dw.astype(w.dtype),
            np.zeros(group_sizes.shape, dtype=jax.dtypes.float0))


_pallas_gmm.defvjp(_pallas_gmm_fwd, _pallas_gmm_bwd)


def shapes_supported(x_shape, w_shape, *, block_m=DEFAULT_BLOCK_M,
                     block_n=DEFAULT_BLOCK_N, block_k=DEFAULT_BLOCK_K,
                     dtype=None):
    """True when the fused kernel can run these shapes: k/n divide their
    (clamped) blocks at MXU-worthy widths. m is unconstrained (the
    packing pads it), but block_m must stay sublane-aligned for the
    activation dtype (f32: 8, bf16: 16) — Mosaic failures at misaligned
    tiles surface at COMPILE time, after dispatch already committed."""
    m, k = x_shape
    g, k2, n = w_shape
    if k != k2 or m < 1 or g < 1:
        return False
    sublane = 8
    if dtype is not None:
        itemsize = jnp.dtype(dtype).itemsize
        sublane = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
    if block_m % sublane:
        return False
    bn, bk = min(block_n, n), min(block_k, k)
    return n % bn == 0 and k % bk == 0 and bn >= 128 and bk >= 128


def _tpu_grouped(xs, w, group_sizes):
    """Registered TPU impl: the tile-aligned Pallas kernel when the
    static gates pass (TuneDB winner + blocks, shapes_supported), else
    the XLA ragged_dot composition. A gated-in kernel compiles or the
    jit fails — nothing here retries on another implementation."""
    from ..registry import pallas_disabled
    from ...core.flags import flag
    m, k = xs.shape
    g, _, n = w.shape
    if (pallas_disabled() or not flag("use_pallas_kernels")
            or db_winner(m, n, k, g, xs.dtype) == "xla"):
        return xla_grouped_matmul(xs, w, group_sizes)
    bm, bn, bk = tuned_blocks(m, n, k, g, xs.dtype)
    if not shapes_supported((m, k), tuple(w.shape), block_m=bm,
                            block_n=bn, block_k=bk, dtype=xs.dtype):
        return xla_grouped_matmul(xs, w, group_sizes)
    return _pallas_gmm(xs, w, group_sizes, bm, bn, bk, False)


def _register():
    # THE registry op parallel/moe.py's _grouped_matmul seam resolves
    # through: xs float [m, k] x w [g, k, n], group_sizes [g] -> f32
    # [m, n]; dropless routing AND the dropless-EP shard_map body both
    # route here, so TuneDB configs and PT_DISABLE_PALLAS apply to every
    # per-expert GEMM uniformly.
    from ..registry import register_kernel
    register_kernel("grouped_matmul", "tpu")(_tpu_grouped)
    register_kernel("grouped_matmul", "any")(xla_grouped_matmul)


_register()


@jax.custom_vjp
def grouped_matmul(xs, w, group_sizes):
    """Dispatch-routed grouped matmul: the single entry every per-expert
    GEMM call site uses (MoE dropless routing, the EP shard_map body).

    custom_vjp at the dispatch boundary, not just the Pallas path: jax's
    ragged_dot ad rules choke on symbolic-Zero tangents inside a
    shard_map transpose (the dropless-EP body), so BOTH backends take
    the one exact bwd below — custom_vjp instantiates the cotangent
    before bwd runs, and the grouped matmul is linear in each operand,
    so this is the exact gradient either way."""
    from ..registry import dispatch
    return dispatch("grouped_matmul")(xs, w, group_sizes)


def _gmm_fwd(xs, w, group_sizes):
    return grouped_matmul(xs, w, group_sizes), (xs, w, group_sizes)


def _gmm_bwd(res, gy):
    xs, w, group_sizes = res
    _, vjp = jax.vjp(
        lambda a, b: xla_grouped_matmul(a, b, group_sizes), xs, w)
    dxs, dw = vjp(gy.astype(jnp.float32))
    return (dxs.astype(xs.dtype), dw.astype(w.dtype),
            np.zeros(group_sizes.shape, dtype=jax.dtypes.float0))


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def _db_cfg(m, n, k, g, dtype):
    from .autotune import _DB
    import jax as _jax
    kind = getattr(_jax.devices()[0], "device_kind", "cpu")
    return _DB.lookup(_DB.key("grouped_matmul", kind, str(dtype),
                              sm=m, sn=n, sk=k, g=g))


def tuned_blocks(m, n, k, g, dtype="bfloat16"):
    """Tune-DB lookup for (m, n, k, g); falls back to MXU defaults."""
    try:
        cfg = _db_cfg(m, n, k, g, dtype)
        if cfg:
            return (cfg.get("block_m", DEFAULT_BLOCK_M),
                    cfg.get("block_n", DEFAULT_BLOCK_N),
                    cfg.get("block_k", DEFAULT_BLOCK_K))
    except Exception:
        pass
    return DEFAULT_BLOCK_M, DEFAULT_BLOCK_N, DEFAULT_BLOCK_K


def db_winner(m, n, k, g, dtype="bfloat16"):
    """Measured dispatch preference for this shape bucket ('xla' = the
    on-hardware A/B showed ragged_dot at least ties the Pallas kernel
    for this bucket; None = no measurement, keep the default)."""
    try:
        cfg = _db_cfg(m, n, k, g, dtype)
        return cfg.get("winner") if cfg else None
    except Exception:
        return None


__all__ = ["grouped_matmul", "grouped_matmul_pallas", "xla_grouped_matmul",
           "shapes_supported", "tuned_blocks", "db_winner"]
