"""Pallas TPU fused weight-only int8 matmul.

Reference analogue: the cutlass weight-only GEMMs behind
python/paddle/nn/quant/quantized_linear.py weight_only_linear:152
(paddle/phi/kernels/fusion/cutlass/...), where dequantization happens in
the GEMM epilogue instead of a separate pass.

TPU-first design: the win at decode time is HBM bandwidth — the weight
crosses HBM as int8 ([n, k], the reference's transposed layout) and is
widened to the activation dtype IN VMEM, right before the MXU dot; the
per-channel scale multiplies the f32 accumulator once per output tile.
XLA's fallback composition (convert + scale folded into dot_general) is
kept for non-TPU backends, group-wise scales, int4, and shapes that do
not tile; dispatch happens in nn/quantized_linear.py via ops.registry.

Block sizes come from the tune DB (`tune_db.json`, op "int8_matmul") with
MXU-shaped defaults.
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

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 512


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[...]                                   # [bm, bk] activation
    wb = w_ref[...].astype(xb.dtype)                  # [bn, bk] int8 -> act
    acc_ref[...] += jax.lax.dot_general(
        xb, wb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bm, bn] f32

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        scale = s_ref[...].astype(jnp.float32)        # [1, bn]
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


def int8_matmul_pallas(x, wq, scale, *, block_m: int = DEFAULT_BLOCK_M,
                       block_n: int = DEFAULT_BLOCK_N,
                       block_k: int = DEFAULT_BLOCK_K,
                       interpret: bool = False):
    """y[m, n] = x[m, k] @ wq[n, k].T * scale[n], dequant fused in VMEM.

    x: float (bf16/f32) [m, k]; wq: int8 [n, k] (transposed reference
    layout); scale: [n] per-channel. Shapes must divide the block sizes —
    the caller (weight_only_linear) checks and falls back otherwise."""
    if not _HAS_PLTPU:
        raise ImportError(
            "pallas.tpu is unavailable in this jax build; use the XLA "
            "weight_only_linear path")
    m, k = x.shape
    n, k2 = wq.shape
    assert k == k2 and scale.shape == (n,)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"shape ({m},{k})x({n},{k}) does not divide blocks "
            f"({block_m},{block_n},{block_k}); gate with shapes_supported()")
    nm, nn, nk = m // block_m, n // block_n, k // block_k
    scale2 = scale.reshape(1, n)

    grid = (nm, nn, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_n, block_k), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=(pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
            if not interpret else None),
        interpret=interpret,
        name="int8_matmul",
    )(x, wq, scale2)
    return out


def shapes_supported(x_shape, w_shape, *, block_m=DEFAULT_BLOCK_M,
                     block_n=DEFAULT_BLOCK_N, block_k=DEFAULT_BLOCK_K,
                     dtype=None):
    """True when the fused kernel can run these shapes without padding:
    every dim divides its (clamped) block."""
    m, k = x_shape
    n, k2 = w_shape
    if k != k2:
        return False
    # m must be sublane-tile-aligned for the ACTIVATION dtype (f32: 8,
    # bf16: 16, int8: 32): Mosaic failures at misaligned block_m surface
    # at jit COMPILE time, after the dispatch fallback has already
    # committed, so the gate has to be conservative (batch-1 decode and
    # ragged m go XLA)
    sublane = 8
    if dtype is not None:
        itemsize = jnp.dtype(dtype).itemsize
        sublane = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
    if m < sublane or m % sublane:
        return False
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    return m % bm == 0 and n % bn == 0 and k % bk == 0 and bn >= 128 \
        and bk >= 128


def xla_weight_only(x, wq, scale):
    """XLA composition fallback: widen int8 to the activation dtype
    (exact — ±127 is representable even in bf16) and apply the
    per-channel scale to the f32 ACCUMULATOR, not the [n, k] weight.
    At decode (m ≤ batch) an O(n·k) dequant pass per call would cost
    more than the dot itself; the epilogue multiply is O(m·n) — the
    same scale-the-accumulator contract the Pallas kernel uses.
    x float [..., k]; wq int8 [n, k]; scale [n] or scalar fp32.
    Returns [..., n] in x.dtype — the activation-dtype convention
    every linear in the repo follows."""
    n, k = wq.shape
    scale = jnp.broadcast_to(
        jnp.asarray(scale, jnp.float32).reshape(-1), (n,))
    acc = jax.lax.dot_general(
        x, wq.astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * scale).astype(x.dtype)


def _tpu_weight_only(x, wq, scale):
    """Registered TPU impl: the fused Pallas kernel when the static
    gates pass (TuneDB winner + blocks, shapes_supported), else the XLA
    composition. A gated-in kernel compiles or the jit fails."""
    from ..registry import pallas_disabled
    from ...core.flags import flag
    scale = jnp.asarray(scale, jnp.float32)
    lead, k = x.shape[:-1], x.shape[-1]
    m = 1
    for d in lead:
        m *= d
    n = wq.shape[0]
    if (pallas_disabled() or not flag("use_pallas_kernels")
            or scale.ndim > 1 or db_winner(m, n, k, x.dtype) == "xla"):
        return xla_weight_only(x, wq, scale)
    bm, bn, bk = tuned_blocks(m, n, k, x.dtype)
    if not shapes_supported((m, k), tuple(wq.shape), block_m=bm,
                            block_n=bn, block_k=bk, dtype=x.dtype):
        return xla_weight_only(x, wq, scale)
    y = int8_matmul_pallas(x.reshape(m, k), wq,
                           jnp.broadcast_to(scale.reshape(-1), (n,)),
                           block_m=bm, block_n=bn, block_k=bk)
    return y.reshape(lead + (n,))


def _register():
    # THE one registry op both quantization/functional.int8_matmul and
    # nn/quantized_linear.weight_only_linear resolve through (ISSUE 17
    # dedupe): per-channel weight-only int8, x float [..., k] x wq int8
    # [n, k] -> [..., n] in x.dtype.
    from ..registry import register_kernel
    register_kernel("int8_matmul", "tpu")(_tpu_weight_only)
    register_kernel("int8_matmul", "any")(xla_weight_only)


_register()


def quantized_matmul(x, wq, scale):
    """Dispatch-routed weight-only int8 matmul: the single entry every
    int8 linear call site uses (model weight_dtype='int8' projections,
    Int8Linear, functional.int8_matmul). TuneDB block configs and the
    PT_DISABLE_PALLAS kill-switch apply uniformly because dispatch
    happens here, not at the callers."""
    from ..registry import dispatch
    return dispatch("int8_matmul")(x, wq, scale)


def _db_cfg(m, n, k, dtype):
    from .autotune import _DB
    import jax as _jax
    kind = getattr(_jax.devices()[0], "device_kind", "cpu")
    return _DB.lookup(_DB.key("int8_matmul", kind, str(dtype),
                              sm=m, sn=n, sk=k))


def tuned_blocks(m, n, k, dtype="bfloat16"):
    """Tune-DB lookup for (m, n, k); falls back to the MXU defaults."""
    try:
        cfg = _db_cfg(m, n, k, dtype)
        if cfg:
            return (cfg.get("block_m", DEFAULT_BLOCK_M),
                    cfg.get("block_n", DEFAULT_BLOCK_N),
                    cfg.get("block_k", DEFAULT_BLOCK_K))
    except Exception:
        pass
    return DEFAULT_BLOCK_M, DEFAULT_BLOCK_N, DEFAULT_BLOCK_K


def db_winner(m, n, k, dtype="bfloat16"):
    """Measured dispatch preference for this shape bucket.

    'xla' = on-hardware A/B showed the XLA dequant-matmul at least ties
    the fused kernel (v5e: the op is weight-streaming/overhead bound at
    serving shapes, so fusing the dequant buys nothing measurable —
    amortized scan-loop timings recorded in the DB entry). None = no
    measurement, caller keeps its default."""
    try:
        cfg = _db_cfg(m, n, k, dtype)
        return cfg.get("winner") if cfg else None
    except Exception:
        return None


__all__ = ["int8_matmul_pallas", "shapes_supported", "tuned_blocks",
           "db_winner", "quantized_matmul", "xla_weight_only"]
