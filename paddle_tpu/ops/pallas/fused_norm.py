"""Pallas TPU fused RMSNorm (forward + backward, custom_vjp).

Reference analogue: paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu
(rms-norm path) and python surface incubate/nn/functional/fused_rms_norm.py.

TPU-first design: the norm is HBM-bandwidth-bound, so the win is a single
pass per tensor — each row block is read once into VMEM, the mean-square
reduction and the scale multiply happen in-register, and (for backward) the
saved per-row rstd avoids recomputing the reduction. The weight gradient is
a cross-row reduction, which Pallas handles with a per-row-block partial
that XLA sums afterwards (keeps the kernel race-free without atomics —
which TPUs don't have).

Falls back to the XLA composition for ragged shapes / non-TPU backends.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from .per_shard import active_axes, batch_spec, per_shard, shards

try:
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except ImportError:  # pragma: no cover
    pltpu = None
    _HAS_PLTPU = False

DEFAULT_BLOCK_R = 256


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


# ---------------------------------------------------------------------------
# kernels ([R, D] layout; grid over row blocks)
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)              # [br, D]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)                  # [br, 1]
    o_ref[...] = (x * rstd * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    rstd_ref[...] = rstd                            # [br, 1]


def _bwd_kernel(x_ref, w_ref, rstd_ref, dy_ref, dx_ref, dwp_ref):
    x = x_ref[...].astype(jnp.float32)              # [br, D]
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)              # [1, D]-broadcastable
    rstd = rstd_ref[...]                            # [br, 1]
    xhat = x * rstd
    wdy = dy * w
    c = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx_ref[...] = ((wdy - xhat * c) * rstd).astype(dx_ref.dtype)
    # per-block partial weight grad, padded to a full (8, D) sublane tile
    # (a (1, D) block over an (nblocks, D) array violates Mosaic's sublane
    # rule — the round-2 bench died here); only sublane 0 carries data
    part = jnp.sum(dy * xhat, axis=0, keepdims=True)          # [1, D] fp32
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, part.shape[1]), 0)
    dwp_ref[...] = jnp.where(sub == 0, jnp.broadcast_to(part, sub.shape), 0.0)


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rms_norm_p(x2d, w, eps, block_r, interpret):
    out, _ = _rms_fwd(x2d, w, eps, block_r, interpret)
    return out


def _rms_fwd(x2d, w, eps, block_r, interpret):
    R, D = x2d.shape
    br = min(block_r, R)
    grid = (R // br,)
    out, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[_vmem((br, D), lambda r: (r, 0)),
                  _vmem((1, D), lambda r: (0, 0))],
        out_specs=[_vmem((br, D), lambda r: (r, 0)),
                   # rstd kept 2-D [R, 1]: rank-1 outputs trip an XLA-vs-
                   # Mosaic tiling mismatch (T(1024) vs T(256)) on real TPU
                   _vmem((br, 1), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, D), x2d.dtype),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        compiler_params=(pltpu.CompilerParams(
            dimension_semantics=("parallel",)) if pltpu else None),
        interpret=interpret,
        name="fused_rmsnorm_fwd",
    )(x2d, w.reshape(1, D))
    return out, rstd


def _rms_fwd_rule(x2d, w, eps, block_r, interpret):
    out, rstd = _rms_fwd(x2d, w, eps, block_r, interpret)
    return out, (x2d, w, rstd)


def _rms_bwd_rule(eps, block_r, interpret, res, dy):
    x2d, w, rstd = res
    R, D = x2d.shape
    br = min(block_r, R)
    nblocks = R // br
    dx, dwp = pl.pallas_call(
        _bwd_kernel,
        grid=(nblocks,),
        in_specs=[_vmem((br, D), lambda r: (r, 0)),
                  _vmem((1, D), lambda r: (0, 0)),
                  _vmem((br, 1), lambda r: (r, 0)),
                  _vmem((br, D), lambda r: (r, 0))],
        out_specs=[_vmem((br, D), lambda r: (r, 0)),
                   _vmem((8, D), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, D), x2d.dtype),
                   jax.ShapeDtypeStruct((nblocks * 8, D), jnp.float32)],
        compiler_params=(pltpu.CompilerParams(
            dimension_semantics=("parallel",)) if pltpu else None),
        interpret=interpret,
        name="fused_rmsnorm_bwd",
    )(x2d, w.reshape(1, D), rstd, dy)
    dw = jnp.sum(dwp, axis=0).astype(w.dtype)
    return dx, dw


_rms_norm_p.defvjp(_rms_fwd_rule, _rms_bwd_rule)


def _pick_block_r(R: int, D: int, block_r: int = DEFAULT_BLOCK_R) -> int:
    """Largest row block that (a) divides R, (b) is sublane-aligned, and
    (c) keeps the BACKWARD kernel's VMEM working set under budget.

    The bwd kernel holds ~6 fp32 [br, D] temporaries (x, dy, xhat, wdy
    plus in/out copies) ≈ 30·br·D bytes of scoped VMEM; the hard limit is
    16 MB (observed live: br=256 at D=4096 allocates 22.6 MB and Mosaic
    aborts the compile — the Llama-3-8B hidden size). Budget 8 MB leaves
    headroom for Mosaic's own stack."""
    budget = 8 * 1024 * 1024
    br = min(block_r, R)
    while br > 8 and (R % br or 30 * br * D > budget):
        br //= 2
    return max(br, 8)


def _shape_ok(shape) -> bool:
    """Mosaic tiling rules for one (local) activation shape."""
    D = shape[-1]
    R = max(math.prod(shape) // D, 1)
    br = _pick_block_r(R, D)
    return D % 128 == 0 and R % br == 0 and br % 8 == 0


def pallas_rms_supported(x, weight) -> bool:
    from ..registry import pallas_disabled
    if not _HAS_PLTPU or weight is None or pallas_disabled():
        return False
    return _shape_ok(x.shape)


def rms_norm_pallas(x, weight, epsilon: float = 1e-6,
                    block_r: int = DEFAULT_BLOCK_R, interpret: bool = False):
    """Fused RMS norm; XLA composition when the shape doesn't tile. Under
    a device mesh the kernel runs per shard (per_shard.py): rows split
    with the batch dimension, the weight replicated."""
    from ..norm import _rms_norm_xla
    if not pallas_rms_supported(x, weight):
        return _rms_norm_xla(x, weight, epsilon)

    def local(x, w):
        D = x.shape[-1]
        x2d = x.reshape(-1, D)
        out = _rms_norm_p(x2d, w, float(epsilon),
                          _pick_block_r(x2d.shape[0], D, block_r), interpret)
        return out.reshape(x.shape)

    act = active_axes()
    if act is None:
        return local(x, weight)
    mesh, free, sizes = act
    bs = batch_spec(free)
    n = shards(bs, sizes)
    if x.ndim < 2 or x.shape[0] % n or not _shape_ok(
            (x.shape[0] // n,) + tuple(x.shape[1:])):
        return _rms_norm_xla(x, weight, epsilon)
    spec = P(bs, *([None] * (x.ndim - 1)))
    return per_shard(local, mesh, free, (spec, P(None)), spec)(x, weight)


from ..registry import register_kernel  # noqa: E402


@register_kernel("rms_norm", "tpu")
def _rms_norm_tpu(x, weight=None, epsilon: float = 1e-6):
    return rms_norm_pallas(x, weight, epsilon)
