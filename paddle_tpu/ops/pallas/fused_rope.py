"""Pallas TPU fused rotary embedding (q and k in one kernel).

Reference analogue: paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu —
one kernel applies the rotation to q and k together so the cos/sin tables
cross HBM once.

TPU design: rope is pure VPU work and HBM-bandwidth-bound. The fusion win
over XLA is structural: one pallas_call reads cos/sin ONCE per sequence
block and rotates BOTH q and k tiles while they sit in VMEM, instead of
two elementwise fusions each re-reading the tables. Whether that beats
XLA's fusion on real hardware is an empirical question that no benchmark
cell has answered yet (the kernel has a name in the trace, ``fused_rope``,
and no share metric); the dispatch keeps the XLA path unless the kernel is
enabled and eligible (training layout, contiguous positions).

Layout: q,k [b, s, h, d] (d = head_dim, lane-aligned at 128/64); cos/sin
[s, d]. Grid over (b, s/block_s). position_ids path (gathered tables)
stays XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except ImportError:  # pragma: no cover
    pltpu = None
    _HAS_PLTPU = False

DEFAULT_BLOCK_S = 512


def _rope_kernel(q_ref, k_ref, cos_ref, sin_ref, qo_ref, ko_ref):
    # half-sliced form: out1 = x1*c1 - x2*s1, out2 = x2*c2 + x1*s2
    # (identical to concat([-x2, x1]) rotate-half, but never materializes
    # the full-width rot/product temporaries — the concat form blew the
    # 16M scoped-vmem stack limit at block_s=512, h=16, d=128 on v5e)
    half = cos_ref.shape[-1] // 2
    c1 = cos_ref[:, :half][None, :, None, :]     # [1, bs, 1, d/2]
    c2 = cos_ref[:, half:][None, :, None, :]
    s1 = sin_ref[:, :half][None, :, None, :]
    s2 = sin_ref[:, half:][None, :, None, :]
    for ref, out in ((q_ref, qo_ref), (k_ref, ko_ref)):
        x1 = ref[..., :half].astype(jnp.float32)  # [1, bs, h, d/2]
        x2 = ref[..., half:].astype(jnp.float32)
        out[..., :half] = (x1 * c1 - x2 * s1).astype(out.dtype)
        out[..., half:] = (x2 * c2 + x1 * s2).astype(out.dtype)


def fused_rope_pallas(q, k, cos, sin, *, block_s: int = DEFAULT_BLOCK_S,
                      interpret: bool = False):
    """Rotate q and k ([b, s, h, d]) by cos/sin ([s, d]) in one kernel."""
    if not _HAS_PLTPU:
        raise ImportError("pallas.tpu unavailable; use the XLA rope path")
    b, s, h, d = q.shape
    assert k.shape[0] == b and k.shape[1] == s and k.shape[3] == d
    assert cos.shape == (s, d) and sin.shape == (s, d)
    hk = k.shape[2]
    block_s = _fit_block_s(min(block_s, s), h, hk, d)
    if s % block_s:
        raise ValueError(f"seq {s} does not divide block_s {block_s}")
    grid = (b, s // block_s)
    cf = jnp.float32

    qo, ko = pl.pallas_call(
        _rope_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, h, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, block_s, hk, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((block_s, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_s, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, h, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, block_s, hk, d), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype)],
        compiler_params=(pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
            if not interpret else None),
        interpret=interpret,
        name="fused_rope",
    )(q, k, cos.astype(cf), sin.astype(cf))
    return qo, ko


_VMEM_BUDGET = 12 * 2**20  # leave headroom under the 16M scoped-vmem limit


def _fit_block_s(block_s: int, h: int, hk: int, d: int) -> int:
    """Largest power-of-two block_s whose VMEM working set fits.

    Per sequence position: q+k blocks in and out (bf16, double-buffered by
    Mosaic) plus the f32 half-width temporaries the kernel body creates
    (~3 live full-width-f32-equivalents per tensor) plus cos/sin (f32).
    Estimate ~= block_s * [(h+hk)*d*(2B*2*2 + 4B*3) + 2*d*4B]."""
    per_s = (h + hk) * d * (2 * 2 * 2 + 4 * 3) + 2 * d * 4
    while block_s > 8 and block_s * per_s > _VMEM_BUDGET:
        block_s //= 2
    return block_s


def rope_supported(q_shape, k_shape, d_lane: int = 128) -> bool:
    """Training-path eligibility: 4D, same b/s/d, lane-aligned head_dim,
    sublane-aligned seq block."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    b, s, h, d = q_shape
    if k_shape[0] != b or k_shape[1] != s or k_shape[3] != d:
        return False
    return d % d_lane == 0 and s % 8 == 0 and s >= 8


def tuned_block_s(s, d, dtype="bfloat16"):
    try:
        from .autotune import _DB
        kind = getattr(jax.devices()[0], "device_kind", "cpu")
        cfg = _DB.lookup(_DB.key("fused_rope", kind, str(dtype), ss=s, d=d))
        # the DB key BUCKETS s, so a recorded block may not divide this
        # exact seq — validate before trusting it
        if cfg and s % int(cfg.get("block_s", DEFAULT_BLOCK_S)) == 0:
            return int(cfg.get("block_s", DEFAULT_BLOCK_S))
    except Exception:
        pass
    bs = next((c for c in (512, 256, 128, 64, 32, 16, 8)
               if s % c == 0), 8)
    return bs


__all__ = ["fused_rope_pallas", "rope_supported", "tuned_block_s"]
