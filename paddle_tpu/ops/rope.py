"""Rotary position embedding.

Reference analogue: paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu and
python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py.

Implements the NEOX/Llama rotate-half convention on [b, s, h, d] tensors;
cos/sin are computed once per (seq, dim) and broadcast — XLA fuses the
elementwise rotation into adjacent matmuls.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rope_freqs(head_dim: int, max_seq: int, base: float = 10000.0,
               scaling_factor: float = 1.0, dtype=jnp.float32):
    """Precompute (cos, sin) tables [max_seq, head_dim]."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32) / scaling_factor
    freqs = jnp.outer(t, inv_freq)                 # [s, d/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [s, d]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def rope_at(positions, head_dim: int, base: float = 10000.0):
    """(cos, sin) [.., head_dim] float32 AT ``positions`` [..] (any whole
    numbers): what a decode tick whose rows stand at different positions
    needs, with no table sized by a longest sequence."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim))
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary_pos_emb(q, k, cos, sin, position_ids=None):
    """q,k: [b, s, h, d]; cos/sin: [max_seq, d] or [s, d].

    Mirrors fused_rotary_position_embedding(use_neox_rotary_style=True).
    """
    s = q.shape[1]
    if position_ids is None:
        out = _try_pallas_rope(q, k, cos[:s], sin[:s])
        if out is not None:
            return out
    if position_ids is not None:
        cos = cos[position_ids]          # [b, s, d]
        sin = sin[position_ids]
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    else:
        cos = cos[:s][None, :, None, :]  # [1, s, 1, d]
        sin = sin[:s][None, :, None, :]
    cos = cos.astype(q.dtype)
    sin = sin.astype(q.dtype)
    q_out = q * cos + rotate_half(q) * sin
    k_out = k * cos + rotate_half(k) * sin
    return q_out, k_out


def _try_pallas_rope(q, k, cos, sin):
    """Fused q+k rotation in one Pallas kernel (training path, contiguous
    positions); None -> XLA composition. The custom_vjp applies the
    transpose rotation (cos, -sin) to the q/k cotangents and computes
    EXACT table cotangents from the saved inputs (q, k, cos, sin are the
    residuals); when the tables are buffers — every model here — the
    table-grad computation and its residual use are dead and XLA's DCE
    removes them under jit. Under a device mesh the kernel runs per shard
    (pallas/per_shard.py): batch over the data axes, heads over "tp"."""
    from .registry import backend_kind, pallas_disabled
    from ..core.flags import flag
    if (pallas_disabled() or not flag("use_pallas_kernels")
            or backend_kind() != "tpu" or q.ndim != 4):
        return None
    from jax.sharding import PartitionSpec as P
    from .pallas.fused_rope import rope_supported, tuned_block_s
    from .pallas.per_shard import active_axes, per_shard, qkv_layout
    bs = tuned_block_s(q.shape[1], q.shape[3], q.dtype)
    local = lambda q, k, cos, sin: _rope_fwd_bwd(q, k, cos, sin, bs)
    act = active_axes()
    if act is None:
        if not rope_supported(tuple(q.shape), tuple(k.shape)):
            return None
        return local(q, k, cos, sin)
    mesh, free, sizes = act
    b_ax, h_ax, nb, nh = qkv_layout(free, sizes)
    if q.shape[0] % nb or q.shape[2] % nh or k.shape[2] % nh:
        return None
    cut = lambda sh: (sh[0] // nb, sh[1], sh[2] // nh, sh[3])
    if not rope_supported(cut(q.shape), cut(k.shape)):
        return None
    qk, tab = P(b_ax, None, h_ax, None), P(None, None)
    return per_shard(local, mesh, free, (qk, qk, tab, tab),
                     (qk, qk))(q, k, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rope_fwd_bwd(q, k, cos, sin, block_s):
    from .pallas.fused_rope import fused_rope_pallas
    return fused_rope_pallas(q, k, cos, sin, block_s=block_s)


def _rope_fwd(q, k, cos, sin, block_s):
    out = _rope_fwd_bwd(q, k, cos, sin, block_s)
    return out, (q, k, cos, sin)


def _rope_bwd(block_s, res, g):
    # rotation matrix transpose: R(theta)^T = R(-theta) -> (cos, -sin)
    from .pallas.fused_rope import fused_rope_pallas
    q, k, cos, sin = res
    gq, gk = g
    dq, dk = fused_rope_pallas(gq, gk, cos, -sin, block_s=block_s)
    # table cotangents (exact; XLA DCEs these when the tables are
    # buffers/stop_gradient'd, the common case): out = x*cos + rot(x)*sin
    f32 = jnp.float32
    dcos = (jnp.sum(gq.astype(f32) * q.astype(f32), axis=(0, 2))
            + jnp.sum(gk.astype(f32) * k.astype(f32), axis=(0, 2)))
    dsin = (jnp.sum(gq.astype(f32) * rotate_half(q).astype(f32),
                    axis=(0, 2))
            + jnp.sum(gk.astype(f32) * rotate_half(k).astype(f32),
                      axis=(0, 2)))
    return dq, dk, dcos.astype(cos.dtype), dsin.astype(sin.dtype)


_rope_fwd_bwd.defvjp(_rope_fwd, _rope_bwd)


def fused_rotary_position_embedding(q, k, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True):
    """API-parity wrapper (reference:
    python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py).
    Note argument order (sin, cos) follows the reference."""
    if cos is None or sin is None:
        raise ValueError("cos/sin tables required")
    if cos.ndim == 4:  # reference passes [1, s, 1, d]
        cos = cos[0, :, 0, :]
        sin = sin[0, :, 0, :]
    q_out, k_out = apply_rotary_pos_emb(q, k, cos, sin, position_ids)
    return q_out, k_out, v
