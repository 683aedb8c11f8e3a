"""Ulysses (all-to-all head-scatter) sequence parallelism.

The second long-context mode next to ring attention (SURVEY.md §5 marks
context parallelism absent from the reference snapshot but first-class for
the TPU build; the DeepSpeed-Ulysses paper is the published pattern).
Activations are sequence-sharded over the "sep" mesh axis; around the
attention core, one all-to-all per tensor trades the sequence sharding for
a HEAD sharding:

    [b, s/n, h, d]  --all_to_all-->  [b, s, h/n, d]
    full-sequence flash attention on h/n local heads
    [b, s, h/n, d]  --all_to_all-->  [b, s/n, h, d]

Communication is O(s·h·d/n) per device per a2a (4 of them fwd) riding ICI
— cheaper than the ring's n ppermute rounds when n is moderate and h
divides; the ring wins when h < n or when overlap with per-step compute
matters. Both are exact; `models/llama.py` picks via config.sp_mode.

GQA: when h_kv % n == 0 K/V all-to-all the same way and the contiguous
head slices stay group-aligned (q head j maps to kv head j//(h/h_kv);
slice i of q maps exactly onto slice i of kv). When h_kv < n with
n % h_kv == 0, K/V heads expand only to n (factor n/h_kv — each device's
q slice sits inside one kv group, so expanded head i IS that group);
only the ragged remainder falls back to full h expansion. Llama-70B
(h_kv=8) at sep=16 pays 2x KV bandwidth, not 8x.

The all-to-alls are linear ops with registered transposes, so jax AD
differentiates straight through them — only the attention core carries a
custom VJP (the Pallas flash kernel's).
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


def _local_attn(q, k, v, causal, scale, interpret):
    """Full-sequence attention on the local head slice. Dispatches to the
    Pallas flash kernel (TPU) / its interpret path or the XLA composition
    (CPU test meshes) via the normal kernel gate."""
    from ..ops.pallas.flash_attention import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  interpret=interpret)


def gqa_expand_factor(h: int, h_kv: int, n: int) -> int:
    """KV head repeat factor before the all-to-all. 1 when h_kv already
    splits over the axis. MINIMAL expansion n/h_kv when h_kv | n: n | h
    makes each device's q-head slice [i·h/n, (i+1)·h/n) lie inside ONE
    original kv group (h/n divides h/h_kv ⟺ h_kv | n), and expanded kv
    head i = original i·h_kv/n is precisely that group — Llama-70B
    (h=64, h_kv=8) at sep=16 pays 2x KV bandwidth, not 8x. Ragged
    remainders expand fully to h (correctness-grade)."""
    if h_kv % n == 0:
        return 1
    if n % h_kv == 0:
        return n // h_kv
    return h // h_kv


def ulysses_supported(h: int, h_kv: int, n: int) -> bool:
    """Query heads must split evenly over the sep axis, and KV heads must
    either split too or expand to h exactly (GQA group expansion)."""
    return n > 1 and h % n == 0 and (h_kv % n == 0 or h % h_kv == 0)


def ulysses_attention(q, k, v, causal: bool = True, axis: str = "sep",
                      scale: Optional[float] = None, mesh=None,
                      interpret: Optional[bool] = None):
    """Exact attention over sequence-sharded q/k/v via head all-to-all.

    q/k/v: [b, s, h(_kv), d] GLOBAL arrays sharded (or shardable) along s
    over ``axis``. Returns [b, s, h, d] with the same sharding. Falls back
    to the single-device path when no mesh/axis is active.
    """
    hm = current_mesh() if mesh is None else mesh
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if hm is None or hm.axis_size(axis) <= 1:
        from ..ops.attention import _sdpa_xla
        return _sdpa_xla(q, k, v, causal=causal, scale=scale)

    n = hm.axis_size(axis)
    h, h_kv = q.shape[2], k.shape[2]
    if not ulysses_supported(h, h_kv, n):
        raise ValueError(
            f"ulysses_attention: need h % n == 0 and (h_kv % n == 0 or "
            f"h % h_kv == 0); got h={h}, h_kv={h_kv}, {axis}={n} — use "
            f"ring_attention instead")
    r = gqa_expand_factor(h, h_kv, n)
    if r > 1:
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    if interpret is None:
        from ..ops.registry import backend_kind
        interpret = backend_kind() != "tpu"

    def local_fn(q_l, k_l, v_l):
        # [b, s/n, h, d] -> [b, s, h/n, d]: split heads, concat sequence
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=2, concat_axis=1, tiled=True)
        qh, kh, vh = a2a(q_l), a2a(k_l), a2a(v_l)
        out = _local_attn(qh, kh, vh, causal, scale, interpret)
        # [b, s, h/n, d] -> [b, s/n, h, d]: split sequence, concat heads
        return jax.lax.all_to_all(out, axis_name=axis, split_axis=1,
                                  concat_axis=2, tiled=True)

    fn = shard_map(local_fn, mesh=hm.mesh, axis_names=frozenset({axis}),
                   in_specs=(P(None, axis, None, None),) * 3,
                   out_specs=P(None, axis, None, None), check_vma=False)
    return fn(q, k, v)


__all__ = ["ulysses_attention", "ulysses_supported"]
