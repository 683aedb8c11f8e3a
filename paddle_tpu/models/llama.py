"""Llama-family decoder-only transformer.

Capability target (BASELINE.json): Llama-3 8B/70B pretraining recipes.
Reference model analogue: PaddleNLP's Llama on the reference's fused kernels
(fused_rms_norm, fused_rope, flash_attention —
python/paddle/incubate/nn/functional/, phi/kernels/fusion/gpu/).

TPU-first design decisions:
- bf16 activations, fp32 norm statistics; big fused matmuls for the MXU
  (QKV fused into one projection, gate+up fused).
- GSPMD sharding annotations on every Parameter (Megatron layout: column
  parallel over "tp" for qkv/gate/up, row parallel for o/down; embeddings
  vocab-sharded; all params additionally sharded over "fsdp" for ZeRO-3).
  The same module runs 1-chip (annotations ignored) or on any mesh.
- static-shape causal flash attention via ops.attention (Pallas on TPU).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import rope as rope_ops
from ..ops import norm as norm_ops
from .serving_core import ServingCore


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    dtype: str = "float32"
    # recompute (activation checkpointing) granularity:
    #   "none"      — save all activations
    #   "selective" — save projection/matmul outputs, recompute the cheap
    #                 elementwise/attention-score work (reference analogue:
    #                 recompute_granularity="core_attn" in the fleet
    #                 recipes; policy = XLA-side dots_with_no_batch_dims)
    #   "full"      — save only layer boundaries
    recompute: str = "none"
    # sequence parallel: shard activations along seq dim over "sep"
    sequence_parallel: bool = False
    # long-context attention over the sep axis: "ring" rotates K/V blocks
    # (works for any head count, overlaps compute with ppermute) or
    # "ulysses" all-to-alls heads for full-sequence local flash (cheaper
    # comm when heads divide the axis; parallel/ulysses.py)
    sp_mode: str = "ring"
    # training loss head:
    #   "fused" — blockwise lm_head-projection + CE, the [b, s, vocab]
    #             logits never materialize (ops/pallas/fused_vocab_ce.py;
    #             reference posture: c_softmax_with_cross_entropy_op.cu)
    #   "naive" — materialize logits, then causal_lm_loss (the escape
    #             hatch; also forced by env PT_NAIVE_LOSS_HEAD=1)
    loss_impl: str = "fused"
    # serving quantization (ISSUE 17):
    #   weight_dtype "int8" — projections (qkv/o/gate_up/down/lm_head)
    #     stored per-channel int8 [n, k] + fp32 scale [n]; every linear
    #     dispatches through the ops-registry "int8_matmul" op (fused
    #     Pallas dequant-matmul on TPU, XLA convert+scale elsewhere).
    #     Serving-only: forward(labels=...) raises. Produce weights with
    #     quantization.serving.quantize_model / tools/quantize_ckpt.py.
    #   kv_dtype "int8" — paged KV pools allocate int8 with per-page fp32
    #     scales riding alongside the page table (alloc_paged_caches).
    weight_dtype: str = "native"
    kv_dtype: str = "native"

    def __post_init__(self):
        if self.recompute not in ("none", "selective", "full"):
            raise ValueError(f"recompute must be 'none'|'selective'|'full', "
                             f"got {self.recompute!r}")
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError(f"sp_mode must be 'ring'|'ulysses', "
                             f"got {self.sp_mode!r}")
        if self.loss_impl not in ("fused", "naive"):
            raise ValueError(f"loss_impl must be 'fused'|'naive', "
                             f"got {self.loss_impl!r}")
        if self.weight_dtype not in ("native", "int8"):
            raise ValueError(f"weight_dtype must be 'native'|'int8', "
                             f"got {self.weight_dtype!r}")
        if self.kv_dtype not in ("native", "int8"):
            raise ValueError(f"kv_dtype must be 'native'|'int8', "
                             f"got {self.kv_dtype!r}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must be divisible by num_attention_heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, hidden_size=4096,
                           intermediate_size=14336, num_hidden_layers=32,
                           num_attention_heads=32, num_key_value_heads=8,
                           max_position_embeddings=8192, rope_theta=500000.0, **kw)

    @staticmethod
    def llama3_70b(**kw) -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, hidden_size=8192,
                           intermediate_size=28672, num_hidden_layers=80,
                           num_attention_heads=64, num_key_value_heads=8,
                           max_position_embeddings=8192, rope_theta=500000.0, **kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        defaults = dict(vocab_size=512, hidden_size=128, intermediate_size=384,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, max_position_embeddings=256)
        defaults.update(kw)
        return LlamaConfig(**defaults)


def _normal(std):
    return I.Normal(0.0, std)


def _make_proj(layer, name, shape, cfg, sharding):
    """Create a projection parameter in the layout ``cfg.weight_dtype``
    demands. Native: float [k, n] (``shape``). int8: the transposed
    reference layout — int8 [n, k] + per-out-channel fp32 ``<name>_scale``
    [n] (weight_quantize's contract) — with the sharding tuple reversed
    to match. Both stay trainable=True so raw_parameters() (the serving
    engines' param pytree) carries them; training in int8 mode is
    refused at the loss head instead."""
    k, n = shape
    if getattr(cfg, "weight_dtype", "native") == "int8":
        setattr(layer, name, layer.create_parameter(
            [n, k], dtype="int8", initializer=I.Constant(0),
            sharding=(sharding[1], sharding[0])))
        setattr(layer, name + "_scale", layer.create_parameter(
            [n], dtype="float32", initializer=I.Constant(1.0),
            sharding=(sharding[1],)))
    else:
        setattr(layer, name, layer.create_parameter(
            shape, dtype=cfg.dtype,
            initializer=_normal(cfg.initializer_range), sharding=sharding))


def _proj(layer, x, name):
    """The one weight-matmul every Llama linear routes through: native
    weights do the plain dense matmul; int8 weights (detected by the
    ``<name>_scale`` twin) dispatch through the ops-registry
    "int8_matmul" op — fused Pallas dequant-in-VMEM on TPU gated by
    TuneDB blocks + the static shape gate (the fused_vocab_ce pattern),
    XLA convert+scale elsewhere, PT_DISABLE_PALLAS honored."""
    scale = getattr(layer, name + "_scale", None)
    if scale is not None:
        wq = getattr(layer, name)
        try:
            from ..ops.pallas.int8_matmul import quantized_matmul
        except ImportError:  # pragma: no cover - jaxlib without pallas
            w = wq.astype(jnp.float32) * jnp.asarray(
                scale, jnp.float32)[:, None]
            return jnp.matmul(x, w.T.astype(x.dtype))
        return quantized_matmul(x, wq, scale)
    return jnp.matmul(x, getattr(layer, name).astype(x.dtype))


# -- int8 paged-KV helpers (ISSUE 17) ----------------------------------------
#
# kv_dtype="int8" pools store K/V pages int8 with ONE fp32 absmax scale per
# physical page (per layer, per K/V side): the per-layer pool entry becomes
# the 4-tuple (kp, vp, kscale, vscale) — kscale/vscale are [num_pages] f32
# arrays riding alongside the page table — instead of the native (kp, vp).
# Page granularity is the sweet spot: per-tensor scales clip long-context
# outliers, per-token scales bloat metadata and break the head-major page
# stream; the page is the unit everything else already moves (COW, prefix
# sharing, handoff, the Pallas block stream), so its scale travels for free.
# Scales only GROW (monotone absmax): a token write that needs a bigger
# scale branchlessly requantizes the page it lands in — old codes shift to
# the new grid with one round per int8 element, bounding the error at half
# a quantization step, and pages never thrash between scales.

_KV_EPS = 1e-30      # scale==0 means "page all zeros"; guard the divides


def _kv_quantized(kv) -> bool:
    return len(kv) == 4


def _kv_scatter_pages(kv, phys, k_tiles, v_tiles):
    """Full-page write (prefill / chunked prefill): ``phys`` [P] physical
    page ids, tiles [n_kv, P, page, hd] float. Quantized pools compute one
    absmax scale per written page and REPLACE (page content is fully
    rewritten, so no monotone constraint applies)."""
    if not _kv_quantized(kv):
        kp, vp = kv
        return (kp.at[:, phys].set(k_tiles.astype(kp.dtype)),
                vp.at[:, phys].set(v_tiles.astype(vp.dtype)))
    kp, vp, ks, vs = kv

    def one(pool, scale, tiles):
        t = tiles.astype(jnp.float32)
        s = jnp.max(jnp.abs(t), axis=(0, 2, 3)) / 127.0          # [P]
        q = jnp.clip(jnp.round(t / jnp.maximum(s, _KV_EPS)[None, :, None,
                                                           None]),
                     -127, 127).astype(jnp.int8)
        return (pool.at[:, phys].set(q),
                scale.at[phys].set(s.astype(scale.dtype)))
    kp, ks = one(kp, ks, k_tiles)
    vp, vs = one(vp, vs, v_tiles)
    return kp, vp, ks, vs


def _kv_scatter_tokens(kv, phys, off, k_new, v_new):
    """Token-slot write (decode / speculative verify): ``phys``/``off``
    [...] (typically [b] or [b, T]) physical page + in-page offset per
    token; ``k_new``/``v_new`` [n_kv, ..., hd] float. Quantized pools grow
    the touched pages' scales monotonically (scatter-max makes duplicate
    pages within one chunk agree on the final scale), requantize those
    pages onto the new grid, then write the new codes."""
    if not _kv_quantized(kv):
        kp, vp = kv
        n_kv, num_pages, page, hd = kp.shape
        # one [hd] ROW per (kv head, token) of the pool seen as rows: a
        # scatter over [:, phys, off] has an [n_kv, hd] window, for which
        # XLA:TPU re-lays the whole pool out and back on every call (two
        # 134 MB copies a pool a layer at the serving shape, 27 ms a tick)
        row = ((jnp.arange(n_kv, dtype=jnp.int32).reshape(
            (n_kv,) + (1,) * phys.ndim) * num_pages + phys[None]) * page
            + off[None])

        def write(pool, new):
            return pool.reshape(-1, hd).at[row].set(
                new.astype(pool.dtype)).reshape(pool.shape)
        return write(kp, k_new), write(vp, v_new)
    kp, vp, ks, vs = kv

    def one(pool, scale, new):
        t = new.astype(jnp.float32)
        amax = jnp.max(jnp.abs(t), axis=(0, -1))                 # [...]
        # per-page candidate via scatter-max: duplicates (several verify
        # tokens landing in one page) all see the same final scale
        s_new = jnp.maximum(
            scale, jnp.zeros_like(scale).at[phys].max(amax / 127.0))
        s_w = s_new[phys]                                        # [...]
        factor = jnp.where(s_w > 0,
                           scale[phys] / jnp.maximum(s_w, _KV_EPS), 0.0)
        pages = pool[:, phys].astype(jnp.float32)  # [n_kv, ..., page, hd]
        pool = pool.at[:, phys].set(
            jnp.clip(jnp.round(pages * factor[None, ..., None, None]),
                     -127, 127).astype(jnp.int8))
        q = jnp.clip(jnp.round(t / jnp.maximum(s_w, _KV_EPS)[None, ...,
                                                             None]),
                     -127, 127).astype(jnp.int8)
        return pool.at[:, phys, off].set(q), s_new
    kp, ks = one(kp, ks, k_new)
    vp, vs = one(vp, vs, v_new)
    return kp, vp, ks, vs


def _kv_gather_ctx(kv, tables):
    """Whole-table gather for the context-attention read: returns
    (k_ctx, v_ctx) [b, n_kv, S, hd] fp32, dequantized when the pool is
    int8 (convert+scale — the XLA fallback shape of the fused kernel's
    widen-in-VMEM)."""
    tables_flat = tables.reshape(-1)
    b, mp = tables.shape
    if _kv_quantized(kv):
        kp, vp, ks, vs = kv
        n_kv, _, page, hd = kp.shape

        def one(pool, scale):
            ctx = pool[:, tables_flat].astype(jnp.float32)
            ctx = ctx * scale[tables_flat][None, :, None, None]
            ctx = ctx.reshape(n_kv, b, mp * page, hd)
            return jnp.transpose(ctx, (1, 0, 2, 3))
        return one(kp, ks), one(vp, vs)
    kp, vp = kv
    n_kv, _, page, hd = kp.shape

    def one(pool):
        ctx = pool[:, tables_flat].astype(jnp.float32)
        ctx = ctx.reshape(n_kv, b, mp * page, hd)
        return jnp.transpose(ctx, (1, 0, 2, 3))
    return one(kp), one(vp)


def _kv_write_prompt(kv, tables, k, v):
    """Whole prompts' K and V [b, s, n_kv, hd] into the pages ``tables``
    [b, max_pages] maps: padded up to whole pages and written head-major
    [n_kv, b * pages, page, hd] (``_kv_scatter_pages``)."""
    b, s, n_kv, hd = k.shape
    page = kv[0].shape[2]
    np_ = -(-s // page)                       # pages holding the prompt
    pad = np_ * page - s

    def tiles(new):
        padded = jnp.pad(new, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # [b, np_, page, n_kv, hd] -> [n_kv, b*np_, page, hd]
        return jnp.transpose(
            padded.reshape(b, np_, page, n_kv, hd), (3, 0, 1, 2, 4)
        ).reshape(n_kv, b * np_, page, hd)
    return _kv_scatter_pages(kv, tables[:, :np_].reshape(-1),
                             tiles(k), tiles(v))


def _paged_decode_attention(q2, kv, tables, pos):
    """One new token a row, q2 [b, n_h, hd], over the page pools ``kv``:
    the Pallas paged kernel on a TPU, its XLA twin (K/V read in the stored
    dtype, per KV head) elsewhere or under ``force_decode_impl("dense")``."""
    from ..ops.pallas.paged_attention import (forced_decode_impl,
                                             paged_decode_attention,
                                             paged_decode_supported,
                                             paged_decode_xla)
    from ..ops.registry import backend_kind
    scales = ({"k_scales": kv[2], "v_scales": kv[3]}
              if _kv_quantized(kv) else {})
    if (forced_decode_impl() != "dense" and backend_kind() == "tpu"
            and paged_decode_supported(q2, kv[0])):
        return paged_decode_attention(q2, kv[0], kv[1], tables, pos,
                                      **scales)
    return paged_decode_xla(q2, kv[0], kv[1], tables, pos, **scales)


def _token_mean(nll, labels, ignore_index: int = -100):
    """Token-weighted mean over per-token nll (ignored rows already 0) —
    the ONE reduction both loss heads share; a drifting copy here is a
    silent fused-vs-naive divergence."""
    cnt = jnp.sum(labels != ignore_index).astype(jnp.float32)
    return jnp.sum(nll) / jnp.maximum(cnt, 1.0)


def causal_lm_loss(logits, labels, ignore_index: int = -100):
    """Token-weighted mean CE for causal-LM heads.

    When a mesh with an active "tp" axis is present, computes the loss over
    VOCAB-SHARDED logits via parallel_cross_entropy — the [b, s, vocab]
    fp32 logits tensor (the single largest activation at Llama-3's 128K
    vocab: b*s*128256*4 bytes) is never gathered or upcast whole; each tp
    shard reduces its vocab slice and psums (reference:
    c_softmax_with_cross_entropy_op.cu:1, surfaced at
    fleet/layers/mpu/mp_layers.py:741). Otherwise the dense fp32 path.
    """
    from ..parallel.mesh import current_mesh
    hm = current_mesh()
    if (hm is not None and hm.axis_size("tp") > 1
            and logits.shape[-1] % hm.axis_size("tp") == 0):
        from ..parallel.mp_layers import parallel_cross_entropy
        nll = parallel_cross_entropy(logits, labels,
                                     ignore_index=ignore_index)
        return _token_mean(nll, labels, ignore_index)
    return F.cross_entropy(logits.astype(jnp.float32), labels,
                           ignore_index=ignore_index)


def fused_loss_enabled(cfg) -> bool:
    """The fused loss head is the default; ``cfg.loss_impl='naive'`` or env
    ``PT_NAIVE_LOSS_HEAD=1`` fall back to the materialized-logits path."""
    import os
    return (getattr(cfg, "loss_impl", "fused") == "fused"
            and not os.environ.get("PT_NAIVE_LOSS_HEAD"))


def fused_causal_lm_loss(hidden, w, labels, ignore_index: int = -100):
    """Token-weighted mean CE(hidden @ w, labels) with the [b, s, vocab]
    logits NEVER materialized — at Llama-3's 128K vocab that fp32 tensor
    (b*s*128256*4 bytes) is the step's largest activation; the blockwise
    kernel (ops/pallas/fused_vocab_ce.py) keeps peak loss-head memory at
    O(b*s*block_v). When a mesh with an active "tp" axis is present and
    ``w`` is vocab-sharded, each shard runs the fused blockwise pass over
    its [H, V/tp] slice and the shards combine with pmax/psum
    (parallel_fused_linear_cross_entropy) — the fused analogue of
    parallel_cross_entropy, so TP never pays the projection-store either."""
    from ..parallel.mesh import current_mesh
    hm = current_mesh()
    if (hm is not None and hm.axis_size("tp") > 1
            and w.shape[-1] % hm.axis_size("tp") == 0):
        from ..parallel.mp_layers import parallel_fused_linear_cross_entropy
        nll = parallel_fused_linear_cross_entropy(
            hidden, w, labels, ignore_index=ignore_index)
        return _token_mean(nll, labels, ignore_index)
    from ..ops.pallas.fused_vocab_ce import fused_linear_cross_entropy
    return fused_linear_cross_entropy(hidden, w, labels,
                                      ignore_index=ignore_index)


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        d, hd = cfg.hidden_size, cfg.head_dim
        n_h, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
        # fused QKV: [d, (n_h + 2*n_kv) * hd], column-parallel over tp
        _make_proj(self, "qkv_proj", [d, (n_h + 2 * n_kv) * hd], cfg,
                   sharding=("fsdp", "tp"))
        # output proj: row-parallel over tp
        _make_proj(self, "o_proj", [n_h * hd, d], cfg,
                   sharding=("tp", "fsdp"))

    def _qkv_rope(self, x, cos, sin, position_ids=None):
        """Fused QKV projection + head split + rotary embedding — shared by
        every forward/prefill/decode variant (dense and paged)."""
        cfg = self.cfg
        b, s, _ = x.shape
        n_h, n_kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        qkv = _proj(self, x, "qkv_proj")
        q, k, v = jnp.split(qkv, [n_h * hd, (n_h + n_kv) * hd], axis=-1)
        q = q.reshape(b, s, n_h, hd)
        k = k.reshape(b, s, n_kv, hd)
        v = v.reshape(b, s, n_kv, hd)
        q, k = rope_ops.apply_rotary_pos_emb(q, k, cos, sin, position_ids)
        return q, k, v

    def forward(self, x, cos, sin, position_ids=None, attn_mask=None,
                segment_ids=None):
        cfg = self.cfg
        b, s, d = x.shape
        n_h, hd = cfg.num_attention_heads, cfg.head_dim
        q, k, v = self._qkv_rope(x, cos, sin, position_ids)
        out = self._sp_attention(q, k, v, attn_mask, segment_ids)
        if out is None:
            if cfg.use_flash_attention:
                out = F.scaled_dot_product_attention(
                    q, k, v, attn_mask=attn_mask, is_causal=True,
                    training=self.training, segment_ids=segment_ids)
            else:
                from ..ops.attention import _sdpa_xla
                out = _sdpa_xla(q, k, v, attn_mask=attn_mask, causal=True,
                                segment_ids=segment_ids)
        out = out.reshape(b, s, n_h * hd)
        return _proj(self, out, "o_proj")

    def _sp_attention(self, q, k, v, attn_mask, segment_ids=None):
        """Long-context path over the "sep" axis (SURVEY §5): the K/V ring
        of flash blocks or Ulysses head all-to-all — never a dense [s, s]
        score tensor. Returns None when sequence parallelism is inactive.
        Packed sequences (``segment_ids``) route through the RING — the
        segment ids rotate with their K/V blocks and the flash kernel
        masks cross-segment pairs; Ulysses has no segment path (its
        sep-degree GQA expansion and the segment tiles conflict), so
        sp_mode='ulysses' + packing raises rather than silently
        gathering the sequence."""
        cfg = self.cfg
        if not cfg.sequence_parallel or attn_mask is not None:
            return None
        from ..parallel.mesh import current_mesh
        hm = current_mesh()
        if hm is None or hm.axis_size("sep") <= 1:
            return None
        if cfg.sp_mode == "ulysses":
            if segment_ids is not None:
                raise NotImplementedError(
                    "segment_ids (packed sequences) with sp_mode='ulysses' "
                    "is not supported — use sp_mode='ring' (the ring "
                    "rotates segment ids with their K/V blocks) or unpack "
                    "the batch.")
            from ..parallel.ulysses import (ulysses_attention,
                                            ulysses_supported)
            if ulysses_supported(cfg.num_attention_heads,
                                 cfg.num_key_value_heads,
                                 hm.axis_size("sep")):
                return ulysses_attention(q, k, v, causal=True)
        from ..parallel.ring_attention import ring_attention
        return ring_attention(q, k, v, causal=True,
                              segment_ids=segment_ids)

    # -- KV-cache inference paths ------------------------------------------

    def prefill(self, x, cos, sin, max_len: int):
        """Full-sequence forward that also materializes a dense KV cache
        [b, max_len, n_kv, hd] holding the prompt's keys/values (inference
        analogue of the reference's fused multi-transformer prefill)."""
        cfg = self.cfg
        b, s, _ = x.shape
        n_h, n_kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q, k, v = self._qkv_rope(x, cos[:s], sin[:s])
        from ..ops.attention import flash_attention
        out = flash_attention(q, k, v, causal=True)
        out = out.reshape(b, s, n_h * hd)
        out = _proj(self, out, "o_proj")
        k_cache = jnp.zeros((b, max_len, n_kv, hd), k.dtype).at[:, :s].set(k)
        v_cache = jnp.zeros((b, max_len, n_kv, hd), v.dtype).at[:, :s].set(v)
        return out, (k_cache, v_cache)

    def decode(self, x, cos, sin, pos, kv_cache):
        """One-token step: x [b, 1, d], pos [b] current position; scatters
        the new k/v into the cache and attends over positions <= pos
        (dense-cache decode, reference masked_multihead_attention shape)."""
        cfg = self.cfg
        b = x.shape[0]
        n_h, n_kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        k_cache, v_cache = kv_cache
        q, k, v = self._qkv_rope(x, cos, sin, pos.reshape(b, 1))
        b_idx = jnp.arange(b)
        k_cache = k_cache.at[b_idx, pos].set(k[:, 0])
        v_cache = v_cache.at[b_idx, pos].set(v[:, 0])
        if n_kv != n_h:
            rep = n_h // n_kv
            k_full = jnp.repeat(k_cache, rep, axis=2)
            v_full = jnp.repeat(v_cache, rep, axis=2)
        else:
            k_full, v_full = k_cache, v_cache
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        logits = jnp.einsum("bhd,bthd->bht", q[:, 0].astype(jnp.float32),
                            k_full.astype(jnp.float32)) * scale
        t_idx = jnp.arange(k_cache.shape[1])[None, None, :]
        logits = jnp.where(t_idx <= pos[:, None, None], logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bht,bthd->bhd", p, v_full.astype(jnp.float32))
        out = out.astype(x.dtype).reshape(b, 1, n_h * hd)
        return _proj(self, out, "o_proj"), (k_cache, v_cache)


    # -- paged-KV (vLLM-style) inference paths ------------------------------

    def alloc_pool(self, num_pages: int, page_size: int):
        """This layer's page pool entry: head-major K and V pools
        [H_kv, num_pages, page_size, hd], (kp, vp) native or (kp, vp,
        kscale, vscale) under ``kv_dtype="int8"``: int8 pages + one fp32
        absmax scale per physical page, per K/V side (ISSUE 17). Scales
        start at 0 = "page holds nothing": dequant of an unwritten page is
        exactly the all-zeros page a native pool starts with."""
        cfg = self.cfg
        shape = (cfg.num_key_value_heads, num_pages, page_size, cfg.head_dim)
        if getattr(cfg, "kv_dtype", "native") == "int8":
            return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                    jnp.zeros((num_pages,), jnp.float32),
                    jnp.zeros((num_pages,), jnp.float32))
        dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def prefill_paged(self, x, cos, sin, kv, tables):
        """Prompt pass writing K/V into head-major page pools
        [H_kv, num_pages, page_size, hd] via ``tables`` [b, max_pages]
        (reference capability: block_multi_head_attention_kernel.cu's
        prefill write path). ``kv`` is the per-layer pool entry —
        (kp, vp) native or (kp, vp, kscale, vscale) int8 — and is
        returned updated. Prompt length is padded up to a page multiple
        inside the pool; padded slots sit beyond seq_len and are never
        unmasked before being overwritten by decode steps."""
        cfg = self.cfg
        b, s, _ = x.shape
        n_h, hd = cfg.num_attention_heads, cfg.head_dim
        q, k, v = self._qkv_rope(x, cos[:s], sin[:s])
        # through the dispatcher, like forward(): the flash kernel on TPU.
        # The dense XLA composition holds two f32 [h, s, s] score tensors
        # (8.6 GB at 32 heads, s=6016) — a long prompt could not prefill
        # beside the weights on one 16 GB chip
        from ..ops.attention import flash_attention
        out = flash_attention(q, k, v, causal=True)
        out = out.reshape(b, s, n_h * hd)
        out = _proj(self, out, "o_proj")
        return out, _kv_write_prompt(kv, tables, k, v)

    def _paged_ctx_attention(self, q, positions, kv, tables):
        """Full-table-span paged attention read: queries ``q``
        [b, C, n_h, hd] at absolute ``positions`` [b, C] gather the whole
        table (static shape: max_pages * page), GQA-expand, and attend
        causally by j_global <= position — O(C * max_len), the same total
        work order as one full-prompt pass. Shared by the chunked-prefill
        extend (shared page-aligned offset per row) and the speculative
        verify step (per-row positions); the causal mask is per row, which
        reduces to the shared-offset mask when rows agree. Int8 pools are
        dequantized in the gather (convert + per-page scale)."""
        cfg = self.cfg
        n_h, n_kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        b, C = positions.shape
        k_ctx, v_ctx = _kv_gather_ctx(kv, tables)    # [b, n_kv, S, hd] f32
        S = k_ctx.shape[2]
        rep = n_h // n_kv
        k_ctx = jnp.repeat(k_ctx, rep, axis=1)       # [b, n_h, S, hd]
        v_ctx = jnp.repeat(v_ctx, rep, axis=1)
        qf = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)
        scores = jnp.einsum("bhcd,bhsd->bhcs", qf, k_ctx) / (hd ** 0.5)
        j = jnp.arange(S, dtype=jnp.int32)[None, None, None, :]
        scores = jnp.where(j <= positions[:, None, :, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhcs,bhsd->bhcd", probs, v_ctx)
        return jnp.transpose(out, (0, 2, 1, 3)).reshape(b, C, n_h * hd)

    def prefill_chunk_paged(self, x, cos, sin, offset, kv, tables):
        """Chunked-prefill step (Sarathi/vLLM-style prefill-extend): a
        C-token chunk at positions [offset, offset+C) writes its K/V
        pages and attends over the FULL paged history plus itself.
        ``offset`` is traced (no recompile per chunk index) and must be
        page-aligned with C a page multiple — the engine enforces both.
        Garbage KV beyond the true prompt (final-chunk padding) is never
        attended by any REAL query position and is overwritten by later
        decode writes — the same invariant as the padded full prefill."""
        cfg = self.cfg
        b, C, _ = x.shape
        n_h, n_kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        page = kv[0].shape[2]
        positions = offset + jnp.arange(C, dtype=jnp.int32)[None, :]
        q, k, v = self._qkv_rope(x, cos, sin,
                                 jnp.broadcast_to(positions, (b, C)))
        npg = C // page
        max_pages = tables.shape[1]
        pidx = offset // page + jnp.arange(npg, dtype=jnp.int32)
        # a final chunk larger than the remaining table (prompt tail with
        # prefill_chunk > page_size) routes its overflow tiles to page 0
        # EXPLICITLY — the serving engine reserves page 0 as the garbage
        # page (chunked prefill is engine-path only), and relying on
        # jnp.take/scatter OOB-drop semantics instead would break under a
        # refactor to clamping indexers
        valid = pidx < max_pages
        phys = jnp.take(tables, jnp.minimum(pidx, max_pages - 1), axis=1)
        phys = jnp.where(valid[None, :], phys, 0)    # [b, npg]

        def tiles(new):
            return jnp.transpose(
                new.reshape(b, npg, page, n_kv, hd), (3, 0, 1, 2, 4)
            ).reshape(n_kv, b * npg, page, hd)
        kv = _kv_scatter_pages(kv, phys.reshape(-1), tiles(k), tiles(v))

        out = self._paged_ctx_attention(
            q, jnp.broadcast_to(positions, (b, C)), kv,
            tables).astype(x.dtype)
        return _proj(self, out, "o_proj"), kv

    def decode_paged(self, x, cos, sin, pos, kv, tables):
        """One-token step over the page pools: writes the new K/V into the
        page slot for position ``pos`` and attends via the Pallas paged
        kernel (XLA gather fallback off-TPU). A ``force_decode_impl``
        scope ("dense") routes the attention through the XLA gather path
        (K/V read in the stored dtype, per KV head) — the serving
        engine's context-aware dense/paged dispatch uses it at or below
        the measured crossover length, which on v5e is 0: no context."""
        cfg = self.cfg
        b = x.shape[0]
        n_h, hd = cfg.num_attention_heads, cfg.head_dim
        page = kv[0].shape[2]
        q, k, v = self._qkv_rope(x, cos, sin, pos.reshape(b, 1))
        b_idx = jnp.arange(b)
        phys = tables[b_idx, pos // page]          # [b]
        off = pos % page
        kv = _kv_scatter_tokens(kv, phys, off,
                                jnp.swapaxes(k[:, 0], 0, 1),
                                jnp.swapaxes(v[:, 0], 0, 1))
        out = _paged_decode_attention(q[:, 0], kv, tables, pos)
        out = out.reshape(b, 1, n_h * hd).astype(x.dtype)
        return _proj(self, out, "o_proj"), kv

    def decode_verify_paged(self, x, cos, sin, pos, kv, tables):
        """Speculative-verify step: T tokens per row at PER-ROW positions
        ``pos[b] .. pos[b]+T-1`` (unlike ``prefill_chunk_paged``'s shared,
        page-aligned offset) — writes all T K/V slots, then attends
        causally over the full paged history plus the in-chunk prefix.
        One weight pass scores every draft position (the point of
        speculative decoding: decode is bandwidth-bound, so T positions
        cost ~one token's weight traffic).

        Writes past a row's table span route to the reserved garbage page
        EXPLICITLY (draft positions may legitimately poke past the
        claimed/claimable region near max_len; the engine only ever
        COMMITS tokens whose pages it claimed). Stale draft K/V left in
        real pages by a rejected suffix is overwritten by the next verify
        chunk before anything attends to it — positions only advance by
        the committed prefix, and every chunk rewrites its own T slots."""
        page = kv[0].shape[2]
        T = x.shape[1]
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        q, k, v = self._qkv_rope(x, cos, sin, positions)
        max_pages = tables.shape[1]
        pidx = positions // page                         # [b, T]
        valid = pidx < max_pages
        phys = jnp.take_along_axis(tables,
                                   jnp.minimum(pidx, max_pages - 1), axis=1)
        phys = jnp.where(valid, phys, 0)                 # garbage page
        off = positions % page

        kv = _kv_scatter_tokens(kv, phys, off,           # new [b, T, kv, hd]
                                jnp.transpose(k, (2, 0, 1, 3)),
                                jnp.transpose(v, (2, 0, 1, 3)))
        out = self._paged_ctx_attention(q, positions, kv,
                                        tables).astype(x.dtype)
        return _proj(self, out, "o_proj"), kv


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        d, m = cfg.hidden_size, cfg.intermediate_size
        # fused gate+up: column-parallel; down: row-parallel
        _make_proj(self, "gate_up_proj", [d, 2 * m], cfg,
                   sharding=("fsdp", "tp"))
        _make_proj(self, "down_proj", [m, d], cfg, sharding=("tp", "fsdp"))

    def forward(self, x):
        gu = _proj(self, x, "gate_up_proj")
        g, u = jnp.split(gu, 2, axis=-1)
        return _proj(self, F.silu(g) * u, "down_proj")


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                          dtype="float32")
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps, dtype="float32")
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cos, sin, position_ids=None, attn_mask=None,
                segment_ids=None):
        h = x + self.self_attn(self.input_layernorm(x), cos, sin, position_ids,
                               attn_mask, segment_ids)
        return h + self.mlp(self.post_attention_layernorm(h))

    def prefill(self, x, cos, sin, max_len: int):
        a, cache = self.self_attn.prefill(self.input_layernorm(x), cos, sin,
                                          max_len)
        h = x + a
        return h + self.mlp(self.post_attention_layernorm(h)), cache

    def decode(self, x, cos, sin, pos, kv_cache):
        a, cache = self.self_attn.decode(self.input_layernorm(x), cos, sin,
                                         pos, kv_cache)
        h = x + a
        return h + self.mlp(self.post_attention_layernorm(h)), cache


class LlamaModel(nn.Layer, ServingCore):
    optional_programs = ("prefill_chunk_paged", "decode_verify_paged")

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            initializer=_normal(cfg.initializer_range), sharding=("tp", "fsdp"))
        self.layers = nn.LayerList([LlamaDecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, dtype="float32")
        cos, sin = rope_ops.rope_freqs(cfg.head_dim, cfg.max_position_embeddings,
                                       cfg.rope_theta)
        self.register_buffer("rope_cos", cos, persistable=False)
        self.register_buffer("rope_sin", sin, persistable=False)

    def _seq_shard(self, x):
        """GSPMD sequence parallelism: constrain activations to be sharded
        along seq over 'sep' (reference analogue: SegmentParallel sep axis +
        sequence_parallel_utils scatter/gather, SURVEY.md §5 long-context)."""
        if not self.cfg.sequence_parallel:
            return x
        from ..parallel.mesh import current_mesh
        from jax.sharding import PartitionSpec, NamedSharding
        hm = current_mesh()
        if hm is None or hm.axis_size("sep") <= 1:
            return x
        sh = NamedSharding(hm.mesh, PartitionSpec(("dp", "fsdp"), "sep", None))
        return jax.lax.with_sharding_constraint(x, sh)

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                segment_ids=None):
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        cos, sin = self.rope_cos, self.rope_sin
        if position_ids is None:
            # default positions 0..s-1: pre-slice so broadcasting is static
            s = input_ids.shape[1]
            cos, sin = cos[:s], sin[:s]
        x = self._seq_shard(x)
        if self.cfg.recompute in ("full", "selective"):
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if self.cfg.recompute == "selective" else None)
            ckpt = jax.checkpoint(
                lambda layer, h: layer(h, cos, sin, position_ids, attn_mask,
                                       segment_ids),
                static_argnums=(0,), policy=policy)
            for layer in self.layers:
                x = self._seq_shard(ckpt(layer, x))
        else:
            for layer in self.layers:
                x = self._seq_shard(layer(x, cos, sin, position_ids, attn_mask,
                                          segment_ids))
        return self.norm(x)

    # -- KV-cache inference paths ------------------------------------------

    def prefill(self, input_ids, max_len: int):
        """Prompt pass returning (hidden, caches): caches is a list of
        per-layer (k_cache, v_cache) sized to max_len."""
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        caches = []
        for layer in self.layers:
            x, cache = layer.prefill(x, self.rope_cos, self.rope_sin, max_len)
            caches.append(cache)
        return self.norm(x), caches

    def decode_step(self, token_ids, pos, caches):
        """token_ids [b] → (hidden [b, 1, d], caches) one position forward."""
        x = jnp.take(self.embed_tokens, token_ids[:, None], axis=0)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache = layer.decode(x, self.rope_cos, self.rope_sin, pos, cache)
            new_caches.append(cache)
        return self.norm(x), new_caches

    # -- paged-KV (vLLM-style) inference paths ------------------------------

    def _paged_layers(self, attend, ids, at, pools, tables):
        """The ONE paged layer loop. ``attend`` is the attention's paged
        method and ``at`` its position arguments: all that the four
        programs below differ in."""
        x = jnp.take(self.embed_tokens, ids, axis=0)
        new_pools = []
        for layer, kv in zip(self.layers, pools):
            a, kv = attend(layer.self_attn, layer.input_layernorm(x),
                           self.rope_cos, self.rope_sin, *at, kv, tables)
            h = x + a
            x = h + layer.mlp(layer.post_attention_layernorm(h))
            new_pools.append(kv)
        return self.norm(x), new_pools

    # the ``ServingCore`` programs over head-major page pools, a layer's
    # whole state: ``slot_state`` (empty) goes back as it came, and nothing
    # is counted

    def prefill_paged(self, input_ids, pools, tables, slot_state, slot,
                      last_idx):
        return self._paged_layers(LlamaAttention.prefill_paged, input_ids,
                                  (), pools, tables) + (slot_state,)

    def prefill_chunk_paged(self, input_ids, offset, pools, tables):
        return self._paged_layers(LlamaAttention.prefill_chunk_paged,
                                  input_ids, (offset,), pools, tables)

    def decode_step_paged(self, token_ids, pos, pools, tables, slot_state):
        return self._paged_layers(
            LlamaAttention.decode_paged, token_ids[:, None], (pos,), pools,
            tables) + (slot_state, None)

    def decode_verify_paged(self, token_ids, pos, pools, tables):
        """Hidden at in-chunk index j scores the token AFTER input j: the
        engine samples targets from every row to accept or reject drafts."""
        return self._paged_layers(LlamaAttention.decode_verify_paged,
                                  token_ids, (pos,), pools, tables)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if not cfg.tie_word_embeddings:
            _make_proj(self, "lm_head", [cfg.hidden_size, cfg.vocab_size],
                       cfg, sharding=("fsdp", "tp"))
        else:
            self.add_parameter("lm_head", None)

    def logits(self, hidden):
        """Vocab projection. In weight_dtype='int8' mode (untied) this is
        the fused dequant-matmul epilogue on the vocab head: the int8
        [V, H] weight crosses HBM quantized and the registry's Pallas
        kernel widens it in VMEM and scales the f32 accumulator blockwise
        (the PR 5 fused-CE template — TuneDB blocks + static shape gate).
        Tied embeddings keep the float gather table, so the tied head
        stays a dense matmul."""
        if self.cfg.tie_word_embeddings:
            w = jnp.swapaxes(self.model.embed_tokens, 0, 1)
            return jnp.matmul(hidden, w.astype(hidden.dtype))
        return _proj(self, hidden, "lm_head")

    def forward(self, input_ids, labels=None, position_ids=None,
                attn_mask=None, segment_ids=None, return_logits=None):
        """``segment_ids`` [b, s] packs multiple documents per row: the
        flash kernel masks cross-segment attention in-kernel (reference
        varlen API: flash_attn_kernel.cu:91 cu_seqlens). Pass per-segment
        ``position_ids`` and -100 labels at segment boundaries for exact
        packed-pretraining semantics.

        With labels, the loss runs the FUSED head by default
        (cfg.loss_impl): CE computed blockwise from ``hidden`` without
        materializing [b, s, vocab] logits. The returned logits then exist
        only for API compatibility — the loss does not read them, so under
        the Trainer's jit (which keeps only the loss) XLA dead-code-
        eliminates the projection and no logits buffer is ever allocated
        (pinned by the HLO guard in tests/test_fused_vocab_ce.py).
        ``return_logits=False`` skips even the traced projection and
        returns the scalar loss alone."""
        if labels is not None and self.cfg.weight_dtype == "int8":
            raise ValueError(
                "weight_dtype='int8' is a serving-only layout (no float "
                "master weights to train); quantize a trained checkpoint "
                "with quantization.serving.quantize_model instead")
        hidden = self.model(input_ids, position_ids, attn_mask, segment_ids)
        if labels is None:
            return self.logits(hidden)
        logits = None
        with jax.named_scope("loss_head"):
            if fused_loss_enabled(self.cfg):
                w = (jnp.swapaxes(self.model.embed_tokens, 0, 1)
                     if self.cfg.tie_word_embeddings else self.lm_head)
                loss = fused_causal_lm_loss(hidden, w, labels)
            else:
                logits = self.logits(hidden)
                loss = causal_lm_loss(logits, labels)
        if return_logits is False:
            return loss
        return loss, (logits if logits is not None else self.logits(hidden))

    # -- size accounting (MFU calculator input) -----------------------------

    def num_params(self) -> int:
        return sum(int(math.prod(p.shape)) for _, p in self.named_parameters())

    def flops_per_token(self, seq_len: int, causal: bool = False) -> float:
        """Model fwd+bwd FLOPs per token (PaLM appendix-B convention:
        6*N_matmul + attention term 12*L*H*Q*T). The embedding gather is not
        a matmul, so the table is excluded from N unless tied (tied weights
        ARE the lm_head matmul). Reference analogue:
        python/paddle/utils/flops.py per-op tables.

        ``causal=True`` halves the attention term to count only the FLOPs a
        causal kernel actually executes (avg context (s+1)/2 per query):
        the honest-utilization convention; the PaLM (non-causal) number is
        the cross-paper-comparable one. The benchmark's ``mfu_pct`` uses
        neither: its family module counts the required FLOPs itself."""
        cfg = self.cfg
        n = self.num_params()
        if not cfg.tie_word_embeddings:
            n -= cfg.vocab_size * cfg.hidden_size  # gather-only table
        attn = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq_len
        if causal:
            attn *= (seq_len + 1) / (2 * seq_len)
        return 6 * n + attn


class LlamaForCausalLMPipe(nn.Layer):
    """Pipeline-parallel Llama.

    Reference analogue: PaddleNLP's ``LlamaForCausalLMPipe`` built on the
    fleet PipelineLayer/LayerDesc machinery (reference:
    fleet/meta_parallel/parallel_layers/pp_layers.py:237 + 1F1B runtime
    pipeline_parallel.py:440). TPU redesign: the decoder body is a
    ``PipelineStack`` — stage-stacked weights sharded over the "pp" mesh
    axis, microbatches advanced by XLA CollectivePermute (see
    parallel/pipeline.py); embedding / final norm / lm_head run
    GSPMD-replicated over "pp", which expresses the reference's
    SharedLayerDesc embedding tie with zero extra machinery.
    """

    def __init__(self, cfg: LlamaConfig, num_stages: int = 1,
                 num_microbatches: int = 1, pp_schedule: str = "gpipe",
                 num_chunks: int = 1):
        super().__init__()
        from ..parallel.pipeline import PipelineStack
        if pp_schedule not in PipelineStack.SCHEDULES:
            raise ValueError(f"pp_schedule must be one of "
                             f"{PipelineStack.SCHEDULES}, got {pp_schedule!r}")
        self.cfg = cfg
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches
        self.pp_schedule = pp_schedule
        self.embed_tokens = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            initializer=_normal(cfg.initializer_range), sharding=("tp", "fsdp"))
        self.decoder = PipelineStack(lambda: LlamaDecoderLayer(cfg),
                                     num_layers=cfg.num_hidden_layers,
                                     num_stages=num_stages,
                                     num_microbatches=num_microbatches,
                                     remat=(cfg.recompute == "full"),
                                     schedule=("interleaved"
                                               if pp_schedule == "interleaved"
                                               else "gpipe"),
                                     num_chunks=num_chunks)
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, dtype="float32")
        if not cfg.tie_word_embeddings:
            self.lm_head = self.create_parameter(
                [cfg.hidden_size, cfg.vocab_size], dtype=cfg.dtype,
                initializer=_normal(cfg.initializer_range),
                sharding=("fsdp", "tp"))
        else:
            self.add_parameter("lm_head", None)
        cos, sin = rope_ops.rope_freqs(cfg.head_dim, cfg.max_position_embeddings,
                                       cfg.rope_theta)
        self.register_buffer("rope_cos", cos, persistable=False)
        self.register_buffer("rope_sin", sin, persistable=False)

    def forward(self, input_ids, labels=None, return_logits=None):
        cfg = self.cfg
        s = input_ids.shape[1]
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        cos, sin = self.rope_cos[:s], self.rope_sin[:s]
        x = self.decoder(x, cos, sin)
        hidden = self.norm(x)
        w = (jnp.swapaxes(self.embed_tokens, 0, 1)
             if cfg.tie_word_embeddings else self.lm_head)
        if labels is None:
            return jnp.matmul(hidden, w.astype(hidden.dtype))
        logits = None
        with jax.named_scope("loss_head"):
            if fused_loss_enabled(cfg):
                loss = fused_causal_lm_loss(hidden, w, labels)
            else:
                logits = jnp.matmul(hidden, w.astype(hidden.dtype))
                loss = causal_lm_loss(logits, labels)
        if return_logits is False:
            return loss
        if logits is None:  # compat tuple; dead (DCE'd) when unused
            logits = jnp.matmul(hidden, w.astype(hidden.dtype))
        return loss, logits

    # -- size accounting (MFU calculator input) -----------------------------
    # Same definitions as LlamaForCausalLM: the Trainer's MFU row and the
    # sharding planner's predicted-MFU both call these, and a pipe model
    # that reported 0 flops (missing attr) made every pp config look free.

    def num_params(self) -> int:
        return sum(int(math.prod(p.shape))
                   for _, p in self.named_parameters())

    def flops_per_token(self, seq_len: int, causal: bool = False) -> float:
        cfg = self.cfg
        n = self.num_params()
        if not cfg.tie_word_embeddings:
            n -= cfg.vocab_size * cfg.hidden_size  # gather-only table
        attn = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq_len
        if causal:
            attn *= (seq_len + 1) / (2 * seq_len)
        return 6 * n + attn

    def loss_and_grads(self, params, input_ids, labels):
        """Fused 1F1B forward+backward over the pipeline (reference:
        pipeline_parallel.py:440 forward_backward_pipeline). Returns
        (mean_loss, grads) with grads matching ``params``' tree exactly —
        the Trainer uses this in place of jax.value_and_grad when
        pp_schedule == "1f1b", giving the 1F1B activation profile
        (ring of <= 2*num_stages-1 microbatch inputs per stage instead of
        all num_microbatches)."""
        from ..parallel.pipeline import microbatch, unmicrobatch
        from ..parallel.schedules import pipeline_1f1b
        cfg = self.cfg
        M, S = self.num_microbatches, self.num_stages
        s_len = input_ids.shape[1]
        cos, sin = self.rope_cos[:s_len], self.rope_sin[:s_len]
        tied = cfg.tie_word_embeddings

        prefix = "decoder.stack__"
        stacked = {leaf: params[prefix + leaf.replace(".", "__")]
                   for leaf in self.decoder._leaf_names}
        staged = self.decoder.stage_trees(stacked)

        head_params = {"norm_w": params["norm.weight"]}
        if tied:
            head_params["embed"] = params["embed_tokens"]
        else:
            head_params["lm_head"] = params["lm_head"]

        def embed_fn(table):
            return jnp.take(table, input_ids, axis=0)
        x, embed_vjp = jax.vjp(embed_fn, params["embed_tokens"])
        x_mb = microbatch(x, M)
        t_mb = microbatch(labels, M)

        stage = self.decoder.stage_fn(cos, sin)

        def loss_head_fn(hp, h, tgt):
            hidden = F.rms_norm(h, hp["norm_w"], cfg.rms_norm_eps)
            w = (jnp.swapaxes(hp["embed"], 0, 1) if tied else hp["lm_head"])
            # (token-summed loss, valid count): pipeline_1f1b normalizes by
            # the GLOBAL count so unevenly-padded microbatches reproduce the
            # unpipelined token-weighted mean exactly. The fused head keeps
            # the per-microbatch [mb, s, vocab] logits from materializing
            # (and the TP composition keeps the vocab un-gathered), same as
            # the unpipelined loss path.
            with jax.named_scope("loss_head"):
                if fused_loss_enabled(cfg):
                    mean = fused_causal_lm_loss(hidden, w, tgt)
                else:
                    logits = jnp.matmul(hidden, w.astype(hidden.dtype))
                    mean = causal_lm_loss(logits, tgt)
            cnt = jnp.sum(tgt != -100).astype(jnp.float32)
            return mean * jnp.maximum(cnt, 1.0), cnt

        loss, g_stack, g_head, dx = pipeline_1f1b(
            stage, staged, x_mb, t_mb, loss_head_fn, head_params,
            num_stages=S, remat=self.decoder.remat, return_dx=True,
            weighted_loss=True)

        (d_emb_in,) = embed_vjp(unmicrobatch(dx).astype(x.dtype))
        grads = {}
        for leaf in self.decoder._leaf_names:
            key = prefix + leaf.replace(".", "__")
            grads[key] = g_stack[leaf].reshape(params[key].shape)
        grads["embed_tokens"] = (g_head["embed"] + d_emb_in if tied
                                 else d_emb_in)
        grads["norm.weight"] = g_head["norm_w"]
        if not tied:
            grads["lm_head"] = g_head["lm_head"]
        grads = {k: grads[k] for k in params}  # preserve tree order
        return loss, grads

    def load_from_unpipelined(self, model: "LlamaForCausalLM") -> None:
        """Copy weights from a LlamaForCausalLM (stacking per-layer params) —
        the Pipe-partition converter (reference analogue:
        fleet/utils/pp_parallel_adaptor.py)."""
        cfg = self.cfg
        own = dict(self.named_parameters())
        own["embed_tokens"].value = model.model.embed_tokens
        self.norm.set_state_dict(model.model.norm.state_dict())
        if not cfg.tie_word_embeddings:
            own["lm_head"].value = model.lm_head
        src = dict(model.named_parameters())
        for leaf in self.decoder._leaf_names:
            stacked = jnp.stack(
                [src[f"model.layers.{i}.{leaf}"].value
                 for i in range(cfg.num_hidden_layers)])
            pname = "decoder.stack__" + leaf.replace(".", "__")
            own[pname].value = self.decoder.pack_leaf(stacked)
