"""MoE causal language models: DeepSeekMoE / Qwen2-MoE family.

Capability target (BASELINE.json configs): DeepSeekMoE, Qwen2-MoE.
Reference substrate: the incubate MoE layer + global_scatter/gather
(python/paddle/incubate/distributed/models/moe/moe_layer.py:263;
SURVEY.md A.2) — the model classes themselves live in PaddleNLP, so this
module defines the architecture from the published papers' shapes:

- DeepSeekMoE: fine-grained routed experts + ALWAYS-on shared experts whose
  output adds to the routed combine; first `first_k_dense_replace` layers
  stay dense.
- Qwen2-MoE: same skeleton (shared_expert + routed), top-4 routing, with a
  sigmoid shared-expert gate.

- DeepSeek-V2/V3's layout, as GLM-4.7-Flash publishes it (``attention=
  "mla"``): latent attention (``LatentAttention``: low-rank queries, ONE
  cached row a token shared by all heads) in front of sigmoid-routed
  experts with a selection bias, a routing scale and a shared expert.

TPU-first: reuses LlamaAttention (fused QKV, flash attention) and the
dense-layout MoE block (one batched einsum on the MXU; all-to-all dispatch
appears from GSPMD sharding — parallel/moe.py).

- ZAYA1's layout (``attention="cca"``): compressed convolutional attention
  (``CompressedConvAttention``: GQA in a compressed space behind two short
  causal convolutions and a value shift, which make a fixed-size state a
  sequence carries from token to token), top-1 experts behind an MLP router
  whose state is handed from layer to layer, residual merges scaled per
  channel, the embedding tied to the head.

Served through ``inference.ContinuousBatchingEngine`` as a ``ServingCore``
(``prefill_paged`` / ``decode_step_paged``), whose loops take each attention
layer's own ``alloc_pool`` / ``alloc_slot_state`` / ``prefill_paged`` /
``decode_paged``: per-head K and V pages under GQA attention, one latent
row under MLA, K and V pages plus a per-slot state under CCA (the other
two hand their empty state entry back as it came).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import rope as rope_ops
from ..parallel.moe import ROUTER_NORM_EPS, MoELayer
from .llama import (LlamaAttention, LlamaConfig, LlamaMLP,
                    _kv_scatter_tokens, _kv_write_prompt, _normal,
                    _paged_decode_attention)
from .serving_core import ServingCore


@dataclass
class MoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632          # dense-MLP size
    moe_intermediate_size: int = 1408      # per-expert FFN size
    num_hidden_layers: int = 8
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 16
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1            # DeepSeekMoE shared experts
    first_k_dense_replace: int = 1         # first k layers dense
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    shared_expert_gate: bool = False       # Qwen2-MoE sigmoid gate
    dtype: str = "float32"
    recompute: str = "none"
    sequence_parallel: bool = False
    # attention kind: "gqa" (LlamaAttention), "mla" (LatentAttention,
    # which needs the five sizes below; num_key_value_heads is not read)
    # or "cca" (CompressedConvAttention: heads of ``head_dim`` in the
    # compressed space, two causal convolutions of 2 taps)
    attention: str = "gqa"
    head_dim: Optional[int] = None         # None: hidden_size / heads
    partial_rotary_factor: float = 1.0
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # the router (parallel/moe.py MoELayer): the defaults are the GShard
    # router (softmax, renormalised whenever top-k > 1, no bias, no scale)
    scoring_func: str = "softmax"
    router_bias: bool = False              # selection bias (noaux_tc)
    norm_topk_prob: Optional[bool] = None
    routed_scaling_factor: float = 1.0
    router: str = "linear"                 # or "mlp" (router_hidden_size)
    router_hidden_size: Optional[int] = None
    router_skip_choice: bool = False       # the MLP router's no-expert output
    # h = s_r * x + s_o * branch + b_o per channel at both residual merges
    residual_scaling: bool = False
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.attention not in ("gqa", "mla", "cca"):
            raise ValueError(f"attention must be 'gqa', 'mla' or 'cca', got "
                             f"{self.attention!r}")
        if self.router == "mlp" and self.rms_norm_eps != ROUTER_NORM_EPS:
            raise ValueError(
                f"router='mlp' normalises its state at eps "
                f"{ROUTER_NORM_EPS} (parallel/moe.py); a model that "
                f"normalises at rms_norm_eps={self.rms_norm_eps} would "
                f"differ from its reference there")
        if self.attention == "mla":
            missing = [k for k in ("q_lora_rank", "kv_lora_rank",
                                   "qk_nope_head_dim", "qk_rope_head_dim",
                                   "v_head_dim") if not getattr(self, k)]
            if missing:
                raise ValueError(f"attention='mla' needs {missing}")

    def _as_llama(self) -> LlamaConfig:
        """Attention/MLP sublayers are config-compatible with Llama's
        (under latent or compressed attention only the dense MLP reads it,
        and the head count, which need not divide the hidden size there,
        is left out)."""
        own = self.attention != "gqa"      # an attention with sizes of its own
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=1 if own else self.num_attention_heads,
            num_key_value_heads=1 if own else self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            initializer_range=self.initializer_range,
            use_flash_attention=self.use_flash_attention, dtype=self.dtype)

    @staticmethod
    def deepseek_moe_16b(**kw) -> "MoEConfig":
        return MoEConfig(vocab_size=102400, hidden_size=2048,
                         intermediate_size=10944, moe_intermediate_size=1408,
                         num_hidden_layers=28, num_attention_heads=16,
                         num_key_value_heads=16, num_experts=64,
                         num_experts_per_tok=6, num_shared_experts=2,
                         first_k_dense_replace=1, **kw)

    @staticmethod
    def qwen2_moe_a14b(**kw) -> "MoEConfig":
        return MoEConfig(vocab_size=151936, hidden_size=3584,
                         intermediate_size=18944, moe_intermediate_size=2560,
                         num_hidden_layers=28, num_attention_heads=28,
                         num_key_value_heads=4, num_experts=64,
                         num_experts_per_tok=8, num_shared_experts=1,
                         first_k_dense_replace=0, shared_expert_gate=True,
                         **kw)

    @staticmethod
    def tiny(**kw) -> "MoEConfig":
        return MoEConfig(vocab_size=512, hidden_size=128,
                         intermediate_size=256, moe_intermediate_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=2, num_experts=4,
                         num_experts_per_tok=2, num_shared_experts=1,
                         first_k_dense_replace=1,
                         max_position_embeddings=256, **kw)


def _rope_at(positions, dim: int, theta: float):
    """(cos, sin) [..., dim] float32 at whole-number ``positions``, in the
    rotate-half convention. Computed where it is used: a table over
    GLM-4.7-Flash's 202,752 positions would be 104 MB of constants in
    every compiled program."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    """Rotate-half RoPE in float32; x [b, s, h, dim], cos/sin [b|1, s, dim]."""
    xf = x.astype(jnp.float32)
    return (xf * cos[:, :, None, :]
            + rope_ops.rotate_half(xf) * sin[:, :, None, :]).astype(x.dtype)


class LatentAttention(nn.Layer):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), as
    GLM-4.7-Flash configures it. Queries through a low-rank bottleneck
    (``q_a_proj``, RMSNorm, ``q_b_proj``), per head [nope | rope]. Keys and
    values from ONE latent a token: ``kv_a_proj`` gives [c | k_r]; ``c`` is
    normalised, ``k_r`` rotated and shared by all heads; ``kv_b_proj``
    expands ``c`` to per-head [k_nope | v].

    Two forms of the same mathematics. Expanded (``forward``,
    ``prefill_paged``): per-head keys [k_nope | k_r] and values through the
    flash kernel. Absorbed (``decode_paged``): ``kv_b_proj``'s key half is
    multiplied into the query and its value half applied after the
    softmax, so attention runs over the cached rows ``[c | k_r]``
    themselves: the cache is one row of kv_lora_rank + qk_rope_head_dim
    numbers a token (padded to whole lane tiles), and a decode step never
    expands it."""

    attention_kind = "mla"

    def __init__(self, cfg: MoEConfig):
        super().__init__()
        self.cfg = cfg
        d, n_h, std = (cfg.hidden_size, cfg.num_attention_heads,
                       cfg.initializer_range)
        self.rank, self.rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        self.nope, self.v_dim = cfg.qk_nope_head_dim, cfg.v_head_dim
        self.scale = 1.0 / math.sqrt(self.nope + self.rope)
        # a cached row [c_kv | k_r] is padded with zeros to whole lane
        # tiles (576 -> 640 at GLM-4.7-Flash's sizes). The chip pads the
        # minor dim of a bf16 array to 128 anyway, and left at 576 it lays
        # a [.., 128, 576] pool out PAGE-minor by default, which fed the
        # decode kernel through two 250 MB copies a layer a tick (10.3 ms
        # of a 24.3 ms tick: chip run, PR 27)
        self.row = -(-(self.rank + self.rope) // 128) * 128

        def proj(shape, sharding):
            return self.create_parameter(shape, dtype=cfg.dtype,
                                         initializer=_normal(std),
                                         sharding=sharding)
        self.q_a_proj = proj([d, cfg.q_lora_rank], ("fsdp", None))
        self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank, cfg.rms_norm_eps,
                                        dtype="float32")
        self.q_b_proj = proj([cfg.q_lora_rank,
                              n_h * (self.nope + self.rope)], ("fsdp", "tp"))
        self.kv_a_proj = proj([d, self.rank + self.rope], ("fsdp", None))
        self.kv_a_layernorm = nn.RMSNorm(self.rank, cfg.rms_norm_eps,
                                         dtype="float32")
        self.kv_b_proj = proj([self.rank, n_h * (self.nope + self.v_dim)],
                              ("fsdp", "tp"))
        self.o_proj = proj([n_h * self.v_dim, d], ("tp", "fsdp"))

    def _mm(self, x, name):
        return jnp.matmul(x, getattr(self, name).astype(x.dtype))

    def _latents(self, x, positions):
        """x [b, s, d] at ``positions`` [b|1, s] -> (q_nope [b, s, h, nope],
        q_rope [b, s, h, rope], rows [b, s, row]): the queries and the
        cache rows ``[c_kv | k_r | 0..]``, rotary applied."""
        cfg = self.cfg
        b, s, _ = x.shape
        q = self._mm(self.q_a_layernorm(self._mm(x, "q_a_proj")), "q_b_proj")
        q = q.reshape(b, s, cfg.num_attention_heads, self.nope + self.rope)
        q_nope, q_rope = q[..., :self.nope], q[..., self.nope:]
        ckr = self._mm(x, "kv_a_proj")
        c = self.kv_a_layernorm(ckr[..., :self.rank]).astype(x.dtype)
        cos, sin = _rope_at(positions, self.rope, cfg.rope_theta)
        k_r = _rotate(ckr[..., None, self.rank:], cos, sin)[:, :, 0]
        pad = jnp.zeros((b, s, self.row - self.rank - self.rope), x.dtype)
        return (q_nope, _rotate(q_rope, cos, sin),
                jnp.concatenate([c, k_r, pad], axis=-1))

    def _kv_b(self, dtype):
        """kv_b_proj per head: (w_uk [rank, h, nope], w_uv [rank, h, v])."""
        w = self.kv_b_proj.astype(dtype).reshape(
            self.rank, self.cfg.num_attention_heads, self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    def _expanded(self, q_nope, q_rope, rows):
        """Causal attention in the expanded form, rows starting at 0."""
        from ..ops.attention import flash_attention
        b, s, n_h, _ = q_nope.shape
        w_uk, w_uv = self._kv_b(rows.dtype)
        c = rows[..., :self.rank]
        k_r = rows[..., self.rank:self.rank + self.rope]
        k = jnp.concatenate(
            [jnp.einsum("bsr,rhn->bshn", c, w_uk),
             jnp.broadcast_to(k_r[:, :, None, :], (b, s, n_h, self.rope))],
            axis=-1)
        v = jnp.einsum("bsr,rhv->bshv", c, w_uv)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention(q, k, v, causal=True, scale=self.scale)
        return self._mm(out.reshape(b, s, n_h * self.v_dim), "o_proj")

    def forward(self, x, cos=None, sin=None):
        """``cos``/``sin`` are taken for the decoder layer's sake and not
        read: the rotary angles are worked out from the positions."""
        q_nope, q_rope, rows = self._latents(
            x, jnp.arange(x.shape[1])[None])
        return self._expanded(q_nope, q_rope, rows)

    # -- paged serving path --------------------------------------------------

    def alloc_pool(self, num_pages: int, page_size: int):
        """This layer's page pool: ONE array [1, num_pages, page_size,
        row] (pages on axis 1, as the engine's page copy expects), ``row``
        being rank + rope padded to whole lane tiles."""
        dt = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32
        return (jnp.zeros((1, num_pages, page_size, self.row), dt),)

    def alloc_slot_state(self, slots: int):
        """Nothing: the latent rows in the pages are the whole state."""
        return ()

    def prefill_paged(self, x, cos, sin, kv, tables, state, slot, last_idx):
        """Prompt pass: expanded attention, and the rows ``[c_kv | k_r]``
        written into the pool's pages in the pool's dtype. Rows past the
        prompt's own length (the engine pads ids to a bucket) lie beyond
        seq_len and are overwritten by decode steps before they are ever
        unmasked. ``state`` (empty) goes back as it came."""
        (pool,) = kv
        b, s, _ = x.shape
        page = pool.shape[2]
        q_nope, q_rope, rows = self._latents(x, jnp.arange(s)[None])
        out = self._expanded(q_nope, q_rope, rows)
        # row by row into the pool seen as rows, as decode_paged writes:
        # a scatter of whole [page, width] tiles makes XLA:TPU re-lay the
        # pool out and back (two 250 MB copies a layer a prefill)
        at = (tables[:, jnp.arange(s) // page] * page
              + jnp.arange(s) % page)                         # [b, s]
        pool = pool.reshape(-1, pool.shape[3]).at[at.reshape(-1)].set(
            rows.reshape(b * s, -1).astype(pool.dtype)).reshape(pool.shape)
        return out, (pool,), state

    def decode_paged(self, x, cos, sin, pos, kv, tables, state):
        """One-token step, absorbed: the new row goes to its page slot and
        every head attends over the cached rows, read once in the dtype
        they are stored in (the Pallas kernel on a TPU, its XLA twin
        elsewhere or under ``force_decode_impl("dense")``)."""
        from ..ops.pallas.latent_attention import (latent_decode_attention,
                                                   latent_decode_supported,
                                                   latent_decode_xla)
        from ..ops.pallas.paged_attention import forced_decode_impl
        from ..ops.registry import backend_kind
        (pool,) = kv
        b = x.shape[0]
        _, _, page, width = pool.shape
        q_nope, q_rope, rows = self._latents(x, pos.reshape(b, 1))
        # one [width] ROW a token of the pool seen as rows (a windowed
        # scatter makes XLA:TPU re-lay the whole pool out: llama.py)
        row = tables[jnp.arange(b), pos // page] * page + pos % page
        pool = pool.reshape(-1, width).at[row].set(
            rows[:, 0].astype(pool.dtype)).reshape(pool.shape)
        w_uk, w_uv = self._kv_b(x.dtype)
        q = jnp.concatenate(
            [jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk), q_rope[:, 0],
             jnp.zeros((b, q_rope.shape[2], width - self.rank - self.rope),
                       x.dtype)], axis=-1)             # [b, h, row]
        if (forced_decode_impl() != "dense" and backend_kind() == "tpu"
                and latent_decode_supported(q, pool, self.rank)):
            ctx = latent_decode_attention(q, pool, tables, pos, self.rank,
                                          self.scale)
        else:
            ctx = latent_decode_xla(q, pool, tables, pos, self.rank,
                                    self.scale)
        out = jnp.einsum("bhr,rhv->bhv", ctx.astype(x.dtype), w_uv)
        return self._mm(out.reshape(b, 1, -1), "o_proj"), (pool,), state


def _prev_row(a):
    """a[:, t-1] at t and zeros at t = 0, for a [b, s, ...]."""
    return jnp.pad(a, ((0, 0), (1, 0)) + ((0, 0),) * (a.ndim - 2))[:, :-1]


class CompressedConvAttention(nn.Layer):
    """Compressed convolutional attention (CCA, arXiv:2510.04476) as
    ZAYA1-8B configures it: GQA of ``num_attention_heads`` over
    ``num_key_value_heads`` heads of ``head_dim`` in a COMPRESSED space
    (Cq = heads x head_dim, Ck = kv heads x head_dim, both narrower than the
    hidden size), one up-projection behind it.

    In front of the attention (``_front``): ``q_down`` / ``k_down`` give
    q0, k0; c = [q0 | k0] goes through a depthwise convolution over time
    (2 taps: the previous token and this one) and a block-diagonal one (2
    taps, one [d, d] block a head); q and k are its output plus the mean of
    q0 and k0 per head; the values are half this token's projection and
    half the PREVIOUS token's; q and k are L2-normalised to sqrt(d) per
    head, k times a learned temperature, and rotated on the first
    ``partial_rotary_factor`` of each head's dims.

    What a token takes from its predecessor is a fixed-size state, not a
    row of a page: the pre-conv row c, the first convolution's output c1
    and the shifted value half, 2 (Cq + Ck) + Ck / 2 numbers a sequence a
    layer (``alloc_slot_state``). ``forward`` and ``prefill_paged`` see the
    whole sequence and shift it; ``decode_paged`` reads the state of its
    rows and returns the next. Behind the front, K and V live in the GQA
    pool layout [H_kv, pages, page, d] and attention is the flash kernel
    (prefill) and ``paged_decode_attention`` (decode), as
    ``LlamaAttention`` calls them."""

    attention_kind = "cca"

    def __init__(self, cfg: MoEConfig):
        super().__init__()
        self.cfg = cfg
        h, d, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        self.n_q, self.n_kv, self.d = (cfg.num_attention_heads,
                                       cfg.num_key_value_heads, d)
        self.cq, self.ck = self.n_q * d, self.n_kv * d
        self.rot = int(d * cfg.partial_rotary_factor)
        c, heads = self.cq + self.ck, self.n_q + self.n_kv

        def matrix(shape, sharding=None):
            return self.create_parameter(shape, dtype=cfg.dtype,
                                         initializer=_normal(std),
                                         sharding=sharding)

        def vector(shape, value):
            return self.create_parameter(shape, dtype="float32",
                                         initializer=I.Constant(value))
        self.q_down = matrix([h, self.cq], ("fsdp", None))
        self.k_down = matrix([h, self.ck], ("fsdp", None))
        self.v_down = matrix([h, self.ck], ("fsdp", None))    # [Wv1 | Wv2]
        self.conv0_weight = vector([2, c], 1.0)       # taps: previous, this
        self.conv0_bias = vector([c], 0.0)
        self.conv1_weight = matrix([2, heads, d, d])  # tap, head, in, out
        self.conv1_bias = vector([c], 0.0)
        self.temp = vector([self.n_kv], 1.0)
        self.o_proj = matrix([self.cq, h], (None, "fsdp"))

    def _down(self, u):
        """u [b, s, H] -> (c [b, s, Cq + Ck] = [q0 | k0], the two halves of
        the value projection [b, s, Ck / 2] each)."""
        c = jnp.concatenate(
            [jnp.matmul(u, self.q_down.astype(u.dtype)),
             jnp.matmul(u, self.k_down.astype(u.dtype))], axis=-1)
        v = jnp.matmul(u, self.v_down.astype(u.dtype))
        return c, v[..., :self.ck // 2], v[..., self.ck // 2:]

    def _conv0(self, c, c_prev):
        w = self.conv0_weight
        return (w[0] * c_prev.astype(jnp.float32)
                + w[1] * c.astype(jnp.float32)
                + self.conv0_bias).astype(c.dtype)

    def _front(self, c, c1, c1_prev, v_own, v_prev, positions):
        """(q [b, s, Hq, d], k, v [b, s, Hkv, d]) from this token's c and c1
        and its predecessor's c1 and value half, at ``positions`` [b|1, s]."""
        b, s, _ = c.shape
        n_q, n_kv, d = self.n_q, self.n_kv, self.d
        w = self.conv1_weight.astype(c.dtype)

        def heads(a):
            return a.reshape(b, s, n_q + n_kv, d)
        c2 = (jnp.einsum("bshd,hde->bshe", heads(c1_prev), w[0],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshd,hde->bshe", heads(c1), w[1],
                           preferred_element_type=jnp.float32)
              + self.conv1_bias.reshape(n_q + n_kv, d))
        c = heads(c).astype(jnp.float32)
        m_q = (c[:, :, :n_q] + jnp.repeat(c[:, :, n_q:], n_q // n_kv,
                                          axis=2)) * 0.5
        m_k = jnp.mean(m_q.reshape(b, s, n_kv, n_q // n_kv, d), axis=3)

        def unit(x):
            return x * (math.sqrt(d) * jax.lax.rsqrt(jnp.maximum(
                jnp.sum(x * x, -1, keepdims=True), 1e-12)))
        cos, sin = _rope_at(positions, self.rot, self.cfg.rope_theta)

        def rotate(x):
            return jnp.concatenate(
                [_rotate(x[..., :self.rot], cos, sin), x[..., self.rot:]],
                axis=-1)
        q = rotate(unit(c2[:, :, :n_q] + m_q))
        k = rotate(unit(c2[:, :, n_q:] + m_k) * self.temp[:, None])
        v = jnp.concatenate([v_own, v_prev], axis=-1).reshape(b, s, n_kv, d)
        return q.astype(v.dtype), k.astype(v.dtype), v

    def _sequence(self, u):
        """The whole-sequence form: (attention output [b, s, H], k, v, and
        (c, c1, the value half the next token takes), each [b, s, ..])."""
        from ..ops.attention import flash_attention
        b, s, _ = u.shape
        c, v_own, v_next = self._down(u)
        c1 = self._conv0(c, _prev_row(c))
        q, k, v = self._front(c, c1, _prev_row(c1), v_own, _prev_row(v_next),
                              jnp.arange(s)[None])
        out = flash_attention(q, k, v, causal=True,
                              scale=1.0 / math.sqrt(self.d))
        out = jnp.matmul(out.reshape(b, s, self.cq),
                         self.o_proj.astype(u.dtype))
        return out, k, v, (c, c1, v_next)

    def forward(self, x, cos=None, sin=None):
        """``cos``/``sin`` are taken for the decoder layer's sake and not
        read: the rotary angles are worked out from the positions."""
        return self._sequence(x)[0]

    # -- paged serving path --------------------------------------------------

    def alloc_pool(self, num_pages: int, page_size: int):
        """K and V page pools [H_kv, num_pages, page_size, d], the GQA
        layout, in the compressed space."""
        dt = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32
        shape = (self.n_kv, num_pages, page_size, self.d)
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def alloc_slot_state(self, slots: int):
        """What each of ``slots`` sequences carries from its last token to
        its next: (c [slots, Cq + Ck], c1 [slots, Cq + Ck], the shifted
        value half [slots, Ck / 2])."""
        dt = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32
        c = self.cq + self.ck
        return (jnp.zeros((slots, c), dt), jnp.zeros((slots, c), dt),
                jnp.zeros((slots, self.ck // 2), dt))

    def prefill_paged(self, x, cos, sin, kv, tables, state, slot, last_idx):
        """Prompt pass of ONE sequence into slot ``slot``: K and V pages
        written whole (rows past the prompt lie beyond seq_len and are
        overwritten by decode steps before they are unmasked), and the
        slot's state taken at the prompt's true last position
        ``last_idx``, not at the bucket's padded end."""
        out, k, v, carried = self._sequence(x)
        kv = _kv_write_prompt(kv, tables, k, v)
        state = tuple(st.at[slot].set(new[0, last_idx].astype(st.dtype))
                      for st, new in zip(state, carried))
        return out, kv, state

    def decode_paged(self, x, cos, sin, pos, kv, tables, state):
        """One-token step of every row: the front from this token and the
        row's state, the new K and V into their page slot, attention by
        the paged kernel (its XLA twin off the TPU or under
        ``force_decode_impl("dense")``). Returns the rows' next state."""
        b = x.shape[0]
        page = kv[0].shape[2]
        c_prev, c1_prev, v_prev = state
        c, v_own, v_next = self._down(x)
        c1 = self._conv0(c, c_prev[:, None])
        q, k, v = self._front(c, c1, c1_prev[:, None], v_own,
                              v_prev[:, None].astype(x.dtype),
                              pos.reshape(b, 1))
        kv = _kv_scatter_tokens(kv, tables[jnp.arange(b), pos // page],
                                pos % page, jnp.swapaxes(k[:, 0], 0, 1),
                                jnp.swapaxes(v[:, 0], 0, 1))
        out = _paged_decode_attention(q[:, 0], kv, tables, pos)
        out = jnp.matmul(out.reshape(b, 1, self.cq).astype(x.dtype),
                         self.o_proj.astype(x.dtype))
        state = tuple(new[:, 0].astype(st.dtype)
                      for st, new in zip(state, (c, c1, v_next)))
        return out, kv, state


class SharedExpertMLP(nn.Layer):
    """DeepSeekMoE's always-on shared expert(s): one SwiGLU MLP of width
    num_shared * moe_ffn; Qwen2-MoE adds a sigmoid gate on its output."""

    def __init__(self, cfg: MoEConfig):
        super().__init__()
        self.cfg = cfg
        width = cfg.num_shared_experts * cfg.moe_intermediate_size
        d = cfg.hidden_size
        std = cfg.initializer_range
        self.gate_up_proj = self.create_parameter(
            [d, 2 * width], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("fsdp", "tp"))
        self.down_proj = self.create_parameter(
            [width, d], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("tp", "fsdp"))
        if cfg.shared_expert_gate:
            self.gate = self.create_parameter([d, 1], dtype="float32",
                                              initializer=_normal(std))
        else:
            self.add_parameter("gate", None)

    def forward(self, x):
        gu = jnp.matmul(x, self.gate_up_proj.astype(x.dtype))
        g, u = jnp.split(gu, 2, axis=-1)
        out = jnp.matmul(F.silu(g) * u, self.down_proj.astype(x.dtype))
        if self.cfg.shared_expert_gate:
            gate = jax.nn.sigmoid(
                jnp.matmul(x.astype(jnp.float32), self.gate))
            out = out * gate.astype(out.dtype)
        return out


class ResidualMerge(nn.Layer):
    """``s_r * x + s_o * branch + b_o`` per channel, float32 (ZAYA1's
    ``scale_residual_merge``), in place of ``x + branch``."""

    def __init__(self, hidden_size: int):
        super().__init__()
        for name, value in (("res_scale", 1.0), ("out_scale", 1.0),
                            ("out_bias", 0.0)):
            setattr(self, name, self.create_parameter(
                [hidden_size], dtype="float32",
                initializer=I.Constant(value)))

    def forward(self, x, branch):
        return (self.res_scale * x + self.out_scale * branch
                + self.out_bias).astype(x.dtype)


class GQAttention(LlamaAttention):
    """``LlamaAttention`` as a layer of this core: its state is all in its
    pages, so it takes and hands back the layer's empty slot-state entry."""

    def alloc_slot_state(self, slots: int):
        return ()

    def prefill_paged(self, x, cos, sin, kv, tables, state, slot, last_idx):
        return super().prefill_paged(x, cos, sin, kv, tables) + (state,)

    def decode_paged(self, x, cos, sin, pos, kv, tables, state):
        return super().decode_paged(x, cos, sin, pos, kv, tables) + (state,)


_ATTENTION = {"mla": LatentAttention, "cca": CompressedConvAttention}


class MoEDecoderLayer(nn.Layer):
    def __init__(self, cfg: MoEConfig, dense: bool = False):
        super().__init__()
        self.cfg = cfg
        self.dense = dense
        lcfg = cfg._as_llama()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                          dtype="float32")
        self.self_attn = (_ATTENTION[cfg.attention](cfg)
                          if cfg.attention in _ATTENTION
                          else GQAttention(lcfg))
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps,
                                                   dtype="float32")
        if dense:
            self.mlp = LlamaMLP(lcfg)
            self.add_sublayer("moe", None)
            self.add_sublayer("shared_experts", None)
        else:
            self.add_sublayer("mlp", None)
            self.moe = MoELayer(cfg.hidden_size, cfg.moe_intermediate_size,
                                cfg.num_experts, top_k=cfg.num_experts_per_tok,
                                capacity_factor=cfg.capacity_factor,
                                dtype=cfg.dtype, scoring=cfg.scoring_func,
                                select_bias=cfg.router_bias,
                                norm_topk_prob=cfg.norm_topk_prob,
                                routed_scaling_factor=cfg.routed_scaling_factor,
                                **({} if cfg.router == "linear" else dict(
                                    router=cfg.router,
                                    router_hidden_size=cfg.router_hidden_size,
                                    skip_choice=cfg.router_skip_choice)))
            if cfg.num_shared_experts > 0:
                self.shared_experts = SharedExpertMLP(cfg)
            else:
                self.add_sublayer("shared_experts", None)
        if cfg.residual_scaling:
            self.attn_merge = ResidualMerge(cfg.hidden_size)
            self.mlp_merge = ResidualMerge(cfg.hidden_size)
        else:
            self.add_sublayer("attn_merge", None)
            self.add_sublayer("mlp_merge", None)

    def merge(self, which: str, x, branch):
        """A residual merge (``which``: "attn" or "mlp"): the sum, or the
        model's per-channel scaled one."""
        scaled = getattr(self, which + "_merge")
        return x + branch if scaled is None else scaled(x, branch)

    def _router_state(self, z, prev):
        """The MLP router's state of this layer (``prev``: the layer
        before's); None under a one-matrix router or in a dense layer."""
        if self.dense or self.moe.router == "linear":
            return None
        return self.moe.router_state(z, prev)

    def forward(self, x, cos, sin, router_state=None):
        """(x, aux, this layer's router state for the next layer)."""
        h = self.merge("attn", x,
                       self.self_attn(self.input_layernorm(x), cos, sin))
        z = self.post_attention_layernorm(h)
        if self.dense:
            return (self.merge("mlp", h, self.mlp(z)),
                    jnp.zeros((), jnp.float32), None)
        r = self._router_state(z, router_state)
        routed, aux = self.moe(z, r)
        if self.shared_experts is not None:
            routed = routed + self.shared_experts(z)
        return self.merge("mlp", h, routed), aux, r

    def mlp_inference(self, z, router_state=None):
        """The block after attention on the serving path: (y, load, r),
        load being the rows each routed expert was sent ([e] int32; None
        for a dense layer) and r this layer's router state (None without
        one). No auxiliary loss, no token dropped."""
        if self.dense:
            return self.mlp(z), None, None
        r = self._router_state(z, router_state)
        routed, load = self.moe.forward_inference(z, r)
        if self.shared_experts is not None:
            routed = routed + self.shared_experts(z)
        return routed, load, r


class MoEForCausalLM(nn.Layer, ServingCore):
    """DeepSeekMoE/Qwen2-MoE-style causal LM. forward returns
    (loss, logits) with labels (loss = CE + aux_weight * load-balance aux),
    logits otherwise."""

    def __init__(self, cfg: MoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            initializer=_normal(cfg.initializer_range), sharding=("tp", "fsdp"))
        self.layers = nn.LayerList([
            MoEDecoderLayer(cfg, dense=(i < cfg.first_k_dense_replace))
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                               dtype="float32")
        if cfg.tie_word_embeddings:
            self.add_parameter("lm_head", None)
        else:
            self.lm_head = self.create_parameter(
                [cfg.hidden_size, cfg.vocab_size], dtype=cfg.dtype,
                initializer=_normal(cfg.initializer_range),
                sharding=("fsdp", "tp"))
        self.attention_kind = cfg.attention
        # what a decode tick counts on the device beside its tokens (the
        # engine adds them up into ``stats()``): rows x top-k routed (the
        # rows that chose no expert included), the most rows one expert
        # got, summed over the routed layers, and, where the router has a
        # skip choice, the rows that took it
        self.tick_counters = ((("moe_assignments", "moe_peak_load")
                               + (("moe_skipped",) if cfg.router_skip_choice
                                  else ()))
                              if cfg.first_k_dense_replace
                              < cfg.num_hidden_layers else ())
        if cfg.attention != "gqa":
            # LatentAttention and CompressedConvAttention work their
            # rotary angles out from positions
            self.rope_cos = self.rope_sin = None
        else:
            cos, sin = rope_ops.rope_freqs(cfg.head_dim,
                                           cfg.max_position_embeddings,
                                           cfg.rope_theta)
            self.register_buffer("rope_cos", cos, persistable=False)
            self.register_buffer("rope_sin", sin, persistable=False)

    def _head(self):
        """The output head [H, V]: ``lm_head``, or the embedding
        transposed where the two are tied."""
        return (jnp.swapaxes(self.embed_tokens, 0, 1)
                if self.cfg.tie_word_embeddings else self.lm_head)

    def logits(self, hidden):
        return jnp.matmul(hidden, self._head().astype(hidden.dtype))

    # -- paged-KV serving path (inference.ContinuousBatchingEngine) ---------

    def expert_path(self, rows: int):
        """``MoELayer.inference_path`` of the routed layers (all alike);
        None where the inference path does not run the experts (none
        routed, or capacity routing)."""
        routed = [layer.moe for layer in self.layers if layer.moe is not None]
        if not routed or self.cfg.capacity_factor is not None:
            return None
        return routed[0].inference_path(rows)

    def alloc_slot_state(self, slots: int):
        """One entry a layer, every leaf leading with the slot: CCA's
        conv/shift state; empty under an attention whose state is all in
        its pages."""
        return [layer.self_attn.alloc_slot_state(slots)
                for layer in self.layers]

    def prefill_paged(self, input_ids, pools, tables, slot_state, slot,
                      last_idx):
        """``ServingCore.prefill_paged``."""
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        new_pools, new_state, r = [], [], None
        for layer, kv, st in zip(self.layers, pools, slot_state):
            a, kv, st = layer.self_attn.prefill_paged(
                layer.input_layernorm(x), self.rope_cos, self.rope_sin, kv,
                tables, st, slot, last_idx)
            h = layer.merge("attn", x, a)
            y, _, r = layer.mlp_inference(layer.post_attention_layernorm(h), r)
            x = layer.merge("mlp", h, y)
            new_pools.append(kv)
            new_state.append(st)
        return self.norm(x), new_pools, new_state

    def decode_step_paged(self, token_ids, pos, pools, tables, slot_state):
        """``ServingCore.decode_step_paged``."""
        x = jnp.take(self.embed_tokens, token_ids[:, None], axis=0)
        new_pools, new_state, r = [], [], None
        routed, peak, skipped = 0, 0, 0
        for layer, kv, st in zip(self.layers, pools, slot_state):
            a, kv, st = layer.self_attn.decode_paged(
                layer.input_layernorm(x), self.rope_cos, self.rope_sin, pos,
                kv, tables, st)
            h = layer.merge("attn", x, a)
            y, load, r = layer.mlp_inference(
                layer.post_attention_layernorm(h), r)
            x = layer.merge("mlp", h, y)
            new_pools.append(kv)
            new_state.append(st)
            if load is not None:
                routed, peak = routed + jnp.sum(load), peak + jnp.max(load)
                if self.cfg.router_skip_choice:
                    # the rows x top-k that no expert was sent
                    skipped = skipped + (load.dtype.type(
                        x.shape[0] * self.cfg.num_experts_per_tok)
                        - jnp.sum(load))
        hidden, counts = self.norm(x), None
        if self.tick_counters:
            counts = jnp.stack(
                [routed + skipped, peak, skipped]
                if self.cfg.router_skip_choice else [routed, peak]
            ).astype(jnp.int32)
        return hidden, new_pools, new_state, counts

    def forward(self, input_ids, labels=None):
        cfg = self.cfg
        s = input_ids.shape[1]
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        cos, sin = ((None, None) if self.rope_cos is None
                    else (self.rope_cos[:s], self.rope_sin[:s]))
        aux_total = jnp.zeros((), jnp.float32)
        r = None                 # the MLP router's state, layer to layer
        if cfg.recompute == "full":
            def run(layer, h, r_):
                return layer(h, cos, sin, r_)
            ckpt = jax.checkpoint(run, static_argnums=(0,))
            for layer in self.layers:
                x, aux, r = ckpt(layer, x, r)
                aux_total = aux_total + aux
        else:
            for layer in self.layers:
                x, aux, r = layer(x, cos, sin, r)
                aux_total = aux_total + aux
        hidden = self.norm(x)
        if labels is None:
            return self.logits(hidden)
        from .llama import (causal_lm_loss, fused_causal_lm_loss,
                            fused_loss_enabled)
        logits = None
        with jax.named_scope("loss_head"):
            if fused_loss_enabled(cfg):
                # fused blockwise head: no [b, s, vocab] logits (TP gets
                # the per-shard fused path, same as Llama)
                ce = fused_causal_lm_loss(hidden, self._head(), labels)
            else:
                logits = self.logits(hidden)
                # vocab-parallel CE when tp is active (no gathered logits)
                ce = causal_lm_loss(logits, labels)
        loss = ce + cfg.aux_loss_weight * aux_total
        if logits is None:  # compat tuple; dead (DCE'd) when unused
            logits = self.logits(hidden)
        return loss, logits

    def num_params(self) -> int:
        return sum(int(math.prod(p.shape)) for _, p in self.named_parameters())

    def num_activated_params(self) -> int:
        """Per-token active params (MoE MFU accounting: only top_k experts +
        shared experts + attention/dense count toward achieved FLOPs)."""
        cfg = self.cfg
        total = self.num_params()
        per_expert = 3 * cfg.hidden_size * cfg.moe_intermediate_size
        n_moe_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
        inactive = (cfg.num_experts - cfg.num_experts_per_tok) * per_expert
        return total - n_moe_layers * inactive

    def flops_per_token(self, seq_len: int) -> float:
        cfg = self.cfg
        n = self.num_activated_params()
        if not cfg.tie_word_embeddings:      # tied: the table is the head
            n -= cfg.vocab_size * cfg.hidden_size  # embedding gather
        attn = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq_len
        return 6 * n + attn
