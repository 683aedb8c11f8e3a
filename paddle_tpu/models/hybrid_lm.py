"""Hybrid state-space / attention / expert causal LM (``nemotron_h``'s layout,
as NVIDIA-Nemotron-3-Nano-30B-A3B publishes it, ``jamba``'s, as
AI21-Jamba2-3B does, ``brumby``'s, as Brumby-14B-Base does, and
``qwen3_next``'s, as Qwen3-Next-80B-A3B-Instruct does).

A decoder of blocks ``x + Mixer(RMSNorm(x))`` whose mixer is ONE of nine
kinds, by a pattern string with one character a block:

- ``M``: a Mamba-2 layer (``Mamba2Mixer``, arXiv:2405.21060): one input
  projection to a gate ``z``, a convolved stream ``xBC`` and a step ``dt`` a
  head; a short causal depthwise convolution and SiLU over ``xBC``; the
  recurrence ``S_t = exp(dt A) S_{t-1} + dt x_t (x) B_t``, ``y_t = S_t C_t +
  D x_t`` over a float32 state [heads, head size, state size]; ``y silu(z)``
  through a grouped RMSNorm; one output projection.
- ``*``: causal GQA attention with NO position embedding (``NoPEAttention``:
  the Mamba layers carry position), K and V in the paged pools.
- ``E``: sigmoid-routed experts with a selection bias beside a shared expert
  (``parallel.moe.MoELayer``), non-gated: ``relu(x W_up)^2 W_down``. The layer
  may hold a SHARE of the experts (``experts_held``: one chip of an
  expert-parallel deployment; the router keeps its full width).
- ``m``: a Mamba-1 layer (``Mamba1Mixer``, arXiv:2312.00752, with Jamba's
  inner norms): an input projection to ``x`` and a gate ``z``; the same
  convolution and SiLU over ``x``; ``[dt | B | C] = x W_x``, each through an
  RMSNorm of its own, the step ``Delta = softplus(dt W_dt + b_dt)`` a CHANNEL
  through a low-rank bottleneck; the recurrence ``h_t = exp(Delta A) h_{t-1}
  + Delta x_t (x) B_t``, ``y_t = h_t C_t + D x_t`` over a float32 state
  [state size, channels] in which EVERY element has a decay of its own (``A``
  [state size, channels]); ``y silu(z)``, no norm; one output projection.
- ``-``: a dense gated MLP (``GatedMLP``): ``(silu(x W_gate) * (x W_up))
  W_down``. A Jamba layer is TWO blocks, its mixer's and this one (``m-`` or
  ``*-``), each behind its own RMSNorm, as the published layer is.
- ``p``: a power-retention layer of degree 2 (``PowerRetentionMixer``,
  arXiv:2507.04239; Brumby's mixer, a Qwen3 attention layer with its softmax
  replaced): q, k and v as GQA attention's, a per-head RMSNorm and THEN a
  rotary embedding on q and k (the one mixer here that reads positions), a
  gate ``g = sigmoid(u W_g)`` a KV head a token; position t weighs position
  j <= t by ``prod_{j < i <= t} g_i (q_t . k_j)^2 / d`` and the output is
  the weighted mean of v (the weights are not negative). As a recurrence:
  a float32 state ``S = g S + phi(k) v^T`` [D, d] and ``z = g z + phi(k)``
  a KV head, ``phi`` the D = d (d + 1) / 2 products of pairs, ``y = phi(q)^T
  S / (phi(q) . z + eps)``. A Brumby layer is ``p-``.
- ``d``, ``a``, ``e``: a Gated DeltaNet layer (a float32 state that is READ
  BACK before it is written: the gated delta rule), gated attention with a
  partial rotary embedding, and softmax-routed SwiGLU experts beside a
  gated shared expert, each behind a ZERO-CENTRED norm (``models/
  hybrid_gated.py`` has the three and their equations). A Qwen3-Next layer
  is ``de`` or ``ae``; a published period of four is ``dededeae``.

Served through ``inference.ContinuousBatchingEngine`` as a ``ServingCore``
(``alloc_paged_caches`` / ``alloc_slot_state`` / ``prefill_paged`` /
``decode_step_paged``): page pools for the attention layers ONLY, a per-slot
state for the Mamba layers only: the convolution's last ``conv_kernel - 1``
inputs (activation dtype) and the recurrence's state in float32, which a
prefill writes at the prompt's true last position and every decode tick
rewrites in place (``ops.pallas.ssm.ssm_state_update``, or for a Mamba-1
layer ``ops.pallas.selective_ssm``'s ``conv_window_step`` and
``selective_state_update``, window and state each in one pass, on a TPU; for
a power-retention layer ``ops.pallas.power_retention``'s
``power_state_update``; for a Gated DeltaNet layer ``ops.pallas.
gated_delta``'s ``gated_delta_state_update``). A
prompt runs the Mamba-2 recurrence in chunks (``ssd_chunked``: matrix
products inside a chunk, the state carried from chunk to chunk), power
retention and the gated delta rule in chunks too
(``power_retention_chunked``, ``gated_delta_chunked``) and the
Mamba-1 recurrence, which has no such form, with time inside a kernel
(``selective_scan``). A pattern without ``*`` or ``a`` keeps no page at all. Not trained: ``forward`` is the whole-sequence form for
tests and evaluation; neither scan has a hand-written backward and no
training cell runs one.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I
from ..parallel.moe import MoELayer, expert_ffn
from .llama import (_kv_scatter_tokens, _kv_write_prompt, _normal,
                    _paged_decode_attention)
from .serving_core import ServingCore


@dataclass
class HybridConfig:
    vocab_size: int = 32000
    hidden_size: int = 2688
    pattern: str = "MEM*E"                 # one character a block
    num_hidden_layers: Optional[int] = None  # the first so many of them
    # Mamba-2 (the inner width is heads x head size)
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128                  # of a prompt's chunked scan
    # Mamba-1 (the inner width is mamba_expand x hidden_size)
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    rope_theta: float = 10000.0            # power retention's and gated
    partial_rotary_factor: float = 1.0     # attention's q and k alone
    # Gated DeltaNet (Hk key heads of K serve Hv value heads of V)
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    delta_chunk_size: int = 64             # of a prompt's chunked delta rule
    # experts
    num_experts: int = 128                 # the router's width
    first_expert_held: int = 0             # the share of them held here:
    num_experts_held: Optional[int] = None  # None: all
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    intermediate_size: int = 8192          # the dense MLP's width
    tie_word_embeddings: bool = False
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        bad = set(self.pattern) - set("ME*m-pdae")
        if bad or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: one of 'M' (Mamba-2)"
                             f", 'm' (Mamba-1), '*' (attention), 'E' "
                             f"(experts), '-' (dense MLP), 'p' (power "
                             f"retention), 'd' (Gated DeltaNet), 'a' (gated "
                             f"attention), 'e' (softmax-routed experts, a "
                             f"gated shared one) a block")
        if (self.num_hidden_layers is not None
                and not 0 < self.num_hidden_layers <= len(self.pattern)):
            raise ValueError(f"num_hidden_layers={self.num_hidden_layers}: "
                             f"the pattern has {len(self.pattern)} blocks")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("n_groups must divide mamba_num_heads")

    @property
    def kinds(self) -> str:
        """The blocks built: the pattern's first ``num_hidden_layers``
        characters (a pipeline stage holds a prefix of the published
        pattern), all of them by default."""
        return self.pattern[:self.num_hidden_layers]

    @property
    def experts_held(self) -> Optional[Tuple[int, int]]:
        """``MoELayer``'s (first, count), None where all are held."""
        if self.num_experts_held is None:
            return None
        return self.first_expert_held, self.num_experts_held

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def mamba1_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @staticmethod
    def tiny(**kw) -> "HybridConfig":
        return HybridConfig(**{**dict(
            vocab_size=256, hidden_size=64, pattern="MEM*E",
            mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
            ssm_state_size=128, chunk_size=16, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=64, mamba_dt_rank=8,
            intermediate_size=96), **kw})


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def ssd_chunked(x, dt, a, b_mat, c_mat, chunk: int):
    """The Mamba-2 recurrence over whole sequences starting from a zero
    state, in chunks (the SSD form): inside a chunk everything is matrix
    products, the state is carried from chunk to chunk.

    x [b, L, H, P]; dt [b, L, H] float32 (0 where a position must leave the
    state as it is); a [H] float32; b_mat, c_mat [b, L, G, N]; L a multiple
    of ``chunk``. Returns (y [b, L, H, P] float32 = S_t C_t, S_L [b, H, P,
    N] float32). With la = dt a and cs its running sum inside a chunk, a
    position l takes exp(cs[l] - cs[s]) (C_l . B_s) dt_s x_s from every s <=
    l of its chunk and exp(cs[l]) C_l S from the state S entering the chunk.
    """
    b, L, H, P = x.shape
    G, N = b_mat.shape[2:]
    R, nc = H // G, L // chunk
    f32 = jnp.float32
    xdt = (x.astype(f32) * dt[..., None]).reshape(b, nc, chunk, G, R, P)
    cs = jnp.cumsum((dt * a).reshape(b, nc, chunk, G, R), axis=2)
    bc = b_mat.astype(f32).reshape(b, nc, chunk, G, N)
    cc = c_mat.astype(f32).reshape(b, nc, chunk, G, N)
    # inside a chunk: [b, c, g, r, l, s], 0 above the diagonal (masked
    # BEFORE the exp: cs[l] - cs[s] is positive there)
    seg = jnp.moveaxis(cs, 2, -1)                        # [b, c, g, r, l]
    seg = seg[..., :, None] - seg[..., None, :]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", cb[:, :, :, None] * decay, xdt)
    # what a chunk adds to the state at its end, and the state entering it
    to_end = jnp.exp(cs[:, :, -1:] - cs)                 # [b, c, s, g, r]
    added = jnp.einsum("bcsgr,bcsgrp,bcsgn->bcgrpn", to_end, xdt, bc)
    whole = jnp.exp(cs[:, :, -1])                        # [b, c, g, r]

    def carry(s, step):
        add, keep = step
        return keep[..., None, None] * s + add, s

    last, entering = jax.lax.scan(
        carry, jnp.zeros((b, G, R, P, N), f32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    y = y + (jnp.einsum("bclgn,cbgrpn->bclgrp", cc, entering)
             * jnp.exp(cs)[..., None])
    return y.reshape(b, L, H, P), last.reshape(b, H, P, N)


def _conv_silu(taps, weight, bias):
    """A depthwise causal convolution's output from its inputs a position,
    ``taps`` a list of [.., channels] (oldest first; ``weight`` [taps,
    channels], the last row the token's own), then SiLU."""
    out = bias + sum(t.astype(jnp.float32) * weight[i]
                     for i, t in enumerate(taps))
    return jax.nn.silu(out).astype(taps[0].dtype)


def _on_tpu() -> bool:
    from ..ops.registry import backend_kind
    return backend_kind() == "tpu"


class Mamba2Mixer(nn.Layer):
    """One Mamba-2 layer (the module docstring has its equations)."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        d, std = cfg.hidden_size, cfg.initializer_range
        self.h, self.p = cfg.mamba_num_heads, cfg.mamba_head_dim
        self.g, self.n = cfg.n_groups, cfg.ssm_state_size
        inner, conv = cfg.d_inner, cfg.conv_dim

        def vector(shape, value):
            return self.create_parameter(shape, dtype="float32",
                                         initializer=I.Constant(value))
        # [z | xBC | dt]
        self.in_proj = self.create_parameter(
            [d, inner + conv + self.h], dtype=cfg.dtype,
            initializer=_normal(std), sharding=("fsdp", None))
        self.conv_weight = vector([cfg.conv_kernel, conv], 1.0)  # last: now
        self.conv_bias = vector([conv], 0.0)
        self.dt_bias = vector([self.h], 0.0)
        self.A_log = vector([self.h], 0.0)
        self.D = vector([self.h], 1.0)
        self.norm_weight = vector([inner], 1.0)
        self.out_proj = self.create_parameter(
            [inner, d], dtype=cfg.dtype, initializer=_normal(std),
            sharding=(None, "fsdp"))

    def _project(self, u):
        """u [.., d] -> (z [.., inner], xBC [.., conv_dim], dt [.., H])."""
        inner, conv = self.cfg.d_inner, self.cfg.conv_dim
        zxd = jnp.matmul(u, self.in_proj.astype(u.dtype))
        return (zxd[..., :inner], zxd[..., inner:inner + conv],
                zxd[..., inner + conv:])

    def _conv(self, taps):
        return _conv_silu(taps, self.conv_weight, self.conv_bias)

    def _split(self, xbc):
        """xBC [.., conv_dim] -> (x [.., H, P], B [.., G, N], C [.., G, N])."""
        lead, inner, gn = xbc.shape[:-1], self.cfg.d_inner, self.g * self.n
        return (xbc[..., :inner].reshape(*lead, self.h, self.p),
                xbc[..., inner:inner + gn].reshape(*lead, self.g, self.n),
                xbc[..., inner + gn:].reshape(*lead, self.g, self.n))

    def _step(self, dt):
        """(Delta [.., H] = softplus(dt + dt_bias), A [H]), float32."""
        return (jax.nn.softplus(dt.astype(jnp.float32) + self.dt_bias),
                -jnp.exp(self.A_log))

    def _out(self, y, x, z):
        """y [.., H, P] float32 (the state's reading) -> the layer's output:
        + D x, times silu(z), RMSNorm over each group's channels, out_proj."""
        cfg = self.cfg
        lead = z.shape[:-1]
        y = y + self.D[:, None] * x.astype(jnp.float32)
        y = y.reshape(*lead, cfg.d_inner) * jax.nn.silu(
            z.astype(jnp.float32))
        grouped = y.reshape(*lead, self.g, cfg.d_inner // self.g)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, -1, keepdims=True) + cfg.rms_norm_eps)
        y = (grouped.reshape(*lead, cfg.d_inner)
             * self.norm_weight).astype(z.dtype)
        return jnp.matmul(y, self.out_proj.astype(z.dtype))

    def _sequence(self, u, last_idx=None):
        """Whole sequences u [b, s, d] from a zero state: (output [b, s, d],
        the convolution's inputs after position ``last_idx`` [b, k - 1,
        conv_dim], the state after it [b, H, P, N]). Positions past
        ``last_idx`` (a bucket's padding; None: the last) take a step of 0,
        so they leave the state as it is."""
        cfg = self.cfg
        b, s, _ = u.shape
        k, chunk = cfg.conv_kernel, cfg.chunk_size
        last_idx = s - 1 if last_idx is None else last_idx
        z, xbc, dt = self._project(u)
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        x, b_mat, c_mat = self._split(
            self._conv([padded[:, i:i + s] for i in range(k)]))
        delta, a = self._step(dt)
        delta = jnp.where((jnp.arange(s) <= last_idx)[None, :, None],
                          delta, 0.0)
        pad = -s % chunk        # whole chunks; a step of 0 moves nothing

        def whole(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        y, state = ssd_chunked(whole(x), whole(delta), a, whole(b_mat),
                               whole(c_mat), chunk)
        tail = jax.lax.dynamic_slice_in_dim(padded, last_idx + 1, k - 1,
                                            axis=1)
        return self._out(y[:, :s], x, z), tail, state

    def forward(self, u):
        return self._sequence(u)[0]

    # -- serving path --------------------------------------------------------

    def alloc_slot_state(self, slots: int):
        """What each of ``slots`` sequences carries from token to token:
        (the convolution's last k - 1 inputs [slots, k - 1, conv_dim] in the
        activation dtype, the recurrence's state in float32, whatever the
        activation dtype, in the layout its update sweeps:
        ``ops.pallas.ssm.pack_state`` of [slots, H, P, N])."""
        from ..ops.pallas.ssm import pack_state
        cfg = self.cfg
        return (jnp.zeros((slots, cfg.conv_kernel - 1, cfg.conv_dim),
                          _dtype(cfg)),
                pack_state(jnp.zeros((slots, self.h, self.p, self.n),
                                     jnp.float32), self.g))

    def prefill(self, u, state, slot, last_idx):
        """The prompt of ONE sequence into slot ``slot``: the state written
        is the state after the prompt's true last position ``last_idx``,
        whatever the bucket the prompt was padded to."""
        from ..ops.pallas.ssm import pack_state
        out, tail, ssm = self._sequence(u, last_idx)
        conv_state, ssm_state = state
        return out, (conv_state.at[slot].set(tail[0].astype(conv_state.dtype)),
                     ssm_state.at[slot].set(pack_state(ssm[0], self.g)))

    def state_path(self, rows, slots: int) -> str:
        """The form the recurrence takes ("kernel" or "xla") in a prompt of
        ``rows`` positions (the chunked scan: matrix products, XLA's) or,
        ``rows`` None, in a tick of ``slots`` slots."""
        from ..ops.pallas.ssm import ssm_state_update_supported
        if rows is not None or not _on_tpu():
            return "xla"
        state = jax.eval_shape(lambda: self.alloc_slot_state(slots))[1]
        b_mat = jax.ShapeDtypeStruct((slots, self.g, self.n), jnp.float32)
        return "kernel" if ssm_state_update_supported(state, b_mat) else "xla"

    def decode(self, u, state):
        """One token of every row u [b, 1, d] through the rows' state (the
        Pallas kernel on a TPU, in place; its ``jnp`` twin elsewhere)."""
        from ..ops.pallas.ssm import ssm_state_update, ssm_state_update_xla
        conv_state, ssm_state = state
        z, xbc, dt = self._project(u[:, 0])
        window = jnp.concatenate(
            [conv_state, xbc[:, None].astype(conv_state.dtype)], axis=1)
        x, b_mat, c_mat = self._split(
            self._conv([window[:, i] for i in range(window.shape[1])]))
        delta, a = self._step(dt)
        update = (ssm_state_update
                  if self.state_path(None, u.shape[0]) == "kernel"
                  else ssm_state_update_xla)
        y, ssm_state = update(ssm_state, x, delta, a, b_mat, c_mat)
        return self._out(y, x, z)[:, None], (window[:, 1:], ssm_state)


class Mamba1Mixer(nn.Layer):
    """One Mamba-1 layer with Jamba's inner norms (the module docstring has
    its equations). ``A_log`` is kept [state size, channels], the layout of
    the state it decays (``ops.pallas.selective_ssm``), where the published
    tensor is its transpose."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        d, std = cfg.hidden_size, cfg.initializer_range
        inner, n, rank = cfg.mamba1_inner, cfg.mamba_d_state, cfg.mamba_dt_rank

        def vector(shape, value):
            return self.create_parameter(shape, dtype="float32",
                                         initializer=I.Constant(value))

        def matrix(shape, sharding):
            return self.create_parameter(shape, dtype=cfg.dtype,
                                         initializer=_normal(std),
                                         sharding=sharding)
        self.in_proj = matrix([d, 2 * inner], ("fsdp", None))     # [x | z]
        self.conv_weight = vector([cfg.conv_kernel, inner], 1.0)  # last: now
        self.conv_bias = vector([inner], 0.0)
        self.x_proj = matrix([inner, rank + 2 * n], (None, None))  # [dt|B|C]
        self.dt_norm = vector([rank], 1.0)
        self.b_norm = vector([n], 1.0)
        self.c_norm = vector([n], 1.0)
        self.dt_proj = matrix([rank, inner], (None, None))
        self.dt_bias = vector([inner], 0.0)
        self.A_log = vector([n, inner], 0.0)
        self.D = vector([inner], 1.0)
        self.out_proj = matrix([inner, d], (None, "fsdp"))

    def _project(self, u):
        """u [.., d] -> (x [.., inner], the gate z [.., inner])."""
        xz = jnp.matmul(u, self.in_proj.astype(u.dtype))
        return jnp.split(xz, 2, axis=-1)

    def _selection(self, x):
        """x [.., inner] (convolved) -> (Delta [.., inner], B [.., N], C
        [.., N]), float32: what the token selects of the recurrence."""
        cfg, f32 = self.cfg, jnp.float32
        n, rank = cfg.mamba_d_state, cfg.mamba_dt_rank

        def norm(t, w):
            return t * jax.lax.rsqrt(
                jnp.mean(t * t, -1, keepdims=True) + cfg.rms_norm_eps) * w
        dbc = jnp.matmul(x, self.x_proj.astype(x.dtype),
                         preferred_element_type=f32)
        dt = norm(dbc[..., :rank], self.dt_norm).astype(x.dtype)
        delta = jax.nn.softplus(jnp.matmul(
            dt, self.dt_proj.astype(x.dtype), preferred_element_type=f32)
            + self.dt_bias)
        return (delta, norm(dbc[..., rank:rank + n], self.b_norm),
                norm(dbc[..., rank + n:], self.c_norm))

    def _out(self, y, x, z):
        """y [.., inner] float32 (the state's reading) -> the layer's
        output: + D x, times silu(z), out_proj."""
        y = (y + self.D * x.astype(jnp.float32)) * jax.nn.silu(
            z.astype(jnp.float32))
        return jnp.matmul(y.astype(z.dtype), self.out_proj.astype(z.dtype))

    def _tick_selection(self, x):
        """``_selection`` for a tick's kernel: (the step [.., inner] BEFORE
        its bias and softplus, which the kernel applies, B [.., N], C
        [.., N]), float32."""
        cfg, f32 = self.cfg, jnp.float32
        n, rank = cfg.mamba_d_state, cfg.mamba_dt_rank

        def norm(t, w):
            return t * jax.lax.rsqrt(
                jnp.mean(t * t, -1, keepdims=True) + cfg.rms_norm_eps) * w
        dbc = jnp.matmul(x, self.x_proj.astype(x.dtype),
                         preferred_element_type=f32)
        dt = norm(dbc[..., :rank], self.dt_norm).astype(x.dtype)
        return (jnp.matmul(dt, self.dt_proj.astype(x.dtype),
                           preferred_element_type=f32),
                norm(dbc[..., rank:rank + n], self.b_norm),
                norm(dbc[..., rank + n:], self.c_norm))

    def state_path(self, rows, slots: int) -> str:
        """The form the recurrence takes in a prompt of ``rows`` positions
        ("kernel" or "xla") or, ``rows`` None, in a tick of ``slots``:
        "fused" where both of the tick's kernels run (the window's step and
        the state update), "kernel" where the update's alone does, "xla" off
        the TPU. Decided from shapes alone."""
        from ..ops.pallas.selective_ssm import (
            conv_window_step_supported, selective_scan_supported,
            selective_state_update_supported)
        cfg = self.cfg
        if not _on_tpu():
            return "xla"
        if rows is not None:
            ok = selective_scan_supported(jax.ShapeDtypeStruct(
                (1, rows, cfg.mamba1_inner), _dtype(cfg)), cfg.mamba_d_state)
            return "kernel" if ok else "xla"
        window, state = jax.eval_shape(lambda: self.alloc_slot_state(slots))
        if not selective_state_update_supported(state):
            return "xla"
        return "fused" if conv_window_step_supported(window) else "kernel"

    def _sequence(self, u, last_idx=None):
        """Whole sequences u [b, s, d] from a zero state: (output [b, s, d],
        the convolution's inputs after position ``last_idx`` [b, k - 1,
        inner], the state after it [b, N, inner]). Positions past
        ``last_idx`` (a bucket's padding; None: the last) take a step of 0,
        so they leave the state as it is."""
        from ..ops.pallas.selective_ssm import (selective_scan,
                                                selective_scan_xla)
        b, s, _ = u.shape
        k = self.cfg.conv_kernel
        last_idx = s - 1 if last_idx is None else last_idx
        x, z = self._project(u)
        padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        x = _conv_silu([padded[:, i:i + s] for i in range(k)],
                       self.conv_weight, self.conv_bias)
        delta, b_mat, c_mat = self._selection(x)
        delta = jnp.where((jnp.arange(s) <= last_idx)[None, :, None],
                          delta, 0.0)
        scan = (selective_scan if self.state_path(s, b) == "kernel"
                else selective_scan_xla)
        y, state = scan(x, delta, -jnp.exp(self.A_log), b_mat, c_mat)
        tail = jax.lax.dynamic_slice_in_dim(padded, last_idx + 1, k - 1,
                                            axis=1)
        return self._out(y, x, z), tail, state

    def forward(self, u):
        return self._sequence(u)[0]

    # -- serving path --------------------------------------------------------

    def alloc_slot_state(self, slots: int):
        """(the convolution's last k - 1 inputs [slots, k - 1, inner] in the
        activation dtype, the recurrence's state [slots, N, inner] in
        float32 whatever the activation dtype)."""
        cfg = self.cfg
        return (jnp.zeros((slots, cfg.conv_kernel - 1, cfg.mamba1_inner),
                          _dtype(cfg)),
                jnp.zeros((slots, cfg.mamba_d_state, cfg.mamba1_inner),
                          jnp.float32))

    def prefill(self, u, state, slot, last_idx):
        """The prompt of ONE sequence into slot ``slot``: the state written
        is the state after the prompt's true last position ``last_idx``,
        whatever the bucket the prompt was padded to."""
        out, tail, ssm = self._sequence(u, last_idx)
        conv_state, ssm_state = state
        return out, (conv_state.at[slot].set(tail[0].astype(conv_state.dtype)),
                     ssm_state.at[slot].set(ssm[0]))

    def decode(self, u, state):
        """One token of every row u [b, 1, d] through the rows' state: on a
        TPU two Pallas kernels, each one pass over its rows in place (the
        window's step; the state update from the raw step to the gated
        row), with ``x_proj``, ``dt_proj`` and the inner norms between them
        XLA's; their ``jnp`` twins elsewhere (``state_path``)."""
        from ..ops.pallas import selective_ssm as k
        conv_state, ssm_state = state
        path = self.state_path(None, u.shape[0])
        xz = jnp.matmul(u[:, 0], self.in_proj.astype(u.dtype))     # [x | z]
        x, conv_state = (
            k.conv_window_step if path == "fused"
            else k.conv_window_step_xla)(
                conv_state, xz, self.conv_weight, self.conv_bias)
        step, b_mat, c_mat = self._tick_selection(x)
        y, ssm_state = (
            k.selective_state_update_xla if path == "xla"
            else k.selective_state_update)(
                ssm_state, x, step, self.dt_bias, -jnp.exp(self.A_log),
                b_mat, c_mat, self.D, xz)
        out = jnp.matmul(y.astype(u.dtype), self.out_proj.astype(u.dtype))
        return out[:, None], (conv_state, ssm_state)


class _HeadsProjection(nn.Layer):
    """What the two attention-shaped mixers share: one input projection to
    [q | k | v] heads and one output projection back, no bias."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        d, hd, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        self.n_q, self.n_kv, self.hd = (cfg.num_attention_heads,
                                        cfg.num_key_value_heads, hd)
        # [q | k | v], column-parallel over tp
        self.qkv_proj = self.create_parameter(
            [d, (self.n_q + 2 * self.n_kv) * hd], dtype=cfg.dtype,
            initializer=_normal(std), sharding=("fsdp", "tp"))
        self.o_proj = self.create_parameter(
            [self.n_q * hd, d], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("tp", "fsdp"))

    def _qkv(self, u):
        b, s, _ = u.shape
        qkv = jnp.matmul(u, self.qkv_proj.astype(u.dtype))
        q, k, v = jnp.split(qkv, [self.n_q * self.hd,
                                  (self.n_q + self.n_kv) * self.hd], axis=-1)
        return (q.reshape(b, s, self.n_q, self.hd),
                k.reshape(b, s, self.n_kv, self.hd),
                v.reshape(b, s, self.n_kv, self.hd))

    def _o(self, out, u):
        b, s = u.shape[:2]
        return jnp.matmul(out.reshape(b, s, self.n_q * self.hd).astype(
            u.dtype), self.o_proj.astype(u.dtype))


class PowerRetentionMixer(_HeadsProjection):
    """One power-retention layer of degree 2 (the module docstring has its
    equations; ``ops.pallas.power_retention`` the state's layout). The gate's
    projection is float32, as a router's is."""

    def __init__(self, cfg: HybridConfig):
        super().__init__(cfg)

        def vector(shape, value):
            return self.create_parameter(shape, dtype="float32",
                                         initializer=I.Constant(value))
        self.g_proj = self.create_parameter(
            [cfg.hidden_size, self.n_kv], dtype="float32",
            initializer=_normal(cfg.initializer_range))
        self.q_norm = vector([self.hd], 1.0)
        self.k_norm = vector([self.hd], 1.0)

    def _qkvg(self, u, positions):
        """u [b, s, d] at ``positions`` [s] or [b, s] -> (q [b, s, Hq, hd]
        and k [b, s, Hkv, hd], each head normalised and THEN rotated, in u's
        dtype; v [b, s, Hkv, hd]; log g [b, s, Hkv] float32)."""
        from ..ops.rope import rope_at, rotate_half
        f32 = jnp.float32
        q, k, v = self._qkv(u)
        cos, sin = rope_at(positions, self.hd, self.cfg.rope_theta)
        cos, sin = cos[..., None, :], sin[..., None, :]

        def head(t, w):
            t = t.astype(f32)
            t = t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                  + self.cfg.rms_norm_eps) * w
            return (t * cos + rotate_half(t) * sin).astype(u.dtype)
        log_g = jax.nn.log_sigmoid(jnp.matmul(u.astype(f32), self.g_proj))
        return head(q, self.q_norm), head(k, self.k_norm), v, log_g

    def state_path(self, rows, slots: int) -> str:
        """The form the recurrence takes ("kernel" or "xla") in a prompt of
        ``rows`` positions or, ``rows`` None, in a tick of ``slots`` slots.
        Decided from shapes alone."""
        from ..ops.pallas import power_retention as k
        cfg = self.cfg
        if not _on_tpu():
            return "xla"
        if rows is not None:
            ok = k.power_retention_chunked_supported(
                jax.ShapeDtypeStruct((1, rows, self.n_q, self.hd), _dtype(cfg)),
                jax.ShapeDtypeStruct((1, rows, self.n_kv, self.hd),
                                     _dtype(cfg)), cfg.chunk_size)
        else:
            ok = k.power_state_update_supported(
                jax.eval_shape(lambda: self.alloc_slot_state(slots))[0],
                jax.ShapeDtypeStruct((slots, self.n_q, self.hd), _dtype(cfg)))
        return "kernel" if ok else "xla"

    def _sequence(self, u, last_idx=None):
        """Whole sequences u [b, s, d] from a zero state, positions counted
        from 0: (output [b, s, d], the state after position ``last_idx``
        ([b, Hkv, T, hd, hd], [b, Hkv, rows, hd])). Positions past ``last_idx``
        (a bucket's padding; None: the last) take a gate of 1 and a key of
        0, so they leave the state as it is."""
        from ..ops.pallas import power_retention as kern
        b, s, _ = u.shape
        q, k, v, log_g = self._qkvg(u, jnp.arange(s))
        if last_idx is not None:
            live = (jnp.arange(s) <= last_idx)[None, :, None]
            k = jnp.where(live[..., None], k, 0)
            log_g = jnp.where(live, log_g, 0.0)
        scan = (kern.power_retention_chunked
                if self.state_path(s, b) == "kernel"
                else kern.power_retention_chunked_xla)
        y, state, z = scan(q, k, v, log_g, self.cfg.chunk_size)
        return self._o(y, u), state, z

    def forward(self, u):
        return self._sequence(u)[0]

    # -- serving path --------------------------------------------------------

    def alloc_slot_state(self, slots: int):
        """(the state [slots, Hkv, T, hd, hd], T = hd / 2 + 1 tiles, and the
        normaliser's [slots, Hkv, T in whole 8s, hd]), float32 whatever the
        activation dtype."""
        from ..ops.pallas.power_retention import tiles, z_rows
        return (jnp.zeros((slots, self.n_kv, tiles(self.hd), self.hd,
                           self.hd), jnp.float32),
                jnp.zeros((slots, self.n_kv, z_rows(self.hd), self.hd),
                          jnp.float32))

    def prefill(self, u, state, slot, last_idx):
        """The prompt of ONE sequence into slot ``slot``: the state written
        is the state after the prompt's true last position ``last_idx``,
        whatever the bucket the prompt was padded to."""
        out, new, z = self._sequence(u, last_idx)
        return out, (state[0].at[slot].set(new[0]),
                     state[1].at[slot].set(z[0]))

    def decode(self, u, state, pos):
        """One token of every row u [b, 1, d] at position ``pos`` [b]
        through the rows' state (the Pallas kernel on a TPU, in place; its
        ``jnp`` twin elsewhere)."""
        from ..ops.pallas import power_retention as kern
        q, k, v, log_g = self._qkvg(u, pos[:, None])
        update = (kern.power_state_update
                  if self.state_path(None, u.shape[0]) == "kernel"
                  else kern.power_state_update_xla)
        y, new, z = update(*state, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0])
        return self._o(y[:, None], u), (new, z)


class NoPEAttention(_HeadsProjection):
    """Causal GQA attention with no position embedding at all (no rotary,
    no table): ``nemotron_h``'s attention layers. The paged interface of
    ``LlamaAttention`` (pools [H_kv, pages, page, d], the flash kernel for a
    prompt, ``paged_attention_decode`` for a tick) without its rotation."""

    def _sequence(self, u):
        from ..ops.attention import flash_attention
        q, k, v = self._qkv(u)
        return self._o(flash_attention(q, k, v, causal=True), u), k, v

    def forward(self, u):
        return self._sequence(u)[0]

    def alloc_pool(self, num_pages: int, page_size: int):
        shape = (self.n_kv, num_pages, page_size, self.hd)
        return (jnp.zeros(shape, _dtype(self.cfg)),
                jnp.zeros(shape, _dtype(self.cfg)))

    def prefill(self, u, kv, tables):
        """Prompt pass: K and V pages written whole (rows past the prompt
        lie beyond seq_len and are overwritten by decode steps before they
        are unmasked)."""
        out, k, v = self._sequence(u)
        return out, _kv_write_prompt(kv, tables, k, v)

    def decode(self, u, pos, kv, tables):
        b = u.shape[0]
        page = kv[0].shape[2]
        q, k, v = self._qkv(u)
        kv = _kv_scatter_tokens(kv, tables[jnp.arange(b), pos // page],
                                pos % page, jnp.swapaxes(k[:, 0], 0, 1),
                                jnp.swapaxes(v[:, 0], 0, 1))
        return self._o(_paged_decode_attention(q[:, 0], kv, tables, pos),
                       u), kv


class Relu2MLP(nn.Layer):
    """The shared expert: ``relu(x W_up)^2 W_down``, no gate, no bias."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        d, width, std = (cfg.hidden_size, cfg.shared_expert_intermediate_size,
                         cfg.initializer_range)
        self.up_proj = self.create_parameter(
            [d, width], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("fsdp", "tp"))
        self.down_proj = self.create_parameter(
            [width, d], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("tp", "fsdp"))

    def forward(self, x):
        return expert_ffn(x, self.up_proj.astype(x.dtype),
                          self.down_proj.astype(x.dtype), "relu2",
                          jnp.matmul, jnp.matmul)


class GatedMLP(nn.Layer):
    """A dense MLP: ``(silu(x W_gate) * (x W_up)) W_down``, no bias; gate and
    up in one leaf [gate | up]."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        d, width, std = (cfg.hidden_size, cfg.intermediate_size,
                         cfg.initializer_range)
        self.gate_up_proj = self.create_parameter(
            [d, 2 * width], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("fsdp", "tp"))
        self.down_proj = self.create_parameter(
            [width, d], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("tp", "fsdp"))

    def forward(self, x):
        return expert_ffn(x, self.gate_up_proj.astype(x.dtype),
                          self.down_proj.astype(x.dtype), "swiglu",
                          jnp.matmul, jnp.matmul)


STATEFUL = "Mmpd"   # the kinds whose mixer keeps a per-slot state
PAGED = "*a"        # the kinds whose mixer keeps K and V pages
ROUTED = "Ee"       # the kinds that route rows to experts
ZERO_CENTRED = "dae"    # the kinds behind a norm that scales by 1 + w


def _block_norm(cfg: HybridConfig, kind: str):
    """The norm in front of a block of ``kind``: float32, zero-centred for
    the kinds that are."""
    if kind in ZERO_CENTRED:
        from .hybrid_gated import ZeroCentredRMSNorm
        return ZeroCentredRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
    return nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, dtype="float32")


class HybridBlock(nn.Layer):
    """``x + Mixer(RMSNorm(x))``; ``kind`` is the block's pattern character."""

    def __init__(self, cfg: HybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = _block_norm(cfg, kind)
        if kind in "Mm-*p":
            self.mixer = {"M": Mamba2Mixer, "m": Mamba1Mixer, "-": GatedMLP,
                          "*": NoPEAttention,
                          "p": PowerRetentionMixer}[kind](cfg)
        elif kind in ZERO_CENTRED:
            from . import hybrid_gated as gated
            if kind == "e":
                self.mixer = MoELayer(
                    cfg.hidden_size, cfg.moe_intermediate_size,
                    cfg.num_experts, top_k=cfg.num_experts_per_tok,
                    capacity_factor=None, dtype=cfg.dtype, scoring="softmax",
                    norm_topk_prob=cfg.norm_topk_prob,
                    experts_held=cfg.experts_held, expert_act="swiglu")
                self.shared_expert = gated.GatedSharedExpert(cfg)
            else:
                self.mixer = {"d": gated.GatedDeltaNetMixer,
                              "a": gated.GatedAttention}[kind](cfg)
        else:
            self.mixer = MoELayer(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.num_experts_per_tok, capacity_factor=None,
                dtype=cfg.dtype, scoring="sigmoid", select_bias=True,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                experts_held=cfg.experts_held, expert_act="relu2")
            self.shared_expert = Relu2MLP(cfg)

    def experts(self, u):
        """The routed experts held here and the shared one, serving path:
        (y, load [held] int32)."""
        routed, load = self.mixer.forward_inference(u)
        return routed + self.shared_expert(u), load

    def forward(self, x):
        """Whole sequences, no cache (the router's auxiliary loss is dropped:
        not trained)."""
        u = self.norm(x)
        if self.kind not in ROUTED:
            return x + self.mixer(u)
        return x + self.mixer(u)[0] + self.shared_expert(u)


class HybridForCausalLM(nn.Layer, ServingCore):
    """The hybrid decoder with its embedding, final norm and head (the
    embedding's transpose under ``tie_word_embeddings``, else a leaf of its
    own). ``forward`` returns logits, or (loss, logits) given labels."""

    attention_kind = "hybrid"       # what ``serving::prefill`` says of it

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            initializer=_normal(cfg.initializer_range),
            sharding=("tp", "fsdp"))
        self.layers = nn.LayerList([HybridBlock(cfg, kind)
                                    for kind in cfg.kinds])
        # the final norm is of the last block's family
        self.norm = _block_norm(cfg, cfg.kinds[-1])
        if not cfg.tie_word_embeddings:
            self.lm_head = self.create_parameter(
                [cfg.hidden_size, cfg.vocab_size], dtype=cfg.dtype,
                initializer=_normal(cfg.initializer_range),
                sharding=("fsdp", "tp"))
        # what a decode tick counts on the device (the engine adds them up
        # into ``stats()``): rows x top-k routed, whoever holds the chosen
        # expert; the most rows one HELD expert got; the choices that fell
        # on a held expert; each summed over the expert layers
        self.tick_counters = (("moe_assignments", "moe_peak_load",
                               "moe_assignments_held")
                              if set(ROUTED) & set(cfg.kinds) else ())

    def logits(self, hidden):
        if self.cfg.tie_word_embeddings:
            return jnp.matmul(hidden,
                              self.embed_tokens.astype(hidden.dtype).T)
        return jnp.matmul(hidden, self.lm_head.astype(hidden.dtype))

    def _kinds(self, kinds: str):
        return [layer for layer in self.layers if layer.kind in kinds]

    # -- serving path (inference.ContinuousBatchingEngine) -------------------

    def expert_path(self, rows: int):
        """``MoELayer.inference_path`` of the expert layers (all alike)."""
        routed = self._kinds(ROUTED)
        return routed[0].mixer.inference_path(rows) if routed else None

    def state_path(self, rows, slots: int):
        """The stateful mixers' own ``state_path`` (all alike): "kernel" or
        "xla", in a tick also "fused" (``Mamba1Mixer.state_path``)."""
        stateful = self._kinds(STATEFUL)
        return stateful[0].mixer.state_path(rows, slots) if stateful else None

    def pool_layers(self):
        """The ATTENTION layers alone keep pages: a pattern without ``*``
        or ``a`` has NO pool, and the engine then holds no page."""
        return [layer.mixer for layer in self._kinds(PAGED)]

    def alloc_slot_state(self, slots: int):
        """One entry for each layer that carries a state (Mamba of either
        kind, power retention, Gated DeltaNet), in order, every leaf
        leading with the slot; empty for a pattern without one."""
        return [layer.mixer.alloc_slot_state(slots)
                for layer in self._kinds(STATEFUL)]

    def prefill_paged(self, input_ids, pools, tables, slot_state, slot,
                      last_idx):
        """``ServingCore.prefill_paged``."""
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        pools, state = list(pools), list(slot_state)
        n_attn = n_mamba = 0
        for layer in self.layers:
            u = layer.norm(x)
            if layer.kind in STATEFUL:
                y, state[n_mamba] = layer.mixer.prefill(
                    u, state[n_mamba], slot, last_idx)
                n_mamba += 1
            elif layer.kind in PAGED:
                y, pools[n_attn] = layer.mixer.prefill(u, pools[n_attn],
                                                       tables)
                n_attn += 1
            elif layer.kind == "-":
                y = layer.mixer(u)
            else:
                y, _ = layer.experts(u)
            x = x + y
        return self.norm(x), pools, state

    def decode_step_paged(self, token_ids, pos, pools, tables, slot_state):
        """``ServingCore.decode_step_paged``."""
        x = jnp.take(self.embed_tokens, token_ids[:, None], axis=0)
        pools, state = list(pools), list(slot_state)
        n_attn = n_mamba = 0
        routed = peak = held = 0
        for layer in self.layers:
            u = layer.norm(x)
            if layer.kind in STATEFUL:
                # power retention rotates q and k: the one mixer that
                # reads the rows' positions
                y, state[n_mamba] = layer.mixer.decode(
                    u, state[n_mamba], *((pos,) if layer.kind == "p" else ()))
                n_mamba += 1
            elif layer.kind in PAGED:
                y, pools[n_attn] = layer.mixer.decode(u, pos, pools[n_attn],
                                                      tables)
                n_attn += 1
            elif layer.kind == "-":
                y = layer.mixer(u)
            else:
                y, load = layer.experts(u)
                routed += x.shape[0] * self.cfg.num_experts_per_tok
                peak, held = peak + jnp.max(load), held + jnp.sum(load)
            x = x + y
        hidden, counts = self.norm(x), None
        if self.tick_counters:
            counts = jnp.stack([jnp.int32(routed), peak, held]).astype(
                jnp.int32)
        return hidden, pools, state, counts

    def forward(self, input_ids, labels=None):
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        for layer in self.layers:
            x = layer(x)
        logits = self.logits(self.norm(x))
        if labels is None:
            return logits
        from .llama import causal_lm_loss
        return causal_lm_loss(logits, labels), logits
