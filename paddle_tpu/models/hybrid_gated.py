"""The gated blocks of ``HybridForCausalLM`` (``qwen3_next``'s layout, as
Qwen3-Next-80B-A3B-Instruct publishes it): pattern characters ``d``, ``a``
and ``e``. A published layer is TWO blocks (``de`` or ``ae``), each behind a
norm of its own, and every such norm is ZERO-CENTRED: ``x / rms(x) * (1 +
w)``, in float32 (``ZeroCentredRMSNorm``; a property of these three kinds,
not a switch the other kinds could flip).

- ``d``: a Gated DeltaNet layer (``GatedDeltaNetMixer``, arXiv:2412.06464):
  one projection to ``[q | k | v | z | b | a]``; a causal depthwise
  convolution WITHOUT bias and SiLU over ``[q | k | v]``; ``Hk`` key heads
  each serving ``Hv / Hk`` consecutive value heads; ``q`` and ``k`` of unit
  length, ``q`` times ``K^-1/2``; ``beta = sigmoid(b)``, ``log alpha =
  -exp(A_log) softplus(a + dt_bias)`` a value head; the gated delta rule
  over a float32 state [Hv, K, V] (``ops.pallas.gated_delta``: the state is
  read back before it is written); a head's reading through RMSNorm (NOT
  zero-centred) times ``silu(z)``; one output projection.
- ``a``: causal GQA attention with an output gate (``GatedAttention``): the
  query projection gives ``[q | gate]`` a head; zero-centred RMSNorm over
  each head of q and of k; a rotary embedding (half-rotation) on the FIRST
  ``partial_rotary_factor x head_dim`` dims of q and k; ``o * sigmoid(gate)``;
  K and V in the paged pools.
- ``e``: softmax-routed SwiGLU experts, the top-k's probabilities
  renormalised (``parallel.moe.MoELayer``; a SHARE of them may be held),
  beside one shared SwiGLU expert times ``sigmoid(u w_sg)``
  (``GatedSharedExpert``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I
from ..parallel.moe import expert_ffn
from .hybrid_lm import NoPEAttention, _conv_silu, _dtype, _on_tpu
from .llama import (_kv_scatter_tokens, _kv_write_prompt, _normal,
                    _paged_decode_attention)


def zero_centred_rms_norm(x, w, eps):
    """``x / rms(x) * (1 + w)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


class ZeroCentredRMSNorm(nn.Layer):
    """RMSNorm whose stored weight is the scale's distance from 1."""

    def __init__(self, size: int, epsilon: float):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter([size], dtype="float32",
                                            initializer=I.Constant(0.0))

    def forward(self, x):
        return zero_centred_rms_norm(x, self.weight, self.epsilon).astype(
            x.dtype)


class GatedDeltaNetMixer(nn.Layer):
    """One Gated DeltaNet layer (the module docstring has its equations).
    The projection's columns are ``[q | k | v | z | b | a]``, heads in order
    inside each (the published checkpoint groups them by key head)."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        d, std = cfg.hidden_size, cfg.initializer_range
        self.hk, self.hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        self.dk, self.dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        if self.hv % self.hk:
            raise ValueError("linear_num_key_heads must divide "
                             "linear_num_value_heads")
        self.key_dim, self.value_dim = self.hk * self.dk, self.hv * self.dv
        self.conv_dim = 2 * self.key_dim + self.value_dim

        def vector(shape, value):
            return self.create_parameter(shape, dtype="float32",
                                         initializer=I.Constant(value))
        self.in_proj = self.create_parameter(
            [d, self.conv_dim + self.value_dim + 2 * self.hv],
            dtype=cfg.dtype, initializer=_normal(std),
            sharding=("fsdp", None))
        self.conv_weight = vector([cfg.conv_kernel, self.conv_dim], 1.0)
        self.dt_bias = vector([self.hv], 0.0)
        self.A_log = vector([self.hv], 0.0)
        self.norm_weight = vector([self.dv], 1.0)
        self.out_proj = self.create_parameter(
            [self.value_dim, d], dtype=cfg.dtype, initializer=_normal(std),
            sharding=(None, "fsdp"))

    def _project(self, u):
        """u [.., d] -> (qkv [.., conv_dim] before its convolution, the gate
        z [.., Hv, V], beta [.., Hv] and log alpha [.., Hv], float32)."""
        f32 = jnp.float32
        proj = jnp.matmul(u, self.in_proj.astype(u.dtype))
        conv, wide = self.conv_dim, self.conv_dim + self.value_dim
        z = proj[..., conv:wide].reshape(*u.shape[:-1], self.hv, self.dv)
        b = proj[..., wide:wide + self.hv].astype(f32)
        a = proj[..., wide + self.hv:].astype(f32)
        return (proj[..., :conv], z, jax.nn.sigmoid(b),
                -jnp.exp(self.A_log) * jax.nn.softplus(a + self.dt_bias))

    def _heads(self, x):
        """The convolved [q | k | v] [.., conv_dim] -> (q, k [.., Hk, K]
        float32 of unit length, q times K^-1/2; v [.., Hv, V])."""
        from ..ops.pallas.gated_delta import l2_normalize
        lead, kd = x.shape[:-1], self.key_dim
        q = l2_normalize(x[..., :kd].reshape(*lead, self.hk, self.dk))
        k = l2_normalize(x[..., kd:2 * kd].reshape(*lead, self.hk, self.dk))
        return (q * self.dk ** -0.5, k,
                x[..., 2 * kd:].reshape(*lead, self.hv, self.dv))

    def _out(self, o, z):
        """o [.., Hv, V] float32 (the state's reading) -> the layer's
        output: a head's RMSNorm, THEN times silu(z), out_proj."""
        y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + self.cfg.rms_norm_eps) * self.norm_weight
        y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
        return jnp.matmul(y.reshape(*y.shape[:-2], self.value_dim),
                          self.out_proj.astype(z.dtype))

    def state_path(self, rows, slots: int) -> str:
        """The form the recurrence takes in a prompt of ``rows`` positions
        ("xla": the chunked form is batched matrix products, XLA's) or,
        ``rows`` None, in a tick of ``slots`` slots ("kernel" or "xla").
        Decided from shapes alone."""
        from ..ops.pallas.gated_delta import gated_delta_state_update_supported
        if rows is not None or not _on_tpu():
            return "xla"
        state = jax.eval_shape(lambda: self.alloc_slot_state(slots))[1]
        k = jax.ShapeDtypeStruct((slots, self.hk, self.dk), jnp.float32)
        return ("kernel" if gated_delta_state_update_supported(state, k)
                else "xla")

    def _sequence(self, u, last_idx=None):
        """Whole sequences u [b, s, d] from a zero state: (output [b, s, d],
        the convolution's inputs after position ``last_idx`` [b, k - 1,
        conv_dim], the state after it [b, Hv, K, V]). Positions past
        ``last_idx`` (a bucket's padding; None: the last) take alpha = 1 and
        beta = 0, so they leave the state as it is."""
        from ..ops.pallas.gated_delta import gated_delta_chunked
        s, taps = u.shape[1], self.cfg.conv_kernel
        last_idx = s - 1 if last_idx is None else last_idx
        qkv, z, beta, log_alpha = self._project(u)
        padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
        q, k, v = self._heads(_conv_silu(
            [padded[:, i:i + s] for i in range(taps)], self.conv_weight, 0.0))
        live = (jnp.arange(s) <= last_idx)[None, :, None]
        o, state = gated_delta_chunked(
            q, k, v, jnp.where(live, log_alpha, 0.0),
            jnp.where(live, beta, 0.0), self.cfg.delta_chunk_size)
        tail = jax.lax.dynamic_slice_in_dim(padded, last_idx + 1, taps - 1,
                                            axis=1)
        return self._out(o, z), tail, state

    def forward(self, u):
        return self._sequence(u)[0]

    # -- serving path --------------------------------------------------------

    def alloc_slot_state(self, slots: int):
        """(the convolution's last k - 1 inputs [slots, k - 1, conv_dim] in
        the activation dtype, the delta rule's state [slots, Hv, K, V] in
        float32 whatever the activation dtype)."""
        cfg = self.cfg
        return (jnp.zeros((slots, cfg.conv_kernel - 1, self.conv_dim),
                          _dtype(cfg)),
                jnp.zeros((slots, self.hv, self.dk, self.dv), jnp.float32))

    def prefill(self, u, state, slot, last_idx):
        """The prompt of ONE sequence into slot ``slot``: the state written
        is the state after the prompt's true last position ``last_idx``,
        whatever the bucket the prompt was padded to."""
        out, tail, new = self._sequence(u, last_idx)
        conv_state, delta_state = state
        return out, (conv_state.at[slot].set(tail[0].astype(conv_state.dtype)),
                     delta_state.at[slot].set(new[0]))

    def decode(self, u, state):
        """One token of every row u [b, 1, d] through the rows' state (the
        Pallas kernel on a TPU, in place; its ``jnp`` twin elsewhere)."""
        from ..ops.pallas import gated_delta as kern
        conv_state, delta_state = state
        qkv, z, beta, log_alpha = self._project(u[:, 0])
        window = jnp.concatenate(
            [conv_state, qkv[:, None].astype(conv_state.dtype)], axis=1)
        q, k, v = self._heads(_conv_silu(
            [window[:, i] for i in range(window.shape[1])],
            self.conv_weight, 0.0))
        update = (kern.gated_delta_state_update
                  if self.state_path(None, u.shape[0]) == "kernel"
                  else kern.gated_delta_state_update_xla)
        o, delta_state = update(delta_state, q, k, v, log_alpha, beta)
        return self._out(o, z)[:, None], (window[:, 1:], delta_state)


class GatedAttention(nn.Layer):
    """Causal GQA attention with per-head zero-centred q and k norms, a
    partial rotary embedding and an output gate (the module docstring). One
    projection leaf, columns ``[Hq x [q | gate] | k | v]``; the paged
    interface of ``NoPEAttention`` (pools [Hkv, pages, page, d])."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        d, hd, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        self.n_q, self.n_kv, self.hd = (cfg.num_attention_heads,
                                        cfg.num_key_value_heads, hd)
        self.rot = int(cfg.partial_rotary_factor * hd)
        if self.rot % 2:
            raise ValueError("partial_rotary_factor x head_dim must be even")
        self.qkv_proj = self.create_parameter(
            [d, 2 * (self.n_q + self.n_kv) * hd], dtype=cfg.dtype,
            initializer=_normal(std), sharding=("fsdp", "tp"))
        self.o_proj = self.create_parameter(
            [self.n_q * hd, d], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("tp", "fsdp"))
        for name in ("q_norm", "k_norm"):
            setattr(self, name, self.create_parameter(
                [hd], dtype="float32", initializer=I.Constant(0.0)))

    def _rotate(self, t, cos, sin):
        """The first ``rot`` dims of t [.., hd] (float32) turned, the rest
        as they are."""
        from ..ops.rope import rotate_half
        head, rest = t[..., :self.rot], t[..., self.rot:]
        return jnp.concatenate([head * cos + rotate_half(head) * sin, rest],
                               axis=-1)

    def _qkvg(self, u, positions):
        """u [b, s, d] at ``positions`` [s] or [b, s] -> (q [b, s, Hq, hd]
        and k [b, s, Hkv, hd], each head normalised and THEN rotated, in u's
        dtype; v [b, s, Hkv, hd]; the gate [b, s, Hq, hd] before its
        sigmoid)."""
        from ..ops.rope import rope_at
        b, s, _ = u.shape
        wide = 2 * self.n_q * self.hd
        proj = jnp.matmul(u, self.qkv_proj.astype(u.dtype))
        qg = proj[..., :wide].reshape(b, s, self.n_q, 2 * self.hd)
        k, v = jnp.split(proj[..., wide:].reshape(b, s, 2 * self.n_kv,
                                                  self.hd), 2, axis=2)
        cos, sin = rope_at(positions, self.rot, self.cfg.rope_theta)
        cos, sin = cos[..., None, :], sin[..., None, :]

        def head(t, w):
            t = zero_centred_rms_norm(t, w, self.cfg.rms_norm_eps)
            return self._rotate(t, cos, sin).astype(u.dtype)
        return (head(qg[..., :self.hd], self.q_norm), head(k, self.k_norm),
                v, qg[..., self.hd:])

    def _o(self, out, gate, u):
        b, s = u.shape[:2]
        out = (out.astype(jnp.float32)
               * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(u.dtype)
        return jnp.matmul(out.reshape(b, s, self.n_q * self.hd),
                          self.o_proj.astype(u.dtype))

    def _sequence(self, u):
        from ..ops.attention import flash_attention
        q, k, v, gate = self._qkvg(u, jnp.arange(u.shape[1]))
        return self._o(flash_attention(q, k, v, causal=True), gate, u), k, v

    def forward(self, u):
        return self._sequence(u)[0]

    alloc_pool = NoPEAttention.alloc_pool       # K and V [Hkv, pages, page, d]

    def prefill(self, u, kv, tables):
        """Prompt pass, positions counted from 0: K (rotated) and V pages
        written whole (rows past the prompt lie beyond seq_len and are
        overwritten by decode steps before they are unmasked)."""
        out, k, v = self._sequence(u)
        return out, _kv_write_prompt(kv, tables, k, v)

    def decode(self, u, pos, kv, tables):
        b = u.shape[0]
        page = kv[0].shape[2]
        q, k, v, gate = self._qkvg(u, pos[:, None])
        kv = _kv_scatter_tokens(kv, tables[jnp.arange(b), pos // page],
                                pos % page, jnp.swapaxes(k[:, 0], 0, 1),
                                jnp.swapaxes(v[:, 0], 0, 1))
        out = _paged_decode_attention(q[:, 0], kv, tables, pos)
        return self._o(out[:, None], gate, u), kv


class GatedSharedExpert(nn.Layer):
    """The shared expert: ``sigmoid(x w_sg) * (silu(x W_gate) * (x W_up))
    W_down``, no bias; gate and up in one leaf [gate | up], ``w_sg`` [d, 1]
    float32 as a router is."""

    def __init__(self, cfg):
        super().__init__()
        d, width, std = (cfg.hidden_size, cfg.shared_expert_intermediate_size,
                         cfg.initializer_range)
        self.gate_up_proj = self.create_parameter(
            [d, 2 * width], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("fsdp", "tp"))
        self.down_proj = self.create_parameter(
            [width, d], dtype=cfg.dtype, initializer=_normal(std),
            sharding=("tp", "fsdp"))
        self.shared_gate = self.create_parameter(
            [d, 1], dtype="float32", initializer=_normal(std))

    def forward(self, x):
        y = expert_ffn(x, self.gate_up_proj.astype(x.dtype),
                       self.down_proj.astype(x.dtype), "swiglu",
                       jnp.matmul, jnp.matmul)
        gate = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                         self.shared_gate))
        return (gate * y.astype(jnp.float32)).astype(x.dtype)
