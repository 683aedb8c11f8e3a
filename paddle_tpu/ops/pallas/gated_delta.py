"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; Qwen3-Next's
linear-attention mixer): one token of every sequence through its state
(``gated_delta_state_update``, a Pallas TPU kernel: a decode tick) and a
whole prompt in chunks (``gated_delta_chunked``, the WY form).

A sequence carries, for every value head, a state ``S`` [K, V] (key size x
value size) in float32. With ``q`` and ``k`` of unit length (``q`` also times
``K^-1/2``), a decay ``alpha`` in (0, 1] and a writing strength ``beta`` in
[0, 1], one number each a head a token, a token moves it by

    S <- alpha S          u = beta (v - S^T k)          S <- S + k u^T
    o = S^T q

The state is READ BACK (``S^T k``) before it is written: the token replaces
what the state holds under its key by its value, where an outer-product
accumulation (Mamba, power retention) only adds. ``Hv`` value heads share
``Hk`` key heads, ``Hv / Hk`` consecutive value heads to one.

**The tick's layout.** The state is kept ``[slots, Hv, K, V]`` as the
mathematics writes it: in a head's tile the KEY's index is on the sublanes
and the value's on the lanes. What the tile is read against and written
with along its rows (``v``, ``u``, ``o``, and the head's two scalars spread
over a row) is then a row broadcast down the sublanes, which costs nothing,
and both readings (``S^T k``, ``S^T q``) are sums over sublanes: whole
register adds and one fold a tile, their result already the row they feed.
Only ``k`` and ``q`` (one number a key index) must be spread along the
lanes, and a key head's spread serves all ``Hv / Hk`` value heads of its
group. The other way round (value on the sublanes) both readings would be
lane reductions, 16 a head. ``k`` and ``q`` come in as COLUMNS ``[slots, K,
2 Hk]`` (XLA's transpose of 32 KB a slot) so that a key head's is a static
column slice. A grid step takes ONE SLOT whole, ``Hv`` tiles of 64 KB (2 MB
each way at Qwen3-Next's 32 heads of 128 x 128), in place
(``input_output_aliases``): the state crosses HBM once each way and no
second copy of it is alive.

**The prompt's form.** Inside a chunk of ``C`` positions, with ``G`` the
running sum of ``log alpha`` and ``D[i, j] = exp(G_i - G_j)`` for ``i >= j``
(the differences are formed and masked BEFORE the exponential: ``exp(-G)``
leaves float32 within a chunk at a fast decay), the ``C`` read-backs are one
unit lower-triangular system:

    A = -tril(beta_i (k_i . k_j) D[i, j], -1)        T = (I - A)^-1
    W = T (beta K exp(G))          U = T (beta V)
    V' = U - W S          O = (Q exp(G)) S + tril(Q K^T D) V'
    S <- exp(G_C) S + (K exp(G_C - G))^T V'

``T`` is the solution of that system by SUBSTITUTION (``_unit_lower_inverse``;
why not by the six doublings ``(I + A)(I + A^2) .. (I + A^32)``, which are as
exact on paper: docs/DESIGN_DECISIONS.md, "The gated delta rule"). The chunks
are walked by a ``lax.scan`` that carries ``S``; everything else is batched
matrix products, XLA's own, float32 at the highest precision.
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

LANES = 128
CHUNK = 64          # positions a chunk of the prompt's form (the published)
VMEM_LIMIT = 32 << 20
_HIGH = jax.lax.Precision.HIGHEST


def l2_normalize(x, eps: float = 1e-6):
    """[.., K] -> float32 of unit length (``x / sqrt(sum x^2 + eps)``)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


# -- the tick -------------------------------------------------------------------

def gated_delta_state_update_xla(state, q, k, v, log_alpha, beta):
    """The update kernel's mathematics in plain ``jax.numpy``, on the same
    layout and in the same order: its oracle in the tests and the path off
    the TPU."""
    f32 = jnp.float32
    rep = state.shape[1] // k.shape[1]
    kf = jnp.repeat(k.astype(f32), rep, axis=1)                # [B, Hv, K]
    qf = jnp.repeat(q.astype(f32), rep, axis=1)
    s = state * jnp.exp(log_alpha.astype(f32))[..., None, None]
    read = jnp.sum(s * kf[..., None], axis=2)                  # [B, Hv, V]
    u = beta.astype(f32)[..., None] * (v.astype(f32) - read)
    s = s + kf[..., None] * u[:, :, None, :]
    return jnp.sum(s * qf[..., None], axis=2), s


def _over_keys(tile):
    """[K, V] -> [1, V]: the sum over the key index as whole-register adds
    (K / 8 of them) and ONE fold of the 8 sublanes left."""
    key, val = tile.shape
    return jnp.sum(jnp.sum(tile.reshape(key // 8, 8, val), axis=0), axis=0,
                   keepdims=True)


def _update_kernel(rows_ref, kq_ref, s_ref, o_ref, s_out_ref, *, key_heads):
    # rows_ref [1, 3, Hv, V]: a head's v, alpha and beta (each scalar over
    # its row); kq_ref [1, K, 2 Hk]: column j key head j's k, column Hk + j
    # its q; s_ref, s_out_ref [1, Hv, K, V]; o_ref [1, Hv, V]
    heads, key, val = s_ref.shape[1:]
    rep = heads // key_heads
    cols = kq_ref[0]
    for j in range(key_heads):
        k_full = jnp.broadcast_to(cols[:, j:j + 1], (key, val))
        q_full = jnp.broadcast_to(
            cols[:, key_heads + j:key_heads + j + 1], (key, val))
        for h in range(j * rep, (j + 1) * rep):
            s = s_ref[0, h] * rows_ref[0, 1, h:h + 1, :]
            read = _over_keys(s * k_full)
            u = rows_ref[0, 2, h:h + 1, :] * (rows_ref[0, 0, h:h + 1, :]
                                              - read)
            s = s + k_full * u
            s_out_ref[0, h] = s
            o_ref[0, h:h + 1, :] = _over_keys(s * q_full)


def gated_delta_state_update(state, q, k, v, log_alpha, beta,
                             interpret: bool = False):
    """One token of every sequence through its delta-rule state, in place.

    state: [B, Hv, K, V] float32 — updated IN PLACE (the second result is
           the same buffer where the caller donates it)
    q, k:  [B, Hk, K] — of unit length, q times K^-1/2 (the caller's); key
           head j serves value heads j Hv / Hk .. (j + 1) Hv / Hk - 1
    v:     [B, Hv, V]
    log_alpha, beta: [B, Hv] float32 — the decay's logarithm (<= 0) and the
           writing strength

    Returns (o [B, Hv, V] float32 = S'^T q, S').
    """
    B, H, K, V = state.shape
    Hk, f32 = k.shape[1], jnp.float32

    def over_row(x):                    # a head's scalar over its row
        return jnp.broadcast_to(x.astype(f32)[..., None], (B, H, V))
    rows = jnp.stack([v.astype(f32), over_row(jnp.exp(log_alpha.astype(f32))),
                      over_row(beta)], axis=1)                 # [B, 3, Hv, V]
    cols = jnp.swapaxes(jnp.concatenate(
        [k.astype(f32), q.astype(f32)], axis=1), 1, 2)         # [B, K, 2 Hk]
    tile = pl.BlockSpec((1, H, K, V), lambda i: (i, 0, 0, 0))
    out_row = pl.BlockSpec((1, H, V), lambda i: (i, 0, 0))
    o, new = pl.pallas_call(
        functools.partial(_update_kernel, key_heads=Hk),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, 3, H, V), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((1, K, 2 * Hk), lambda i: (i, 0, 0)), tile],
        out_specs=[out_row, tile],
        out_shape=[jax.ShapeDtypeStruct((B, H, V), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={2: 1},
        # a slot's block in and out, double-buffered: four times its bytes
        compiler_params=(None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT)),
        interpret=interpret,
        name="gated_delta_state_update",
    )(rows, cols, state)
    return o, new


def gated_delta_state_update_supported(state, k) -> bool:
    """Mosaic's rules for this layout: whole (8, 128) float32 tiles a head,
    whole groups of value heads, and a slot's block (twice, double-buffered,
    in and out) within the VMEM asked for."""
    from ..registry import pallas_disabled
    if not _HAS_PLTPU or pallas_disabled():
        return False
    _, heads, key, val = state.shape
    return (state.dtype == jnp.float32 and val % LANES == 0 and key % 8 == 0
            and heads % 8 == 0 and heads % k.shape[1] == 0
            and 4 * 4 * heads * key * val <= VMEM_LIMIT - (8 << 20))


# -- the prompt -----------------------------------------------------------------

def _unit_lower_inverse(a, base: int = 16):
    """``(I - A)^-1`` for ``A`` [.., C, C] strictly lower triangular, by
    substitution: a diagonal block of ``base`` rows a row at a time (row i
    of the inverse is row i of ``I`` plus ``A[i, :i]`` times the rows above
    it), and two halves joined by ``[[T1, 0], [T2 A21 T1, T2]]``. No power
    of ``A`` is formed."""
    c = a.shape[-1]
    if c > base:
        h = c // 2
        t = _unit_lower_inverse(
            jnp.stack([a[..., :h, :h], a[..., h:, h:]]), base)
        low = jnp.einsum("...ij,...jk,...kl->...il", t[1], a[..., h:, :h],
                         t[0], precision=_HIGH)
        return jnp.concatenate(
            [jnp.concatenate([t[0], jnp.zeros_like(low)], -1),
             jnp.concatenate([low, t[1]], -1)], -2)
    eye = jnp.eye(c, dtype=a.dtype)

    def row(i, t):
        # rows >= i of ``t`` are still rows of I, where A[i, :] is 0
        return t.at[..., i, :].set(eye[i] + jnp.einsum(
            "...j,...jk->...k", a[..., i, :], t, precision=_HIGH))

    return jax.lax.fori_loop(1, c, row, jnp.broadcast_to(eye, a.shape))


def gated_delta_chunked(q, k, v, log_alpha, beta, chunk: int = CHUNK):
    """Whole sequences through the gated delta rule from a zero state, in
    chunks (the module docstring has the algebra).

    q, k:  [b, L, Hk, K] — of unit length, q times K^-1/2
    v:     [b, L, Hv, V]
    log_alpha, beta: [b, L, Hv] float32 — 0 and 0 at a position that must
           leave the state as it is (a bucket's padding)

    Returns (o [b, L, Hv, V] float32, S [b, Hv, K, V] float32 after position
    L - 1). ``L`` is padded to whole chunks with such positions.
    """
    f32 = jnp.float32
    b, L, hv, V = v.shape
    hk, K = k.shape[2:]
    rep, pad = hv // hk, -L % chunk
    nc = (L + pad) // chunk

    def split(t):           # [b, L, H, ..] -> [nc, b, H, chunk, ..]
        t = jnp.pad(t.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = jnp.moveaxis(t, 1, 2)
        return jnp.moveaxis(t.reshape(b, t.shape[1], nc, chunk, *t.shape[3:]),
                            2, 0)
    with jax.named_scope("gated_delta_chunked"):
        qs, ks = (jnp.repeat(split(t), rep, axis=2) for t in (q, k))
        vs, bs = split(v), split(beta)
        g = jnp.cumsum(split(log_alpha), axis=-1)          # [nc, b, Hv, C]
        low = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(low, g[..., :, None] - g[..., None, :],
                                  -jnp.inf))               # [.., i, j], i >= j
        kk = jnp.einsum("...ik,...jk->...ij", ks, ks, precision=_HIGH)
        strict = jnp.tril(jnp.ones((chunk, chunk), f32), -1)
        t = _unit_lower_inverse(-(bs[..., None] * kk * decay) * strict)
        e = jnp.exp(g)[..., None]                          # [.., C, 1]
        w = jnp.einsum("...ij,...jk->...ik", t, bs[..., None] * ks * e,
                       precision=_HIGH)
        u = jnp.einsum("...ij,...jv->...iv", t, bs[..., None] * vs,
                       precision=_HIGH)
        inside = jnp.einsum("...ik,...jk->...ij", qs, ks,
                            precision=_HIGH) * decay
        to_end = jnp.exp(g[..., -1:] - g)[..., None]       # [.., C, 1]
        whole = jnp.exp(g[..., -1])[..., None, None]       # [.., 1, 1]

        def step(s, xs):
            w_c, u_c, q_c, in_c, k_c, all_c = xs
            fresh = u_c - jnp.einsum("bhik,bhkv->bhiv", w_c, s,
                                     precision=_HIGH)
            o = (jnp.einsum("bhik,bhkv->bhiv", q_c, s, precision=_HIGH)
                 + jnp.einsum("bhij,bhjv->bhiv", in_c, fresh,
                              precision=_HIGH))
            s = all_c * s + jnp.einsum("bhik,bhiv->bhkv", k_c, fresh,
                                       precision=_HIGH)
            return s, o

        state, o = jax.lax.scan(
            step, jnp.zeros((b, hv, K, V), f32),
            (w, u, qs * e, inside, ks * to_end, whole))
        # [nc, b, Hv, C, V] -> [b, L, Hv, V]
        o = jnp.moveaxis(o, 0, 2).reshape(b, hv, nc * chunk, V)
        return jnp.moveaxis(o, 1, 2)[:, :L], state


__all__ = ["l2_normalize", "gated_delta_state_update",
           "gated_delta_state_update_xla",
           "gated_delta_state_update_supported", "gated_delta_chunked",
           "CHUNK"]
