"""Pallas TPU kernels: a power-retention layer's recurrence (degree 2), one
token of every sequence (``power_state_update``: a decode tick) and a whole
prompt in chunks (``power_retention_chunked``).

Power retention (Manifest AI, arXiv:2507.04239; Brumby's mixer) replaces the
softmax of attention by an even power of the score. With degree 2 the weight
of position j for position t is ``exp(G_t - G_j) (q_t . k_j)^2 / d`` (``G`` the
running sum of a gate's logarithm, one number a KV head a token), and a
square is an inner product in a larger space: ``phi(x) . phi(y) = (x . y)^2 /
d`` for ``phi(x)`` the ``D = d (d + 1) / 2`` distinct products ``x_i x_j``.
So a sequence carries, for every KV head, a state in float32

    S[t] = g[t] S[t-1] + phi(k[t]) v[t]^T      [D, d]     (D = 8,256 at d = 128)
    z[t] = g[t] z[t-1] + phi(k[t])             [D]
    y[t, n] = phi(q[t, n])^T S[t] / (phi(q[t, n]) . z[t] + eps)

shared by the ``R = Hq / Hkv`` query heads of its group, and that state IS
the traffic: 4.2 MB a KV head, 34 MB a sequence a layer at Brumby's widths.
``phi`` never exists in HBM: both kernels form it in VMEM from the d numbers.

**The layout of D** is circulant: the unordered pairs {i, j} of 0..d-1 are
indexed by their distance ``t = (i - j) mod d`` in 0..d/2 and by ``i``, so

    phi(x)[t, l] = c_t x[l] x[(l - t) mod d] / sqrt(d)      c_0 = 1, else sqrt 2

is a lane-rotation of ``x`` times ``x``: tile ``t`` is ONE ``roll`` of the d
lanes, no gather, no triangle to unpack. Distances 0..d/2 - 1 give d pairs
each; distance d/2 gives every pair twice, so its lanes ``l >= d/2`` are
held at zero. That is ``T = d/2 + 1`` tiles of d lanes: 65 x 128 = 8,320
rows held for the 8,256 the mathematics has (64 of padding: 0.8%). The state
is kept ``[.., T, d (v), d (l)]``: in a tile the value's index on the
sublanes and the pair's on the lanes, so what multiplies a tile's columns
(``phi(k)``, ``phi(q)``) is a row broadcast down the sublanes and ``v`` is
spread along the lanes once a head (one transpose); ``z`` is ``[.., T, d]``.

``power_state_update`` sweeps one (slot, KV head) block a grid step, in
place (``input_output_aliases``): the decay, the rank-1 term and the R
readings on the vector units in one pass, each reading summed over its
lanes at the end (a transpose and a sum over sublanes).
``power_retention_chunked`` walks a prompt in chunks of 128 positions with
the state resident in VMEM (it is the kernel's output block): inside a chunk
masked scores ``(q k^T)^2 / d`` times the decay's differences (masked BEFORE
the exponential), against the carried state one product a tile, ``[R chunk,
d] x [d, d]``, with ``phi`` of the chunk's rows made a tile at a time. Its
matrix products take their operands in ``q``'s dtype (bfloat16 in a bfloat16
model, one pass of the MXU; float32 in a float32 one) and add up in float32;
the state is float32 whatever that dtype is.
"""

from __future__ import annotations

import functools
import math

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
EPS = 1e-6          # added to the sum of a position's weights
CHUNK = 128         # positions a step of the prompt's kernel
ROWS = 32           # sublanes of a tile the tick works on at once (4 vregs)
UNROLL = 5          # tiles a trip of the tick's loop (65 = 13 x 5)
VMEM_LIMIT = 64 << 20


def tiles(d: int) -> int:
    """Tiles of d lanes that hold the d (d + 1) / 2 pairs: distances 0..d/2."""
    return d // 2 + 1


def z_rows(d: int) -> int:
    """Rows the normaliser's state is held in: the tiles, in whole sublane
    tiles (65 -> 72, the rest zero), so that the chip lays ``[.., Hkv, rows,
    d]`` out in that order (it would put a dimension of 65 further out)."""
    return -(-tiles(d) // 8) * 8


def _coef(d: int):
    """[T, d] float32: ``c_t / sqrt(d)``, 0 on the half of the last tile
    that would hold its pairs a second time."""
    t = jnp.arange(tiles(d))[:, None]
    lane = jnp.arange(d)[None, :]
    c = jnp.where(t == 0, 1.0, math.sqrt(2.0)) / math.sqrt(d)
    return jnp.where((t == d // 2) & (lane >= d // 2), 0.0, c).astype(
        jnp.float32)


def phi(x):
    """[.., d] -> [.., T, d] float32, ``phi(x) . phi(y) = (x . y)^2 / d`` (d
    even), in the state's layout."""
    d = x.shape[-1]
    x = x.astype(jnp.float32)
    partner = (jnp.arange(d)[None, :] - jnp.arange(tiles(d))[:, None]) % d
    return x[..., None, :] * x[..., partner] * _coef(d)


def _phi_tile(x, t, lane):
    """Tile ``t`` (static or traced) of ``phi`` of the rows ``x`` [n, d],
    inside a kernel: one rotation of the lanes and a product; ``lane`` is
    x's lane index."""
    d = x.shape[-1]
    coef = jnp.where(t == 0, 1.0, math.sqrt(2.0)) / math.sqrt(d)
    live = jnp.where(t == d // 2, d // 2, d)
    return jnp.where(lane < live, x * pltpu.roll(x, t, 1) * coef, 0.0)


# -- the tick -------------------------------------------------------------------

def power_state_update_xla(state, z, q, k, v, log_g):
    """The update kernel's mathematics in plain ``jax.numpy``, on the same
    layout: its oracle in the tests and the path off the TPU."""
    f32 = jnp.float32
    b, hkv, d = k.shape
    g = jnp.exp(log_g.astype(f32))[..., None, None]               # [B, H, 1, 1]
    pk = phi(k)                                                   # [B, H, T, d]
    pq = phi(q.reshape(b, hkv, -1, d))                            # [B, H, R, T, d]
    new = (g[..., None] * state
           + v.astype(f32)[:, :, None, :, None] * pk[:, :, :, None, :])
    z_new = g * z[:, :, :tiles(d)] + pk
    # a reading is a small sum of large signed terms: float32 products,
    # whatever the backend's default for a float32 einsum is
    high = jax.lax.Precision.HIGHEST
    num = jnp.einsum("bhrtl,bhtvl->bhrv", pq, new, precision=high)
    den = jnp.einsum("bhrtl,bhtl->bhr", pq, z_new, precision=high)
    return ((num / (den[..., None] + EPS)).reshape(q.shape), new,
            _whole_rows(z_new))


def _whole_rows(z):
    """[.., T, d] -> [.., z_rows(d), d], zeros below."""
    pad = [(0, 0)] * z.ndim
    pad[-2] = (0, z_rows(z.shape[-1]) - z.shape[-2])
    return jnp.pad(z, pad)


def _update_kernel(g_ref, x_ref, z_ref, s_ref, y_ref, z_out_ref, s_out_ref,
                   phi_ref, acc_ref, *, group, heads):
    # g_ref [B Hkv] (SMEM); x_ref, y_ref [1, 1, 8, d]: rows 0..R-1 the
    # group's q, row R k, row R + 1 v; z_ref [1, 1, z_rows, d]; s_ref
    # [1, 1, T, d, d]; phi_ref [T, R + 1, 8, d] (a row of phi on all 8
    # sublanes); acc_ref [R, d, d]
    f32 = jnp.float32
    n_tiles, d = s_ref.shape[2:4]
    g = g_ref[pl.program_id(0) * heads + pl.program_id(1)]
    x = x_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    den = jnp.zeros(x.shape, f32)
    for t in range(n_tiles):
        p = _phi_tile(x, t, lane)
        row = g * z_ref[0, 0, t:t + 1, :] + p[group:group + 1]
        z_out_ref[0, 0, t:t + 1, :] = row
        den = den + p * row
        for n in range(group + 1):
            phi_ref[t, n] = jnp.broadcast_to(p[n:n + 1], x.shape)
    z_out_ref[0, 0, n_tiles:, :] = jnp.zeros(
        (z_ref.shape[2] - n_tiles, d), f32)
    den = jnp.sum(den, axis=1, keepdims=True)                     # [8, 1]
    # v spread along the lanes: vb[i, :] = v[i]
    vb = jnp.broadcast_to(x[group + 1:group + 2], (d, d)).T
    per = ROWS // 8
    for r in range(d // ROWS):
        spread = [vb[r * ROWS + 8 * j:r * ROWS + 8 * (j + 1)]
                  for j in range(per)]

        def some(i, acc, r=r, spread=spread):
            out = list(acc)
            for t in (i * UNROLL + u for u in range(UNROLL)):
                ps = [phi_ref[t, n] for n in range(group + 1)]
                for j in range(per):
                    rows = pl.ds(r * ROWS + 8 * j, 8)
                    s = g * s_ref[0, 0, t, rows, :] + spread[j] * ps[group]
                    s_out_ref[0, 0, t, rows, :] = s
                    for n in range(group):
                        out[n * per + j] = out[n * per + j] + s * ps[n]
            return tuple(out)

        acc = jax.lax.fori_loop(
            0, n_tiles // UNROLL, some,
            tuple(jnp.zeros((8, d), f32) for _ in range(group * per)))
        for n in range(group):
            for j in range(per):
                acc_ref[n, r * ROWS + 8 * j:r * ROWS + 8 * (j + 1), :] = (
                    acc[n * per + j])
    # a reading is its accumulator summed over the lanes: as a row
    sublane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    num = jnp.zeros(x.shape, f32)
    for n in range(group):
        num = jnp.where(sublane == n, jnp.sum(acc_ref[n].T, axis=0,
                                              keepdims=True), num)
    y_ref[0, 0] = num / (den + EPS)


def power_state_update(state, z, q, k, v, log_g, interpret: bool = False):
    """One token of every sequence through its power-retention state, in
    place.

    state: [B, Hkv, T, d, d] float32 — updated IN PLACE (returned as the same
           buffer where the caller donates it); T = d / 2 + 1
    z:     [B, Hkv, z_rows(d), d] float32 — the normaliser's state (rows
           past T zero), in place too
    q:     [B, Hq, d]; head n reads the state of KV head n // (Hq / Hkv)
    k, v:  [B, Hkv, d]
    log_g: [B, Hkv] float32 — the logarithm of the token's gate

    Returns (y [B, Hq, d] float32, state', z').
    """
    B, H, T, d, _ = state.shape
    R = q.shape[1] // H
    f32 = jnp.float32
    rows = jnp.concatenate(
        [q.reshape(B, H, R, d).astype(f32), k.astype(f32)[:, :, None],
         v.astype(f32)[:, :, None], jnp.zeros((B, H, 6 - R, d), f32)], axis=2)
    row = pl.BlockSpec((1, 1, 8, d), lambda b, h, g: (b, h, 0, 0))
    zed = pl.BlockSpec((1, 1, z.shape[2], d), lambda b, h, g: (b, h, 0, 0))
    tile = pl.BlockSpec((1, 1, T, d, d), lambda b, h, g: (b, h, 0, 0, 0))
    y, z_new, new = pl.pallas_call(
        functools.partial(_update_kernel, group=R, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H),
            in_specs=[row, zed, tile], out_specs=[row, zed, tile],
            scratch_shapes=[pltpu.VMEM((T, R + 1, 8, d), f32),
                            pltpu.VMEM((R, d, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, 8, d), f32),
                   jax.ShapeDtypeStruct(z.shape, f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands count the prefetched scalars: z is 2, the state 3
        input_output_aliases={2: 1, 3: 2},
        compiler_params=(None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT)),
        interpret=interpret,
        name="power_state_update",
    )(jnp.exp(log_g.astype(f32)).reshape(B * H), rows, z, state)
    return y[:, :, :R].reshape(B, H * R, d), new, z_new


def power_state_update_supported(state, q) -> bool:
    """Mosaic's rules for this layout: tiles of exactly 128 lanes, a group
    of at most 6 query heads (q, k and v of a KV head ride in one tile of 8
    rows), and a block in and out, double-buffered, within the VMEM asked
    for."""
    from ..registry import pallas_disabled
    _, heads, n_tiles, d, _ = state.shape
    return (_HAS_PLTPU and not pallas_disabled() and state.dtype == jnp.float32
            and d == LANES and n_tiles == tiles(d) and q.shape[1] % heads == 0
            and q.shape[1] // heads <= 6
            and 4 * 4 * n_tiles * d * d <= VMEM_LIMIT - (8 << 20))


# -- the prompt -----------------------------------------------------------------

def _chunks(log_g, chunk: int):
    """log_g [b, H, L] (L whole chunks) -> (G [b, H, nc, chunk], its running
    sum inside a chunk; what a chunk's position keeps of itself at the
    chunk's end, exp(G_last - G); what the chunk keeps of the state entering
    it, exp(G_last) [b, H, nc])."""
    b, h, length = log_g.shape
    cs = jnp.cumsum(log_g.astype(jnp.float32).reshape(
        b, h, length // chunk, chunk), axis=-1)
    return cs, jnp.exp(cs[..., -1:] - cs), jnp.exp(cs[..., -1])


def _heads_first(t, length: int):
    """[b, L, H, ..] -> [b, H, length, ..], zeros past L."""
    t = jnp.moveaxis(t, 1, 2)
    pad = [(0, 0)] * t.ndim
    pad[2] = (0, length - t.shape[2])
    return jnp.pad(t, pad)


def power_retention_chunked_xla(q, k, v, log_g, chunk: int = CHUNK):
    """The chunked form in plain ``jax.numpy`` (a ``lax.scan`` over chunks,
    ``phi`` of one chunk alive at a time), on the kernel's layout: its oracle
    in the tests and the path off the TPU. Float32 throughout."""
    f32 = jnp.float32
    b, L, hq, d = q.shape
    hkv = k.shape[2]
    R, padded = hq // hkv, L + -L % chunk
    nc = padded // chunk
    cs, to_end, whole = _chunks(_heads_first(log_g, padded), chunk)

    def split(t):                       # [b, H, padded, ..] -> [nc, b, H, chunk, ..]
        return jnp.moveaxis(t.reshape(*t.shape[:2], nc, chunk, *t.shape[3:]),
                            2, 0)
    qs = split(_heads_first(q.astype(f32), padded).reshape(
        b, hkv, R, padded, d).swapaxes(2, 3))       # [nc, b, H, chunk, R, d]
    ks, vs = (split(_heads_first(t.astype(f32), padded)) for t in (k, v))
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(carry, xs):
        s, z = carry                    # [b, H, T, d, d], [b, H, T, d]
        qc, kc, vc, g, keep, all_ = xs
        seg = g[..., :, None] - g[..., None, :]                   # [b, H, t, j]
        decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
        score = jnp.einsum("bhtrd,bhjd->bhrtj", qc, kc) / math.sqrt(d)
        w = decay[:, :, None] * score * score
        pq, pk = phi(qc), phi(kc)       # [b, H, t, R, T, d], [b, H, j, T, d]
        e = jnp.exp(g)[:, :, None, :, None]                       # [b, H, 1, t, 1]
        num = (jnp.einsum("bhrtj,bhjv->bhrtv", w, vc)
               + e * jnp.einsum("bhtrcl,bhcvl->bhrtv", pq, s))
        den = (jnp.sum(w, -1)
               + e[..., 0] * jnp.einsum("bhtrcl,bhcl->bhrt", pq, z))
        s = (all_[..., None, None, None] * s
             + jnp.einsum("bhj,bhjv,bhjcl->bhcvl", keep, vc, pk))
        z = all_[..., None, None] * z + jnp.einsum("bhj,bhjcl->bhcl", keep, pk)
        return (s, z), num / (den[..., None] + EPS)

    zero = (jnp.zeros((b, hkv, tiles(d), d, d), f32),
            jnp.zeros((b, hkv, tiles(d), d), f32))       # z without its padding
    (s, z), y = jax.lax.scan(step, zero, (
        qs, ks, vs, *(jnp.moveaxis(t, 2, 0) for t in (cs, to_end, whole))))
    # [nc, b, H, R, chunk, d] -> [b, L, Hq, d]
    y = jnp.moveaxis(y, 0, 3).reshape(b, hq, padded, d)
    return jnp.moveaxis(y, 1, 2)[:, :L], s, _whole_rows(z)


def _chunk_kernel(whole_ref, q_ref, k_ref, v_ref, g_ref, keep_ref, y_ref,
                  s_ref, z_ref, acc_ref, den_ref, *, group, mx):
    # whole_ref [b Hkv nc] (SMEM); q_ref, y_ref [1, 1, C, R d]; k_ref, v_ref
    # [1, 1, C, d]; g_ref, keep_ref [1, 1, 1, 1, C]; s_ref [1, 1, T, d, d] and
    # z_ref [1, 1, T, 8, d] (a row on all 8 sublanes): the OUTPUT blocks,
    # resident over the chunks of a head; acc_ref, den_ref [R C, d]
    f32 = jnp.float32
    c = pl.program_id(2)
    n_tiles, _, d = z_ref.shape[2:]
    chunk = k_ref.shape[2]
    highest = jax.lax.Precision.HIGHEST if mx == f32 else None
    nt = (((1,), (1,)), ((), ()))

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    whole = whole_ref[(pl.program_id(0) * pl.num_programs(1)
                       + pl.program_id(1)) * pl.num_programs(2) + c]
    q5 = jnp.concatenate([q_ref[0, 0, :, n * d:(n + 1) * d]
                          for n in range(group)], axis=0).astype(f32)
    kk, vv = k_ref[0, 0].astype(f32), v_ref[0, 0].astype(f32)
    g_j = jnp.broadcast_to(g_ref[0, 0, 0], (chunk, chunk))     # [t, j] = G_j
    g_t = g_j.T                                                # [t, j] = G_t
    keep = jnp.broadcast_to(keep_ref[0, 0, 0], (chunk, chunk)).T
    # what the chunk's rows add to the state by its end: (v keep)^T phi(k)
    v_keep_t = (vv * keep).T.astype(mx)                        # [d (v), C]
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, d), 1)
    lane5 = jnp.concatenate([lane] * group, axis=0)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    den_ref[...] = jnp.zeros_like(den_ref)

    def tile(t, _):
        pq, pk = _phi_tile(q5, t, lane5), _phi_tile(kk, t, lane)
        s = s_ref[0, 0, t]                                     # [d (v), d (l)]
        zed = z_ref[0, 0, t]                                   # [8, d]
        acc_ref[...] += jax.lax.dot_general(
            pq.astype(mx), s.astype(mx), nt, preferred_element_type=f32,
            precision=highest)
        den_ref[...] += pq * zed[:1]
        s_ref[0, 0, t] = whole * s + jnp.dot(
            v_keep_t, pk.astype(mx), preferred_element_type=f32,
            precision=highest)
        z_ref[0, 0, t] = whole * zed + jnp.sum(pk * keep, axis=0,
                                               keepdims=True)
        return 0

    jax.lax.fori_loop(0, n_tiles, tile, 0)
    # inside the chunk: the decay's differences, masked before the exp
    at = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    to = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(at >= to, g_t - g_j, -jnp.inf))
    score = jax.lax.dot_general(
        q5.astype(mx), kk.astype(mx), nt, preferred_element_type=f32,
        precision=highest) / math.sqrt(d)
    w = jnp.concatenate([decay] * group, axis=0) * score * score
    e_t = jnp.exp(jnp.concatenate([g_t] * group, axis=0))      # [R C, d]
    num = e_t * acc_ref[...] + jnp.dot(
        w.astype(mx), vv.astype(mx), preferred_element_type=f32,
        precision=highest)
    den = (jnp.sum(w, axis=1, keepdims=True)
           + e_t[:, :1] * jnp.sum(den_ref[...], axis=1, keepdims=True) + EPS)
    y = num / den
    for n in range(group):
        y_ref[0, 0, :, n * d:(n + 1) * d] = y[n * chunk:(n + 1) * chunk].astype(
            y_ref.dtype)


def power_retention_chunked(q, k, v, log_g, chunk: int = CHUNK,
                            interpret: bool = False):
    """Whole sequences through power retention from a zero state, in chunks.

    q:     [b, L, Hq, d]; k, v: [b, L, Hkv, d] — k ZERO where a position must
           leave the state as it is (a bucket's padding)
    log_g: [b, L, Hkv] float32 — 0 at such a position

    Returns (y [b, L, Hq, d] float32, S [b, Hkv, T, d, d] and z [b, Hkv,
    z_rows(d), d] float32 after position L - 1). ``L`` is padded to whole chunks with such
    positions. The kernel takes ``chunk`` = d = 128 alone.
    """
    b, L, hq, d = q.shape
    hkv = k.shape[2]
    R, padded = hq // hkv, L + -L % chunk
    nc, T, f32 = padded // chunk, tiles(d), jnp.float32
    mx = jnp.bfloat16 if q.dtype == jnp.bfloat16 else f32
    cs, to_end, whole = _chunks(_heads_first(log_g, padded), chunk)
    # a KV head's R query heads side by side on the lanes: [b, Hkv, L, R d]
    q_in = jnp.moveaxis(_heads_first(q, padded).reshape(
        b, hkv, R, padded, d), 2, 3).reshape(b, hkv, padded, R * d)
    wide = pl.BlockSpec((1, 1, chunk, R * d), lambda i, h, c, w: (i, h, c, 0))
    one = pl.BlockSpec((1, 1, chunk, d), lambda i, h, c, w: (i, h, c, 0))
    line = pl.BlockSpec((1, 1, 1, 1, chunk), lambda i, h, c, w: (i, h, c, 0, 0))
    y, s, z = pl.pallas_call(
        functools.partial(_chunk_kernel, group=R, mx=mx),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, hkv, nc),
            in_specs=[wide, one, one, line, line],
            out_specs=[wide,
                       pl.BlockSpec((1, 1, T, d, d),
                                    lambda i, h, c, w: (i, h, 0, 0, 0)),
                       pl.BlockSpec((1, 1, T, 8, d),
                                    lambda i, h, c, w: (i, h, 0, 0, 0))],
            scratch_shapes=[pltpu.VMEM((R * chunk, d), f32),
                            pltpu.VMEM((R * chunk, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, padded, R * d), f32),
                   jax.ShapeDtypeStruct((b, hkv, T, d, d), f32),
                   jax.ShapeDtypeStruct((b, hkv, T, 8, d), f32)],
        compiler_params=(None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT)),
        interpret=interpret,
        name="power_retention_chunked",
    )(whole.reshape(-1), q_in, _heads_first(k, padded),
      _heads_first(v, padded), cs[:, :, :, None], to_end[:, :, :, None])
    y = jnp.moveaxis(y.reshape(b, hkv, padded, R, d), 2, 1)
    return y.reshape(b, padded, hq, d)[:, :L], s, _whole_rows(z[:, :, :, 0])


def power_retention_chunked_supported(q, k, chunk: int = CHUNK) -> bool:
    """Tiles and chunks of exactly 128, whole groups of query heads."""
    from ..registry import pallas_disabled
    d = q.shape[-1]
    return (_HAS_PLTPU and not pallas_disabled() and d == LANES
            and chunk == LANES and q.shape[2] % k.shape[2] == 0)


__all__ = ["phi", "tiles", "z_rows", "power_state_update", "power_state_update_xla",
           "power_state_update_supported", "power_retention_chunked",
           "power_retention_chunked_xla", "power_retention_chunked_supported"]
