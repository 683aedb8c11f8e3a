"""Pallas TPU kernel: one decode step of a Mamba-2 (SSD) layer's state.

A Mamba-2 layer (arXiv:2405.21060) carries, for every sequence and head, a
state ``S`` [P, N] (head size x state size) in float32. One new token moves
it by

    S' = exp(dt A) S + (dt x) (x) B          y = S' C

with a scalar decay a head (``dt`` [H] after its softplus, ``A`` [H] < 0),
the token's input ``x`` [H, P] and, shared by the ``H / G`` heads of a group,
``B`` and ``C`` [G, N]. The skip term ``D x`` and everything around the
recurrence (projections, convolution, gate, norm) are the caller's.

At serving sizes the state is the traffic: Nemotron-3-Nano's 64 heads of
[64, 128] float32 are 2 MB a sequence a layer, and a decode tick reads and
rewrites every slot's. ``ssm_state_update`` makes that ONE pass: a grid step
takes one sequence's state block, forms ``S'`` tile by tile in registers,
reduces it against ``C`` for ``y`` in the same sweep and writes it back to
the buffer it came from (``input_output_aliases``), so the state crosses HBM
once each way and no second copy of it is ever alive.

**The state's layout** (``pack_state``) is chosen so that nothing in the
sweep crosses lanes a head: a tile is ``[N, r P]``, the state size on the
sublanes and ``r = 128 / P`` heads of one group side by side on the lanes
(Nemotron: [128, 2 x 64]). What multiplies a COLUMN of it (``exp(dt A)`` and
``dt x``, one number a head and p) is then a row ``[1, r P]`` broadcast down
the sublanes, which costs nothing; the reading ``S' C`` sums over sublanes
(whole-register adds and one fold a tile), and its result is already the row
of ``y``. Only ``B`` and ``C`` (one number an n) must be spread along the
lanes, ONCE A GROUP, not once a head. With the state laid out [P, N] a head
instead, every head pays two lane-broadcasts and a lane-reduction a register
(32 crossings of 8 a head against 4).
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


def heads_per_tile(p: int, per_group: int) -> int:
    """Heads side by side on a tile's lanes: as many of ONE group as fill
    ``LANES`` (1 for a head size of ``LANES`` or more)."""
    return max((r for r in range(1, per_group + 1)
                if per_group % r == 0 and r * p <= LANES), default=1)


def pack_state(state, groups: int):
    """[.., H, P, N] (a head's state as the recurrence writes it) ->
    [.., H / r, N, r P], the layout the slots keep."""
    *lead, h, p, n = state.shape
    r = heads_per_tile(p, h // groups)
    tiles = state.reshape(*lead, h // r, r, p, n)
    return jnp.moveaxis(tiles, -1, -3).reshape(*lead, h // r, n, r * p)


def unpack_state(packed, heads: int):
    """The inverse of ``pack_state``: [.., H / r, N, r P] -> [.., H, P, N]."""
    *lead, tiles, n, lanes = packed.shape
    r = heads // tiles
    state = packed.reshape(*lead, tiles, n, r, lanes // r)
    return jnp.moveaxis(state, -3, -1).reshape(*lead, heads, lanes // r, n)


def _rows(x, dt, a, tiles: int):
    """What multiplies a tile's columns, as rows [B, tiles, r P] float32:
    the decay ``exp(dt A)`` (a head's, repeated over its p) and ``dt x``."""
    b, h, p = x.shape
    dt = dt.astype(jnp.float32)
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], (b, h, p))
    xdt = x.astype(jnp.float32) * dt[..., None]
    return decay.reshape(b, tiles, -1), xdt.reshape(b, tiles, -1)


def _kernel(decay_ref, xdt_ref, b_ref, c_ref, s_ref, y_ref, s_out_ref, *,
            groups, tiles_per_group):
    # decay_ref, xdt_ref, y_ref: [1, tiles, r P]; b_ref, c_ref: [1, N, G];
    # s_ref: [1, tiles, N, r P]
    n, lanes = s_ref.shape[2], s_ref.shape[3]
    b_cols, c_cols = b_ref[0], c_ref[0]                       # [N, G]
    for g in range(groups):
        b_full = jnp.broadcast_to(b_cols[:, g:g + 1], (n, lanes))
        c_full = jnp.broadcast_to(c_cols[:, g:g + 1], (n, lanes))
        for t in range(g * tiles_per_group, (g + 1) * tiles_per_group):
            s = (s_ref[0, t] * decay_ref[0, t:t + 1, :]
                 + xdt_ref[0, t:t + 1, :] * b_full)           # [N, r P]
            s_out_ref[0, t] = s
            y_ref[0, t:t + 1, :] = jnp.sum(s * c_full, axis=0, keepdims=True)


def ssm_state_update(state, x, dt, a, b_mat, c_mat, interpret: bool = False):
    """One token of every sequence through its Mamba-2 state, in place.

    state: [B, H / r, N, r P] float32 (``pack_state``) — updated IN PLACE
           (the second result is the same buffer where the caller donates it)
    x:     [B, H, P] — the token's input a head
    dt:    [B, H] float32 — the step, after its softplus
    a:     [H] float32 — ``-exp(A_log)``, a scalar a head
    b_mat, c_mat: [B, G, N] — a group's B and C; head h uses group h // (H / G)

    Returns (y [B, H, P] float32 = S' C, S' in the state's layout).
    """
    B, tiles, N, lanes = state.shape
    H, P = x.shape[1:]
    G = b_mat.shape[1]
    decay, xdt = _rows(x, dt, a, tiles)
    row = pl.BlockSpec((1, tiles, lanes), lambda i: (i, 0, 0))
    col = pl.BlockSpec((1, N, G), lambda i: (i, 0, 0))
    tile = pl.BlockSpec((1, tiles, N, lanes), lambda i: (i, 0, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_kernel, groups=G, tiles_per_group=tiles // G),
        grid=(B,),
        in_specs=[row, row, col, col, tile],
        out_specs=[row, tile],
        out_shape=[jax.ShapeDtypeStruct((B, tiles, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={4: 1},
        # a slot's block in and out, double-buffered: four times its bytes
        compiler_params=(pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=24 << 20) if not interpret else None),
        interpret=interpret,
        name="ssm_state_update",
    )(decay, xdt, jnp.swapaxes(b_mat.astype(jnp.float32), 1, 2),
      jnp.swapaxes(c_mat.astype(jnp.float32), 1, 2), state)
    return y.reshape(B, H, P), new


def ssm_state_update_xla(state, x, dt, a, b_mat, c_mat):
    """The kernel's mathematics in plain ``jax.numpy``, on the same layout:
    its oracle in the tests and the path off the TPU."""
    B, tiles, N, lanes = state.shape
    G = b_mat.shape[1]
    decay, xdt = _rows(x, dt, a, tiles)
    per = tiles // G                    # a tile's group: tile // per
    b_t = jnp.repeat(b_mat.astype(jnp.float32), per, axis=1)  # [B, tiles, N]
    c_t = jnp.repeat(c_mat.astype(jnp.float32), per, axis=1)
    new = (state * decay[:, :, None, :]
           + xdt[:, :, None, :] * b_t[..., None])
    y = jnp.sum(new * c_t[..., None], axis=2)                 # [B, tiles, r P]
    return y.reshape(x.shape), new


def ssm_state_update_supported(state, b_mat) -> bool:
    """Mosaic's rules for this layout: whole (8, 128) float32 tiles, whole
    groups of tiles, and a slot's block (twice, double-buffered, in and
    out) within the VMEM asked for."""
    from ..registry import pallas_disabled
    if not _HAS_PLTPU or pallas_disabled():
        return False
    _, tiles, n, lanes = state.shape
    return (state.dtype == jnp.float32 and lanes % LANES == 0 and n % 8 == 0
            and tiles % b_mat.shape[1] == 0
            and 4 * tiles * n * lanes * 4 <= 20 << 20)


__all__ = ["ssm_state_update", "ssm_state_update_xla",
           "ssm_state_update_supported", "pack_state", "unpack_state"]
