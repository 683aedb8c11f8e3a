"""Pallas TPU kernels: a Mamba-1 (selective state-space) layer's recurrence,
one token of every sequence (``selective_state_update``: a decode tick) and a
whole prompt (``selective_scan``).

A Mamba-1 layer (arXiv:2312.00752; Jamba's mixer) carries, for every sequence,
a state ``h`` [D, N] (channels x state size) in float32. One token moves it by

    h'[c, n] = exp(Delta[c] A[c, n]) h[c, n] + Delta[c] x[c] B[n]
    y[c]     = sum_n h'[c, n] C[n]

with ``A`` [D, N] < 0 a parameter: EVERY element of the state decays at a rate
of its own, where a Mamba-2 head has one scalar (``ssm.py``). So nothing here
is a matrix product: the decay is ``D N`` exponentials a token, and the
prompt's form has no chunked (SSD) factorisation. The skip ``D x``, the gate
and everything around the recurrence (projections, convolution, the inner
norms, the softplus) are the caller's.

**The layout** is ``[.., N, D]``: the state index on the sublanes (N = 16:
two float32 tiles), channels on the lanes. What multiplies a COLUMN of it
(``Delta`` and ``Delta x``, one number a channel) is then a row broadcast
down the sublanes, the reading against ``C`` sums over sublanes (one
whole-register add and one fold a lane tile) and is already the row of
``y``; only ``B`` and ``C`` (N numbers a token) are spread along the lanes,
once a token. ``A`` is kept in the same layout (``[N, D]``: the parameter is
stored so) and stays resident in VMEM across the grid.

``selective_state_update`` sweeps the slots' state once, in place
(``input_output_aliases``), ``SLOTS`` slots a grid step. ``selective_scan``
runs TIME INSIDE the kernel: the grid walks blocks of ``T`` positions in
order, the whole state [N, D] (320 KB at Jamba's widths) stays in a VMEM
scratch from block to block, and only ``x``, ``Delta`` and ``y`` stream: the
[L, D, N] products an associative scan would write to HBM never exist.
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
SLOTS = 8           # slots a grid step of the update (a float32 sublane tile)
STEPS = 8           # positions of the scan unrolled between two loop tests
TIME_BLOCK = 64     # positions a grid step of the scan, where they divide L
VMEM_LIMIT = 40 << 20


def _lane_chunk(d: int) -> int:
    """Lanes of the state worked on at once: [N, chunk] float32 values stay
    in registers (16 x 512: 8 of the 64)."""
    return next(c for c in (512, 256, LANES) if d % c == 0)


def _columns(m, block: int):
    """[.., L, N] -> [.., L / block, N, block]: a block's B (or C) with the
    state index on the sublanes, so that one position's is a column."""
    *lead, length, n = m.shape
    m = m.astype(jnp.float32).reshape(*lead, length // block, block, n)
    return jnp.swapaxes(m, -1, -2)


def _params(interpret: bool, *semantics):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


# -- the tick -------------------------------------------------------------------

def _update_kernel(a_ref, dt_ref, dx_ref, b_ref, c_ref, h_ref, y_ref,
                   h_out_ref, *, chunk):
    # a_ref [N, D]; dt_ref, dx_ref, y_ref [S, D]; b_ref, c_ref [1, N, S];
    # h_ref, h_out_ref [S, N, D]
    slots, _, d = h_ref.shape
    b_cols, c_cols = b_ref[0], c_ref[0]
    for i in range(slots):
        b_i, c_i = b_cols[:, i:i + 1], c_cols[:, i:i + 1]         # [N, 1]
        for lo in range(0, d, chunk):
            at = slice(lo, lo + chunk)
            h = (jnp.exp(dt_ref[i:i + 1, at] * a_ref[:, at]) * h_ref[i, :, at]
                 + dx_ref[i:i + 1, at] * b_i)
            h_out_ref[i, :, at] = h
            y_ref[i:i + 1, at] = jnp.sum(h * c_i, axis=0, keepdims=True)


def _slots_a_step(slots: int) -> int:
    return SLOTS if slots % SLOTS == 0 else slots


def selective_state_update(state, x, dt, a_t, b_mat, c_mat,
                           interpret: bool = False):
    """One token of every sequence through its Mamba-1 state, in place.

    state: [B, N, D] float32 — updated IN PLACE (the second result is the
           same buffer where the caller donates it)
    x:     [B, D] — the token's input a channel (after its convolution)
    dt:    [B, D] float32 — the step, after its softplus
    a_t:   [N, D] float32 — ``-exp(A_log)``, in the state's layout
    b_mat, c_mat: [B, N]

    Returns (y [B, D] float32 = h' C, h' [B, N, D]).
    """
    B, N, D = state.shape
    S = _slots_a_step(B)
    dt = dt.astype(jnp.float32)
    row = pl.BlockSpec((S, D), lambda i: (i, 0))
    col = pl.BlockSpec((1, N, S), lambda i: (i, 0, 0))
    tile = pl.BlockSpec((S, N, D), lambda i: (i, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_update_kernel, chunk=_lane_chunk(D)),
        grid=(B // S,),
        in_specs=[pl.BlockSpec((N, D), lambda i: (0, 0)), row, row, col, col,
                  tile],
        out_specs=[row, tile],
        out_shape=[jax.ShapeDtypeStruct((B, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=_params(interpret, "parallel"),
        interpret=interpret,
        name="selective_state_update",
    )(a_t, dt, dt * x.astype(jnp.float32), _columns(b_mat, S),
      _columns(c_mat, S), state)
    return y, new


def selective_state_update_xla(state, x, dt, a_t, b_mat, c_mat):
    """The kernel's mathematics in plain ``jax.numpy``, on the same layout:
    its oracle in the tests and the path off the TPU."""
    dt = dt.astype(jnp.float32)[:, None, :]                       # [B, 1, D]
    new = (jnp.exp(dt * a_t) * state
           + dt * x.astype(jnp.float32)[:, None, :]
           * b_mat.astype(jnp.float32)[..., None])
    return jnp.sum(new * c_mat.astype(jnp.float32)[..., None], axis=1), new


def _tiles(n: int, d: int) -> bool:
    from ..registry import pallas_disabled
    return (_HAS_PLTPU and not pallas_disabled() and n % 8 == 0
            and d % LANES == 0)


def selective_state_update_supported(state) -> bool:
    """Mosaic's rules for this layout: whole (8, 128) float32 tiles, whole
    steps of ``SLOTS`` slots (or all of them in one), and a step's blocks
    (double-buffered, in and out) within the VMEM asked for."""
    slots, n, d = state.shape
    s = _slots_a_step(slots)
    return (state.dtype == jnp.float32 and _tiles(n, d) and s <= SLOTS
            and 4 * (s + 1) * n * d * 4 <= VMEM_LIMIT // 2)


# -- the prompt -----------------------------------------------------------------

def _scan_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, h_out_ref, h_ref,
                 dx_ref, *, chunk):
    # a_ref [N, D]; x_ref, dt_ref, y_ref [1, T, D]; b_ref, c_ref
    # [1, T / STEPS, N, STEPS]; h_out_ref [1, N, D]; scratch: h_ref [N, D]
    # (the state, from block to block), dx_ref [T, D] (Delta x)
    block = pl.program_id(1)

    @pl.when(block == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    steps, d = x_ref.shape[1:]
    dx_ref[...] = dt_ref[0] * x_ref[0].astype(jnp.float32)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (STEPS, chunk), 0)
    for lo in range(0, d, chunk):
        at = slice(lo, lo + chunk)
        a = a_ref[:, at]

        def group(g, h, at=at, a=a):
            rows = pl.ds(pl.multiple_of(g * STEPS, STEPS), STEPS)
            dt, dx = dt_ref[0, rows, at], dx_ref[rows, at]    # [STEPS, chunk]
            b_cols, c_cols = b_ref[0, g], c_ref[0, g]         # [N, STEPS]
            y = jnp.zeros((STEPS, chunk), jnp.float32)
            for j in range(STEPS):
                h = (jnp.exp(dt[j:j + 1] * a) * h
                     + dx[j:j + 1] * b_cols[:, j:j + 1])
                y = jnp.where(sublane == j, jnp.sum(
                    h * c_cols[:, j:j + 1], axis=0, keepdims=True), y)
            y_ref[0, rows, at] = y
            return h

        h_ref[:, at] = jax.lax.fori_loop(0, steps // STEPS, group,
                                         h_ref[:, at])

    @pl.when(block == pl.num_programs(1) - 1)
    def _():
        h_out_ref[0] = h_ref[...]


def _time(length: int):
    """(positions the kernel walks, positions a grid step): whole bfloat16
    sublane tiles of time (16), in blocks of ``TIME_BLOCK`` where they
    divide them."""
    padded = length + -length % 16
    return padded, TIME_BLOCK if padded % TIME_BLOCK == 0 else padded


def selective_scan(x, dt, a_t, b_mat, c_mat, interpret: bool = False):
    """Whole sequences through the Mamba-1 recurrence from a zero state.

    x:   [b, L, D] — the inputs a channel (after their convolution)
    dt:  [b, L, D] float32 — the steps, after their softplus; 0 where a
         position must leave the state as it is (a bucket's padding)
    a_t: [N, D] float32
    b_mat, c_mat: [b, L, N]

    Returns (y [b, L, D] float32 = h_t C_t, h_L [b, N, D] float32). ``L`` is
    padded to whole tiles of time with steps of 0, which move nothing.
    """
    b, L, D = x.shape
    N = a_t.shape[0]
    padded, T = _time(L)
    x, dt, b_mat, c_mat = (jnp.pad(t, ((0, 0), (0, padded - L), (0, 0)))
                           for t in (x, dt.astype(jnp.float32), b_mat, c_mat))
    seq = pl.BlockSpec((1, T, D), lambda i, t: (i, t, 0))
    col = pl.BlockSpec((1, T // STEPS, N, STEPS), lambda i, t: (i, t, 0, 0))
    y, last = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=_lane_chunk(D)),
        grid=(b, padded // T),
        in_specs=[pl.BlockSpec((N, D), lambda i, t: (0, 0)), seq, seq, col,
                  col],
        out_specs=[seq, pl.BlockSpec((1, N, D), lambda i, t: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, padded, D), jnp.float32),
                   jax.ShapeDtypeStruct((b, N, D), jnp.float32)],
        scratch_shapes=([pltpu.VMEM((N, D), jnp.float32),
                         pltpu.VMEM((T, D), jnp.float32)]
                        if _HAS_PLTPU else []),
        compiler_params=_params(interpret, "parallel", "arbitrary"),
        interpret=interpret,
        name="selective_scan",
    )(a_t, x, dt, _columns(b_mat, STEPS), _columns(c_mat, STEPS))
    return y[:, :L], last


def selective_scan_xla(x, dt, a_t, b_mat, c_mat):
    """The same recurrence as XLA runs it: ``L`` sequential steps of the
    tick's update in a ``lax.scan``. The kernel's oracle in the tests, the
    path off the TPU, and what ``tools/tune_kernels.py --selective-scan``
    times the kernel against."""
    def step(h, xs):
        y, h = selective_state_update_xla(h, *xs[:2], a_t, *xs[2:])
        return h, y

    zero = jnp.zeros((x.shape[0],) + a_t.shape, jnp.float32)
    last, y = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b_mat, c_mat)))
    return jnp.moveaxis(y, 0, 1), last


def selective_scan_supported(x, n: int) -> bool:
    """Whole tiles, and a block of time (x, Delta, y double-buffered, Delta
    x once) within the VMEM asked for."""
    _, length, d = x.shape
    t = _time(length)[1]
    return (_tiles(n, d)
            and t * d * (2 * (x.dtype.itemsize + 8) + 4) <= VMEM_LIMIT // 2)


__all__ = ["selective_state_update", "selective_state_update_xla",
           "selective_state_update_supported", "selective_scan",
           "selective_scan_xla", "selective_scan_supported"]
