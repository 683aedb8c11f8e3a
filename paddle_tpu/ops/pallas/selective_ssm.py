"""Pallas TPU kernels: a Mamba-1 (selective state-space) layer's recurrence,
one token of every sequence (``selective_state_update``: a decode tick, with
the elementwise work around it, and ``conv_window_step``, the convolution
window's step in front of it) and a whole prompt (``selective_scan``).

A Mamba-1 layer (arXiv:2312.00752; Jamba's mixer) carries, for every sequence,
a state ``h`` [D, N] (channels x state size) in float32. One token moves it by

    h'[c, n] = exp(Delta[c] A[c, n]) h[c, n] + Delta[c] x[c] B[n]
    y[c]     = sum_n h'[c, n] C[n]

with ``A`` [D, N] < 0 a parameter: EVERY element of the state decays at a rate
of its own, where a Mamba-2 head has one scalar (``ssm.py``). So nothing here
is a matrix product: the decay is ``D N`` exponentials a token, and the
prompt's form has no chunked (SSD) factorisation. In a prompt the skip
``D x``, the gate and everything around the recurrence (projections,
convolution, the inner norms, the softplus) are the caller's. In a tick what
is elementwise a channel is the kernels': ``conv_window_step`` shifts a
slot's window in place and returns ``silu(conv)``, ``selective_state_update``
takes the RAW step and returns the gated row (``softplus``, ``Delta x``,
``+ D x``, ``silu(z)`` inside, in float32, one cast at the end); the two
small products between them (``x_proj``, ``dt_proj``) and the inner norms of
a few dozen numbers a slot stay the caller's (XLA's).

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
WINDOW_SLOTS = 32   # slots a grid step of the window's step, where they divide
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

def _update_kernel(a_ref, bias_ref, skip_ref, x_ref, step_ref, z_ref, b_ref,
                   c_ref, h_ref, out_ref, h_out_ref, *, chunk):
    # a_ref [N, D]; bias_ref, skip_ref [1, D]; x_ref, z_ref, out_ref [S, D]
    # (the activation dtype); step_ref [S, D] float32; b_ref, c_ref
    # [1, N, S]; h_ref, h_out_ref [S, N, D]
    slots, _, d = h_ref.shape
    f32 = jnp.float32
    b_cols, c_cols = b_ref[0], c_ref[0]
    sublane = jax.lax.broadcasted_iota(jnp.int32, (slots, chunk), 0)
    for lo in range(0, d, chunk):
        at = slice(lo, lo + chunk)
        # what is one number a channel, for all the step's slots at once
        a, x = a_ref[:, at], x_ref[:, at].astype(f32)
        dt = jax.nn.softplus(step_ref[:, at] + bias_ref[:, at])
        dx = dt * x
        y = jnp.zeros((slots, chunk), f32)
        for i in range(slots):
            h = (jnp.exp(dt[i:i + 1] * a) * h_ref[i, :, at]
                 + dx[i:i + 1] * b_cols[:, i:i + 1])
            h_out_ref[i, :, at] = h
            y = jnp.where(sublane == i, jnp.sum(
                h * c_cols[:, i:i + 1], axis=0, keepdims=True), y)
        out_ref[:, at] = ((y + skip_ref[:, at] * x) * jax.nn.silu(
            z_ref[:, at].astype(f32))).astype(out_ref.dtype)


def _slots_a_step(slots: int) -> int:
    return SLOTS if slots % SLOTS == 0 else slots


def selective_state_update(state, x, step, dt_bias, a_t, b_mat, c_mat, skip,
                           gate, interpret: bool = False):
    """One token of every sequence through its Mamba-1 state, in place, from
    the layer's raw quantities to the gated row.

    state: [B, N, D] float32 — updated IN PLACE (the second result is the
           same buffer where the caller donates it)
    x:     [B, D] — the token's input a channel (after its convolution)
    step:  [B, D] float32 — the step BEFORE its bias and softplus
    dt_bias, skip: [D] float32 — the step's bias and ``D``
    a_t:   [N, D] float32 — ``-exp(A_log)``, in the state's layout
    b_mat, c_mat: [B, N]
    gate:  [B, D] or wider — ``z``: its LAST D columns (the caller hands
           ``in_proj``'s whole product [x | z] over and a block index picks
           the half: no slice is written)

    Returns (``(h' C + D x) silu(z)`` [B, D] in x's dtype, h' [B, N, D]),
    ``h'`` moved by ``Delta = softplus(step + dt_bias)``; float32 inside,
    one cast at the end.
    """
    B, N, D = state.shape
    S = _slots_a_step(B)
    f32 = jnp.float32
    vec = pl.BlockSpec((1, D), lambda i: (0, 0))
    row = pl.BlockSpec((S, D), lambda i: (i, 0))
    last = gate.shape[1] // D - 1
    col = pl.BlockSpec((1, N, S), lambda i: (i, 0, 0))
    tile = pl.BlockSpec((S, N, D), lambda i: (i, 0, 0))
    out, new = pl.pallas_call(
        functools.partial(_update_kernel, chunk=_lane_chunk(D)),
        grid=(B // S,),
        in_specs=[pl.BlockSpec((N, D), lambda i: (0, 0)), vec, vec, row, row,
                  pl.BlockSpec((S, D), lambda i: (i, last)), col, col, tile],
        out_specs=[row, tile],
        out_shape=[jax.ShapeDtypeStruct((B, D), x.dtype),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={8: 1},
        compiler_params=_params(interpret, "parallel"),
        interpret=interpret,
        name="selective_state_update",
    )(a_t, dt_bias.astype(f32)[None], skip.astype(f32)[None], x,
      step.astype(f32), gate, _columns(b_mat, S), _columns(c_mat, S), state)
    return out, new


def selective_recurrence_xla(state, x, dt, a_t, b_mat, c_mat):
    """The recurrence alone in plain ``jax.numpy``, on the kernels' layout:
    ``dt`` [B, D] is the step AFTER its softplus; (y [B, D] float32 = h' C,
    h' [B, N, D])."""
    dt = dt.astype(jnp.float32)[:, None, :]                       # [B, 1, D]
    new = (jnp.exp(dt * a_t) * state
           + dt * x.astype(jnp.float32)[:, None, :]
           * b_mat.astype(jnp.float32)[..., None])
    return jnp.sum(new * c_mat.astype(jnp.float32)[..., None], axis=1), new


def selective_state_update_xla(state, x, step, dt_bias, a_t, b_mat, c_mat,
                               skip, gate):
    """The update kernel's mathematics in plain ``jax.numpy``: its oracle in
    the tests and the path off the TPU."""
    f32 = jnp.float32
    xf, z = x.astype(f32), gate[:, -x.shape[1]:].astype(f32)
    y, new = selective_recurrence_xla(
        state, x, jax.nn.softplus(step.astype(f32) + dt_bias), a_t, b_mat,
        c_mat)
    return ((y + skip * xf) * jax.nn.silu(z)).astype(x.dtype), new


def _tiles(n: int, d: int) -> bool:
    from ..registry import pallas_disabled
    return (_HAS_PLTPU and not pallas_disabled() and n % 8 == 0
            and d % LANES == 0)


def selective_state_update_supported(state) -> bool:
    """Mosaic's rules for this layout: whole (8, 128) float32 tiles, whole
    steps of ``SLOTS`` slots (or all of them in one), and a step's blocks
    (the state in and out, ``A`` and four rows of at most float32, each
    double-buffered) within the VMEM asked for."""
    slots, n, d = state.shape
    s = _slots_a_step(slots)
    return (state.dtype == jnp.float32 and _tiles(n, d) and s <= SLOTS
            and 8 * d * ((2 * s + 1) * n + 4 * s) <= VMEM_LIMIT // 2)


def _window_kernel(w_ref, bias_ref, x_ref, win_ref, y_ref, win_out_ref, *,
                   chunk):
    # w_ref [K, D]; bias_ref [1, D]; x_ref, y_ref [S, D]; win_ref,
    # win_out_ref [K - 1, S, D]: a tap's rows are a plane of their own
    f32 = jnp.float32
    taps, _, d = win_ref.shape
    for lo in range(0, d, chunk):
        at = slice(lo, lo + chunk)
        rows = [win_ref[i, :, at] for i in range(taps)] + [
            x_ref[:, at].astype(win_ref.dtype)]
        out = bias_ref[:, at] + sum(r.astype(f32) * w_ref[i:i + 1, at]
                                    for i, r in enumerate(rows))
        y_ref[:, at] = jax.nn.silu(out).astype(y_ref.dtype)
        for i in range(taps):
            win_out_ref[i, :, at] = rows[i + 1]


def _sublanes(dtype) -> int:
    """Rows of a sublane tile: 8 of float32, 16 of bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def _window_slots(slots: int, dtype) -> int:
    """Slots a grid step of the window's step: whole sublane tiles of the
    window's dtype, as many as ``WINDOW_SLOTS`` where they divide the slots;
    else all of them."""
    tile = _sublanes(dtype)
    return next((s for s in (WINDOW_SLOTS, WINDOW_SLOTS // 2, tile)
                 if s % tile == 0 and slots % s == 0), slots)


def conv_window_step(window, x, weight, bias, interpret: bool = False):
    """One token of every sequence through its depthwise causal convolution:
    the window of the last K - 1 inputs steps IN PLACE and the convolution's
    output comes back through its SiLU.

    window: [B, K - 1, D] — oldest first; updated IN PLACE (the second
            result is the same buffer where the caller donates it)
    x:      [B, D] or wider — the token's input: its FIRST D columns (the
            caller hands ``in_proj``'s whole product [x | z] over)
    weight: [K, D] float32, the last row the token's own; bias: [D]

    Returns (silu(bias + sum_i tap_i weight_i) [B, D] in the window's dtype,
    the window one token on). The kernel sees the window as [K - 1, B, D]:
    that is how the chip lays a [B, 3, D] leaf out (the short dimension
    outermost, slots on the sublanes), so the swap is a bitcast there and a
    tap is whole tiles.
    """
    B, taps, D = window.shape
    S = _window_slots(B, window.dtype)
    f32 = jnp.float32
    vec = lambda k: pl.BlockSpec((k, D), lambda i: (0, 0))
    row = pl.BlockSpec((S, D), lambda i: (i, 0))
    plane = pl.BlockSpec((taps, S, D), lambda i: (0, i, 0))
    y, new = pl.pallas_call(
        functools.partial(_window_kernel, chunk=_lane_chunk(D)),
        grid=(B // S,),
        in_specs=[vec(taps + 1), vec(1), row, plane],
        out_specs=[row, plane],
        out_shape=[jax.ShapeDtypeStruct((B, D), window.dtype),
                   jax.ShapeDtypeStruct((taps, B, D), window.dtype)],
        input_output_aliases={3: 1},
        compiler_params=_params(interpret, "parallel"),
        interpret=interpret,
        name="conv_window_step",
    )(weight.astype(f32), bias.astype(f32)[None], x,
      jnp.swapaxes(window, 0, 1))
    return y, jnp.swapaxes(new, 0, 1)


def conv_window_step_xla(window, x, weight, bias):
    """The window kernel's mathematics in plain ``jax.numpy``: its oracle in
    the tests and the path where the kernel does not run."""
    x = x[:, :window.shape[2]].astype(window.dtype)
    rows = [window[:, i] for i in range(window.shape[1])] + [x]
    out = bias + sum(r.astype(jnp.float32) * weight[i]
                     for i, r in enumerate(rows))
    return (jax.nn.silu(out).astype(window.dtype),
            jnp.concatenate([window[:, 1:], x[:, None]], axis=1))


def conv_window_step_supported(window) -> bool:
    """Whole sublane tiles of slots in the window's dtype, whole lane tiles
    of channels, and a step's blocks (the window in and out, the two rows,
    each double-buffered) within the VMEM asked for."""
    from ..registry import pallas_disabled
    slots, taps, d = window.shape
    item = jnp.dtype(window.dtype).itemsize
    s = _window_slots(slots, window.dtype)
    return (_HAS_PLTPU and not pallas_disabled() and d % LANES == 0
            and s % _sublanes(window.dtype) == 0
            and 4 * (taps + 1) * s * d * item <= VMEM_LIMIT // 2)


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
        y, h = selective_recurrence_xla(h, *xs[:2], a_t, *xs[2:])
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
           "selective_state_update_supported", "selective_recurrence_xla",
           "conv_window_step", "conv_window_step_xla",
           "conv_window_step_supported", "selective_scan",
           "selective_scan_xla", "selective_scan_supported"]
