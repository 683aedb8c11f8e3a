"""The gated delta rule (``ops/pallas/gated_delta.py``): three forms of one
function agree on the CPU in float32: the tick's ``jnp`` twin applied token
by token, the prompt's chunked (WY) form, and the benchmark reference's own
token-by-token loop (``benchmarks/refs/qwen3_next.py::delta_rule``, which
shares no code with the program); and the tick's kernel (interpret mode)
is its twin, in place.

Two kinds of gates: SEEDED (alpha ~ 0.5: the state forgets half of itself a
token, so what the chunks carry hardly shows) and NEAR ONE (log alpha ~
-1/400, beta ~ 0.98: nothing fades by itself, a write stays until later
keys overwrite it, so a fault in the chunk-to-chunk state or in the
triangular system moves the output by its own size).

Tolerances, and why: all forms are float32 sums of the same ~L x K products
in different orders. Readings of size ~1 agree to 2e-5 over 333 tokens
(measured 5e-7 to 1.3e-6: forty times of room for another BLAS); the state
(size ~4 with gates near one) to 4e-5.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.refs import qwen3_next as ref  # noqa: E402
from paddle_tpu.ops.pallas import gated_delta as gd  # noqa: E402

HK, HV, K, V = 2, 4, 16, 128
F32 = jnp.float32


def _inputs(length, gates, seed=0, b=1):
    """(q, k [b, L, Hk, K] of unit length, q times K^-1/2; v [b, L, Hv, V];
    log alpha, beta [b, L, Hv])."""
    rng = np.random.default_rng(seed)
    q = gd.l2_normalize(jnp.asarray(rng.normal(size=(b, length, HK, K)),
                                    F32)) * K ** -0.5
    k = gd.l2_normalize(jnp.asarray(rng.normal(size=(b, length, HK, K)), F32))
    v = jnp.asarray(rng.normal(size=(b, length, HV, V)), F32)
    scale, shift = (0.7, 0.0) if gates == "seeded" else (1 / 400, 4.0)
    log_alpha = -jnp.asarray(np.abs(rng.normal(size=(b, length, HV))) * scale,
                             F32)
    beta = jax.nn.sigmoid(jnp.asarray(
        rng.normal(size=(b, length, HV)) + shift, F32))
    return q, k, v, log_alpha, beta


def _tick_by_tick(q, k, v, log_alpha, beta):
    """The tick's twin over a whole sequence from a zero state."""
    def token(s, t):
        o, s = gd.gated_delta_state_update_xla(s, *t)
        return s, o
    s, o = jax.lax.scan(
        token, jnp.zeros((q.shape[0], HV, K, V), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, log_alpha, beta)))
    return jnp.moveaxis(o, 0, 1), s


# 333: five chunks of 64 and a ragged sixth; 320: whole chunks; 64: one
@pytest.mark.parametrize("length", [333, 320, 64])
@pytest.mark.parametrize("gates", ["seeded", "near_one"])
def test_the_three_forms_agree(gates, length):
    q, k, v, log_alpha, beta = _inputs(length, gates, seed=length)
    if gates == "near_one":
        assert float(log_alpha.min()) > -0.02 and float(beta.mean()) > 0.95
    o_tick, s_tick = jax.jit(_tick_by_tick)(q, k, v, log_alpha, beta)
    o_chunk, s_chunk = jax.jit(gd.gated_delta_chunked)(q, k, v, log_alpha, beta)
    rep = HV // HK
    with jax.default_matmul_precision("highest"):
        o_ref = ref.delta_rule(jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2),
                               v, jnp.exp(log_alpha), beta)
    assert float(jnp.abs(o_ref).max()) > 0.2
    assert float(jnp.abs(o_tick - o_ref).max()) < 2e-5
    assert float(jnp.abs(o_chunk - o_ref).max()) < 2e-5
    assert float(jnp.abs(s_chunk - s_tick).max()) < 4e-5
    if gates == "near_one" and length == 333:
        # what the variant is for: the last, ragged chunk's positions read
        # mostly what the chunks before it left (a state of K = 16 keys
        # holds the last few dozen writes, whatever alpha is)
        alone = jax.jit(_tick_by_tick)(
            *(t[:, 320:] for t in (q, k, v, log_alpha, beta)))
        assert float(jnp.abs(alone[0] - o_tick[:, 320:]).max()) > 0.3
        assert float(jnp.abs(alone[1] - s_tick).max()) > 0.3


def test_a_position_with_alpha_one_and_beta_zero_leaves_the_state_alone():
    """A bucket's padding: rows past the prompt's last position take log
    alpha = 0 and beta = 0, whatever their q, k and v, and the state after
    the padded sequence is the state after the prompt."""
    q, k, v, log_alpha, beta = _inputs(100, "near_one", seed=3)
    live = (jnp.arange(100) < 70)[None, :, None]
    padded = gd.gated_delta_chunked(q, k, v, jnp.where(live, log_alpha, 0.0),
                                    jnp.where(live, beta, 0.0))
    alone = gd.gated_delta_chunked(*(t[:, :70]
                                     for t in (q, k, v, log_alpha, beta)))
    assert float(jnp.abs(padded[1] - alone[1]).max()) < 1e-6
    assert float(jnp.abs(padded[0][:, :70] - alone[0]).max()) < 1e-6


def test_the_triangular_system_is_solved_where_powers_of_a_would_cancel():
    """``T = (I - A)^-1`` by substitution. With identical keys and gates at
    one, ``A`` is minus the all-ones strict lower triangle and ``T`` is the
    bidiagonal (1 on the diagonal, -1 under it); the doublings ``(I + A)(I +
    A^2) ..`` reach it through binomials up to C(62, 31) ~ 4.5e17, which
    float32 cannot cancel. Also a random ``A`` against numpy's inverse in
    float64."""
    c = 64
    a = -jnp.tril(jnp.ones((c, c), F32), -1)
    want = np.eye(c) - np.eye(c, k=-1)
    assert np.abs(np.asarray(gd._unit_lower_inverse(a)) - want).max() < 1e-6
    t, m = jnp.eye(c) + a, a
    for _ in range(5):
        m = m @ m
        t = t + t @ m
    assert not np.abs(np.asarray(t) - want).max() < 1.0     # the doublings
    rng = np.random.default_rng(0)
    a = np.tril(rng.normal(size=(3, 2, c, c)).astype(np.float32) * 0.3, -1)
    want = np.linalg.inv(np.eye(c) - a.astype(np.float64))
    got = np.asarray(gd._unit_lower_inverse(jnp.asarray(a)))
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_the_tick_kernel_is_its_twin_in_place():
    """Interpret mode, 3 slots of 8 value heads on 4 key heads (two value
    heads a key head, EVERY head of a slot in one grid step): the reading
    and the new state are the twin's to float32 rounding (the sums run in
    another order), and the state's operand is aliased to its result."""
    rng = np.random.default_rng(1)
    b, hk, hv = 3, 4, 8
    state = jnp.asarray(rng.normal(size=(b, hv, K, V)), F32)
    q = gd.l2_normalize(jnp.asarray(rng.normal(size=(b, hk, K)), F32)) / 4
    k = gd.l2_normalize(jnp.asarray(rng.normal(size=(b, hk, K)), F32))
    v = jnp.asarray(rng.normal(size=(b, hv, V)), jnp.bfloat16)
    log_alpha = -jnp.asarray(np.abs(rng.normal(size=(b, hv))), F32)
    beta = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(b, hv)), F32))
    args = (state, q, k, v, log_alpha, beta)
    o_k, s_k = gd.gated_delta_state_update(*args, interpret=True)
    o_x, s_x = gd.gated_delta_state_update_xla(*args)
    assert o_k.shape == (b, hv, V) and o_k.dtype == F32
    assert float(jnp.abs(o_x).max()) > 0.3
    assert float(jnp.abs(o_k - o_x).max()) < 2e-6
    assert float(jnp.abs(s_k - s_x).max()) < 2e-6
    # value head h reads key head h // 2: swapping two key heads' k moves
    # exactly their four value heads
    k2 = k.at[:, jnp.asarray([0, 1])].set(k[:, jnp.asarray([1, 0])])
    moved = np.abs(np.asarray(gd.gated_delta_state_update(
        state, q, k2, v, log_alpha, beta, interpret=True)[1] - s_k)
    ).max(axis=(0, 2, 3))
    assert (moved[:4] > 1e-3).all() and not moved[4:].any()
    jaxpr = jax.make_jaxpr(lambda *a: gd.gated_delta_state_update(
        *a, interpret=True))(*args)
    call = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call")
    assert tuple(call.params["input_output_aliases"]) == ((2, 1),)
    assert call.params["grid_mapping"].grid == (b,)     # a slot a step


def test_where_the_kernel_runs_is_decided_from_shapes(monkeypatch):
    from paddle_tpu.ops import registry
    state = jax.ShapeDtypeStruct((192, 32, 128, 128), F32)
    k = jax.ShapeDtypeStruct((192, 16, 128), F32)
    assert gd.gated_delta_state_update_supported(state, k)
    # 4 x a slot's 2 MiB block must fit the VMEM asked for
    assert 4 * 4 * 32 * 128 * 128 <= gd.VMEM_LIMIT - (8 << 20)
    small = jax.ShapeDtypeStruct((2, 4, 16, 64), F32)      # 64 lanes
    assert not gd.gated_delta_state_update_supported(
        small, jax.ShapeDtypeStruct((2, 2, 16), F32))
    monkeypatch.setattr(registry, "pallas_disabled", lambda: True)
    assert not gd.gated_delta_state_update_supported(state, k)
