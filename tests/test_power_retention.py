"""Power retention of degree 2 (``ops/pallas/power_retention.py``): the
expansion, the tick's kernel and the prompt's, each against its ``jnp`` twin
in interpret mode at Brumby's widths (head size 128, 5 query heads a KV
head), and every form of the function against the QUADRATIC one the
benchmark's reference computes (``benchmarks/refs/brumby.py``: every position
weighs every earlier one, no state, no ``phi``).

Tolerances, and why. Program and reference are two algebraic forms of one
float32 function: a reading is a ratio of two sums of up to a few thousand
terms, so the forms agree to a few units of float32 rounding RELATIVE TO THE
DENOMINATOR: where a position's weights add up to ``den`` the recurrent
form's sum over 8,256 products carries ~1e-6 of absolute error and the
reading ~1e-6 / den of it. The seeded cases keep ``den`` above 0.01, so 5e-4
absolute on readings of size ~1 holds with room; a kernel against its twin
(the same sums in another order) agrees to 2e-5, states to 1e-4 of a state
whose entries reach ~10.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.ops.pallas import power_retention as pr  # noqa: E402

F32 = jnp.float32
HQ, HKV, D = 10, 2, 128                   # two groups of five, as published


def quadratic(q, k, v, log_g):
    """The quadratic form in float64 numpy: q [L, Hq, d]; k, v [L, Hkv, d];
    log_g [L, Hkv] -> (y [L, Hq, d], the sums of weights [L, Hq])."""
    q, k, v, log_g = (np.asarray(t, np.float64) for t in (q, k, v, log_g))
    L, hq, d = q.shape
    total = np.cumsum(log_g, 0)
    y, den = np.zeros((L, hq, d)), np.zeros((L, hq))
    for n in range(hq):
        h = n // (hq // k.shape[1])
        s = q[:, n] @ k[:, h].T / math.sqrt(d)
        w = np.tril(np.exp(np.minimum(total[:, None, h] - total[None, :, h],
                                      0.0)) * s * s)
        den[:, n] = w.sum(1)
        y[:, n] = w @ v[:, h] / (den[:, n, None] + pr.EPS)
    return y, den


def inputs(L, hq=HQ, hkv=HKV, d=D, gate=1 / 256, seed=0):
    """q, k at unit RMS and v, [L, H, d]; log g ~ -|N(0, gate)|: gates near 1
    at 1/256 (a state that still holds half of what it held 180 tokens
    ago), fast ones at 1."""
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(L, hq, d)), rng.normal(size=(L, hkv, d)),
            rng.normal(size=(L, hkv, d)),
            -np.abs(rng.normal(size=(L, hkv))) * gate)


def batch(*arrays):
    return tuple(jnp.asarray(a, F32)[None] for a in arrays)


def test_phi_is_the_square_of_the_inner_product():
    """``phi(x) . phi(y) = (x . y)^2 / d`` in 65 tiles of 128 lanes, 8,320
    numbers for the 8,256 pairs: the last tile's upper half stays zero."""
    rng = np.random.default_rng(1)
    for d in (16, 128):
        x, y = rng.normal(size=(2, 7, d))
        a, b = pr.phi(jnp.asarray(x)), pr.phi(jnp.asarray(y))
        assert a.shape == (7, d // 2 + 1, d) == (7, pr.tiles(d), d)
        want = (np.sum(x * y, -1) ** 2) / d
        assert np.allclose(np.sum(np.asarray(a * b), (-1, -2)), want,
                           rtol=2e-5)
        assert not np.asarray(a)[:, -1, d // 2:].any()
        assert np.count_nonzero(np.asarray(a)[0]) == d * (d + 1) // 2
    assert pr.tiles(128) * 128 == 8320 and 128 * 129 // 2 == 8256
    assert pr.z_rows(128) == 72 and pr.z_rows(16) == 16


@pytest.mark.parametrize("gate", [1 / 256, 1.0], ids=["slow", "fast"])
@pytest.mark.parametrize("length", [130, 300])
def test_the_chunked_twin_is_the_quadratic_form_over_several_chunks(length,
                                                                    gate):
    """Lengths that are no multiple of the chunk, three and five chunks of
    64: with gates near 1 nearly all of a late position's weight comes
    THROUGH the carried state, so a fault in what crosses a chunk's edge
    shows whole."""
    q, k, v, log_g = inputs(length, gate=gate)
    want, den = quadratic(q, k, v, log_g)
    y, s, z = pr.power_retention_chunked_xla(*batch(q, k, v, log_g), chunk=64)
    assert den.min() > 0.01
    assert np.abs(np.asarray(y[0]) - want).max() < 5e-4
    if gate < 1:        # the carried share: what the last chunk adds is small
        assert den[-1].min() > 20 * den[63].max() / 64
    assert s.shape == (1, HKV, 65, D, D) and z.shape == (1, HKV, 72, D)
    assert not np.asarray(z)[:, :, 65:].any()


@pytest.mark.parametrize("gate", [1 / 256, 1.0], ids=["slow", "fast"])
def test_the_recurrence_token_by_token_is_both(gate):
    """The tick's update from a zero state reads what the quadratic form
    reads at every position, and leaves the state the chunked form
    leaves."""
    L = 70
    q, k, v, log_g = inputs(L, gate=gate, seed=2)
    want, den = quadratic(q, k, v, log_g)
    _, s, z = pr.power_retention_chunked_xla(*batch(q, k, v, log_g), chunk=32)
    state = (jnp.zeros((1, HKV, 65, D, D), F32), jnp.zeros((1, HKV, 72, D), F32))
    step = jax.jit(pr.power_state_update_xla)
    for t in range(L):
        y, *state = step(*state, *batch(q[t], k[t], v[t], log_g[t]))
        # a position's reading is exact to ~1e-6 of its weights' sum
        assert np.abs(np.asarray(y[0]) - want[t]).max() < 2e-5 / min(
            den[t].min(), 1.0)
    assert np.abs(np.asarray(state[0] - s)).max() < 1e-4
    assert np.abs(np.asarray(state[1] - z)).max() < 1e-4


def test_padding_leaves_the_state_as_it_is():
    """A bucket's padding (a gate of 1, a key of 0) moves neither the state
    nor the readings in front of it; the state returned is the state at
    the prompt's TRUE last position."""
    L, pad = 150, 106
    q, k, v, log_g = inputs(L, seed=3)
    y, s, z = pr.power_retention_chunked_xla(*batch(q, k, v, log_g), chunk=64)
    zeros = lambda t: np.concatenate([t, np.zeros((pad,) + t.shape[1:])])
    rng = np.random.default_rng(4)
    more = lambda t: np.concatenate([t, rng.normal(size=(pad,) + t.shape[1:])])
    y2, s2, z2 = pr.power_retention_chunked_xla(
        *batch(more(q), zeros(k), more(v), zeros(log_g)), chunk=64)
    assert np.abs(np.asarray(y2[0, :L] - y[0])).max() < 1e-5
    assert np.abs(np.asarray(s2 - s)).max() < 1e-5
    assert np.abs(np.asarray(z2 - z)).max() < 1e-5


# -- the kernels, in interpret mode -------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_update_kernel_equals_its_twin_in_interpret_mode(dtype):
    """Widths 128, 8 KV heads, 5 a group, 2 slots, on a state eight tokens
    deep; q, k, v in the model's dtype, the state float32 either way."""
    B, hq, hkv = 2, 40, 8
    rng = np.random.default_rng(5)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), F32)
    state = (jnp.zeros((B, hkv, 65, D, D), F32), jnp.zeros((B, hkv, 72, D), F32))
    twin = jax.jit(pr.power_state_update_xla)
    for _ in range(8):
        _, *state = twin(*state, draw(B, hq, D), draw(B, hkv, D),
                         draw(B, hkv, D), -jnp.abs(draw(B, hkv)) / 8)
    args = (draw(B, hq, D).astype(dtype), draw(B, hkv, D).astype(dtype),
            draw(B, hkv, D).astype(dtype), -jnp.abs(draw(B, hkv)))
    want = twin(*state, *args)
    got = pr.power_state_update(*state, *args, interpret=True)
    assert got[0].dtype == F32 and got[0].shape == (B, hq, D)
    for a, b, tol in zip(got, want, (2e-5, 1e-4, 1e-4)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.abs(np.asarray(a - b)).max() < tol
    assert not np.asarray(got[2])[:, :, 65:].any()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 0.08)])
@pytest.mark.parametrize("length", [128, 300])
def test_the_chunked_kernel_equals_its_twin_in_interpret_mode(length, dtype,
                                                              tol):
    """One chunk and three, the last one ragged, slow gates. In float32 the
    kernel's products are float32's and it equals its twin; in bfloat16 its
    products take bfloat16 operands (one pass of the MXU: ``phi`` and the
    state rounded to 8 bits of mantissa a term), which the float32 twin
    does not, so readings of size ~1 differ by a few hundredths while the
    state, summed in float32 from rounded terms, stays within 1% of its
    largest entry."""
    q, k, v, log_g = inputs(length, hq=HQ, hkv=HKV, seed=6)
    args = tuple(t.astype(dtype) for t in batch(q, k, v)) + batch(log_g)
    want = pr.power_retention_chunked_xla(*args)
    got = pr.power_retention_chunked(*args, interpret=True)
    assert got[0].shape == (1, length, HQ, D)
    assert np.abs(np.asarray(got[0] - want[0])).max() < tol
    scale = float(jnp.max(jnp.abs(want[1])))
    assert np.abs(np.asarray(got[1] - want[1])).max() < (
        1e-5 if dtype == jnp.float32 else 0.01) * scale
    assert np.abs(np.asarray(got[2] - want[2])).max() < (
        1e-4 if dtype == jnp.float32 else 0.01 * scale)


def test_the_update_kernel_updates_its_state_operands_in_place():
    """The state and the normaliser go in as operands 2 and 3 (behind the
    prefetched gates and the packed rows) and come back as results 1 and 2:
    ``input_output_aliases`` says so, which is what lets a donated 1.1 GB
    a layer stay ONE buffer."""
    state = (jnp.zeros((1, 1, 65, D, D), F32), jnp.zeros((1, 1, 72, D), F32))
    rest = (jnp.ones((1, 5, D)), jnp.ones((1, 1, D)), jnp.ones((1, 1, D)),
            jnp.zeros((1, 1)))
    jaxpr = jax.make_jaxpr(lambda s, z: pr.power_state_update(
        s, z, *rest, interpret=True))(*state).jaxpr
    call = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
    aliases = dict(call.params["input_output_aliases"])
    assert aliases == {2: 1, 3: 2}
    ins = [v.aval.shape for v in call.invars]
    outs = [v.aval.shape for v in call.outvars]
    assert ins[2] == outs[1] == (1, 1, 72, D)
    assert ins[3] == outs[2] == (1, 1, 65, D, D)


def test_the_gates_say_what_mosaic_takes(monkeypatch):
    """Tiles of exactly 128 lanes and chunks of 128; a group of at most 6
    query heads; nothing with the kernels switched off."""
    from paddle_tpu.ops import registry
    sds = jax.ShapeDtypeStruct
    state = sds((32, 8, 65, 128, 128), F32)
    assert pr.power_state_update_supported(state, sds((32, 40, 128), F32))
    assert not pr.power_state_update_supported(state, sds((32, 64, 128), F32))
    assert not pr.power_state_update_supported(
        sds((32, 8, 9, 16, 16), F32), sds((32, 40, 16), F32))
    assert not pr.power_state_update_supported(
        sds((32, 8, 65, 128, 128), jnp.bfloat16), sds((32, 40, 128), F32))
    q, k = sds((1, 512, 40, 128), F32), sds((1, 512, 8, 128), F32)
    assert pr.power_retention_chunked_supported(q, k, 128)
    assert not pr.power_retention_chunked_supported(q, k, 64)
    assert not pr.power_retention_chunked_supported(
        sds((1, 512, 40, 64), F32), sds((1, 512, 8, 64), F32), 128)
    monkeypatch.setattr(registry, "pallas_disabled", lambda: True)
    assert not pr.power_state_update_supported(state, sds((32, 40, 128), F32))
    assert not pr.power_retention_chunked_supported(q, k, 128)


@pytest.mark.parametrize("flag,bench", [
    ("--power-update", "power_state_update"),
    ("--power-prefill", "power_retention_chunked")])
def test_the_tuning_tool_times_each_kernel_against_its_twin(flag, bench,
                                                            capsys,
                                                            monkeypatch):
    """``tools/tune_kernels.py`` runs both benches in interpret mode: a
    line a bench with both sides' times and the widest difference of the
    two readings."""
    import json
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import tune_kernels
    monkeypatch.setattr(sys, "argv", ["tune_kernels.py", flag, "--interpret"])
    tune_kernels.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    line = next(l for l in lines if l.get("bench") == bench)
    assert line["pallas_us"] > 0 and line["xla_us"] > 0
    assert next(l["reading_max_diff"] for l in lines
                if "reading_max_diff" in l) < 0.1
    assert lines[-1] == {"tuned": False, "cases": 1}
