"""Pallas fused rope vs the XLA composition (interpret mode on CPU).

Reference analogue: fused_rope_kernel.cu parity tests. The kernel rotates
q and k in one pass; the vjp applies the transpose rotation (cos, -sin).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import rope as rope_ops
from paddle_tpu.ops.pallas.fused_rope import (fused_rope_pallas,
                                              rope_supported, tuned_block_s)
from paddle_tpu.ops.registry import pallas_disabled_scope


def _xla_rope(q, k, cos, sin):
    """Reference computation with kernel dispatch OFF — on a TPU host the
    public API would route to the very kernel under test."""
    with pallas_disabled_scope():
        return rope_ops.apply_rotary_pos_emb(q, k, cos, sin)


def _data(b=2, s=64, h=4, hk=2, d=128, dtype=jnp.float32, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.normal(0, 1, (b, s, h, d)), dtype)
    k = jnp.asarray(rs.normal(0, 1, (b, s, hk, d)), dtype)
    cos, sin = rope_ops.rope_freqs(d, s)
    return q, k, cos, sin


class TestFusedRopeKernel:
    def test_matches_xla_composition(self):
        q, k, cos, sin = _data()
        want_q, want_k = _xla_rope(q, k, cos, sin)
        got_q, got_k = fused_rope_pallas(q, k, cos, sin, block_s=32,
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(got_q), np.asarray(want_q),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_k), np.asarray(want_k),
                                   rtol=1e-5, atol=1e-5)

    def test_gqa_head_counts_differ(self):
        q, k, cos, sin = _data(h=8, hk=2)
        want_q, want_k = _xla_rope(q, k, cos, sin)
        got_q, got_k = fused_rope_pallas(q, k, cos, sin, block_s=64,
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(got_q), np.asarray(want_q),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_k), np.asarray(want_k),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_io(self):
        q, k, cos, sin = _data(dtype=jnp.bfloat16)
        got_q, _ = fused_rope_pallas(q, k, cos, sin, block_s=64,
                                     interpret=True)
        assert got_q.dtype == jnp.bfloat16
        want_q, _ = _xla_rope(q, k, cos, sin)
        np.testing.assert_allclose(
            np.asarray(got_q, np.float32), np.asarray(want_q, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_transpose_rotation_is_the_vjp(self):
        """The rope vjp used by the dispatch: rotating the cotangent by
        (cos, -sin) must equal jax.vjp of the XLA composition."""
        q, k, cos, sin = _data(s=16)
        def f(qq, kk):
            with pallas_disabled_scope():
                return rope_ops.apply_rotary_pos_emb(qq, kk, cos, sin)
        out, vjp_fn = jax.vjp(f, q, k)
        gq = jnp.ones_like(out[0])
        gk = jnp.ones_like(out[1])
        want_dq, want_dk = vjp_fn((gq, gk))
        got_dq, got_dk = fused_rope_pallas(gq, gk, cos, -sin, block_s=16,
                                           interpret=True)
        np.testing.assert_allclose(np.asarray(got_dq), np.asarray(want_dq),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_dk), np.asarray(want_dk),
                                   rtol=1e-5, atol=1e-5)

    def test_support_gate(self):
        assert rope_supported((2, 64, 4, 128), (2, 64, 2, 128))
        assert not rope_supported((2, 64, 4, 96), (2, 64, 2, 96))   # lane
        assert not rope_supported((2, 63, 4, 128), (2, 63, 2, 128)) # seq%8
        assert not rope_supported((2, 64, 128), (2, 64, 128))       # rank

    def test_tuned_block_divides(self):
        for s in (8, 24, 128, 2048, 520):
            bs = tuned_block_s(s, 128)
            assert s % bs == 0

    def test_seq_indivisible_raises(self):
        q, k, cos, sin = _data(s=64)
        with pytest.raises(ValueError, match="divide"):
            fused_rope_pallas(q, k, cos, sin, block_s=48, interpret=True)

    def test_table_cotangents_formula(self):
        """_rope_bwd's dcos/dsin must match jax.vjp of the XLA path wrt
        the tables (they are real grads, not zeros)."""
        q, k, cos, sin = _data(s=16)

        def f(c, s_):
            with pallas_disabled_scope():
                qo, ko = rope_ops.apply_rotary_pos_emb(q, k, c, s_)
            return qo, ko

        out, vjp_fn = jax.vjp(f, cos, sin)
        gq, gk = jnp.ones_like(out[0]), jnp.ones_like(out[1])
        want_dcos, want_dsin = vjp_fn((gq, gk))

        rot = rope_ops.rotate_half
        got_dcos = (jnp.sum(gq * q, axis=(0, 2))
                    + jnp.sum(gk * k, axis=(0, 2)))
        got_dsin = (jnp.sum(gq * rot(q), axis=(0, 2))
                    + jnp.sum(gk * rot(k), axis=(0, 2)))
        np.testing.assert_allclose(np.asarray(got_dcos),
                                   np.asarray(want_dcos), rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(got_dsin),
                                   np.asarray(want_dsin), rtol=1e-4,
                                   atol=1e-4)
