"""Pallas kernels under a device mesh (ops/pallas/per_shard.py).

A Mosaic kernel cannot be partitioned by GSPMD, so on a mesh each kernel
entry point runs per shard inside a fully-manual shard_map. The lowering is
pinned for the described chip in tests/test_aot_tpu_compile.py; here the
NUMBERS are: the same entry points in Pallas interpret mode on a 2x2
virtual CPU mesh, values and gradients against the XLA composition — in
particular the sums the region's transpose owes (dW over the data axes,
dhidden over tp).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel import HybridMesh, shard_tensor

pytestmark = pytest.mark.skipif(jax.device_count() < 4,
                                reason="needs 4 (virtual) devices")

LAYOUTS = [dict(fsdp=4), dict(fsdp=2, tp=2), dict(dp=2, tp=2)]


def _mesh(layout):
    return HybridMesh.build(devices=jax.devices()[:4], **layout)


def _close(got, want, tol=2e-5):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("layout", LAYOUTS, ids=str)
def test_rms_norm_per_shard(layout):
    from paddle_tpu.ops.norm import _rms_norm_xla
    from paddle_tpu.ops.pallas.fused_norm import rms_norm_pallas
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.normal(0, 1, (4, 16, 128)), jnp.float32)
    w = jnp.asarray(rs.normal(1, 0.1, (128,)), jnp.float32)

    def loss(fn, x, w):
        return jnp.sum(jnp.sin(fn(x, w)))

    want = jax.value_and_grad(
        lambda x, w: loss(lambda a, b: _rms_norm_xla(a, b, 1e-6), x, w),
        argnums=(0, 1))(x, w)
    with _mesh(layout):
        xs = shard_tensor(x, spec=P(("dp", "fsdp"), None, None))
        got = jax.jit(jax.value_and_grad(
            lambda x, w: loss(lambda a, b: rms_norm_pallas(
                a, b, 1e-6, interpret=True), x, w), argnums=(0, 1)))(xs, w)
    _close(got, want)


@pytest.mark.parametrize("layout", LAYOUTS, ids=str)
@pytest.mark.parametrize("packed", [False, True])
def test_flash_attention_per_shard(layout, packed):
    from paddle_tpu.ops.attention import _sdpa_xla
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.normal(0, 1, (4, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (4, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (4, 128, 2, 32)), jnp.float32)
    seg = (jnp.asarray(np.repeat(np.arange(2), 64)[None].repeat(4, 0))
           if packed else None)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.sin(fn(q, k, v)))

    want = jax.value_and_grad(
        lambda q, k, v: loss(lambda *a: _sdpa_xla(
            *a, causal=True, segment_ids=seg), q, k, v),
        argnums=(0, 1, 2))(q, k, v)
    with _mesh(layout):
        spec = P(("dp", "fsdp"), None, "tp", None)
        qs, ks, vs = (shard_tensor(a, spec=spec) for a in (q, k, v))
        got = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(lambda *a: flash_attention_pallas(
                *a, causal=True, segment_ids=seg, block_q=64, block_k=64,
                interpret=True), q, k, v), argnums=(0, 1, 2)))(qs, ks, vs)
    _close(got, want, tol=2e-4)


@pytest.mark.parametrize("layout", LAYOUTS, ids=str)
def test_fused_ce_per_shard(layout):
    """The loss head as the model calls it: fused_causal_lm_loss picks the
    data-parallel or the vocab-parallel composition from the mesh."""
    from paddle_tpu.models.llama import _token_mean
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops.pallas.fused_vocab_ce import (
        fused_linear_cross_entropy)
    from paddle_tpu.parallel.mp_layers import (
        parallel_fused_linear_cross_entropy)
    rs = np.random.RandomState(2)
    h = jnp.asarray(rs.normal(0, 1, (4, 8, 32)), jnp.float32)
    w = jnp.asarray(rs.normal(0, 0.2, (32, 96)), jnp.float32)
    lab = rs.randint(0, 96, (4, 8))
    lab[0, :3] = -100
    lab = jnp.asarray(lab)
    want = jax.value_and_grad(
        lambda h, w: F.cross_entropy((h @ w).astype(jnp.float32), lab,
                                     ignore_index=-100),
        argnums=(0, 1))(h, w)
    hm = _mesh(layout)
    tp = hm.axis_size("tp") > 1

    def fused(h, w):
        if tp:
            nll = parallel_fused_linear_cross_entropy(
                h, w, lab, block_n=8, block_v=16, interpret=True)
            return _token_mean(nll, lab)
        return fused_linear_cross_entropy(h, w, lab, block_n=8, block_v=16,
                                          interpret=True)

    with hm:
        hs = shard_tensor(h, spec=P(("dp", "fsdp"), None, None))
        ws = shard_tensor(w, spec=P("fsdp", "tp"))
        got = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(hs, ws)
    _close(got, want)
