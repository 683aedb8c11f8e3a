"""Fused vocab-projection + cross-entropy loss head (ISSUE 5).

The contract under test: ``fused_linear_cross_entropy(hidden, w, labels)``
is numerically interchangeable with the naive
``F.cross_entropy((hidden @ w).astype(f32), labels)`` — loss AND grads
(hidden, w, tied embedding) — across fp32/bf16, ignore_index, tied/untied
embeddings, and vocab sizes not divisible by the block size; the TP
composition matches the dense oracle under shard_map on the faked
8-device mesh; and the compiled fused train step contains NO intermediate
of size B*S*V (the regression this head exists to prevent — the HLO
guard)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
import paddle_tpu.nn.functional as F
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas.fused_vocab_ce import (fused_linear_cross_entropy,
                                                  lse_and_target)


def _naive(h, w, lab, ignore_index=-100):
    return F.cross_entropy((h @ w).astype(jnp.float32), lab,
                           ignore_index=ignore_index)


def _mk(n, hd, v, dtype, seed=0, ignore_rows=2):
    rs = np.random.RandomState(seed)
    h = jnp.asarray(rs.randn(n, hd), dtype)
    w = jnp.asarray(rs.randn(hd, v) * 0.1, dtype)
    lab = rs.randint(0, v, (n,))
    lab[:ignore_rows] = -100
    return h, w, jnp.asarray(lab)


# -- op-level gradcheck matrix ---------------------------------------------

@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("v,block_v", [(64, 16),    # divisible
                                       (300, 128)])  # NOT divisible (pad)
def test_gradcheck_vs_naive(dtype, rtol, v, block_v):
    h, w, lab = _mk(24, 16, v, dtype)
    fused = lambda h, w: fused_linear_cross_entropy(
        h, w, lab, block_n=8, block_v=block_v, impl="xla")
    lf = fused(h, w)
    ln = _naive(h, w, lab)
    np.testing.assert_allclose(float(lf), float(ln), rtol=rtol, atol=rtol)
    gf = jax.grad(fused, argnums=(0, 1))(h, w)
    gn = jax.grad(lambda h, w: _naive(h, w, lab), argnums=(0, 1))(h, w)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=rtol, atol=rtol)


def test_ignore_index_all_masked_row_safe():
    """A batch whose every label is ignored: loss 0, grads 0 (no NaN from
    the lse of nothing)."""
    h, w, _ = _mk(8, 16, 32, jnp.float32)
    lab = jnp.full((8,), -100, jnp.int32)
    fn = lambda h, w: fused_linear_cross_entropy(h, w, lab, block_n=8,
                                                 block_v=16, impl="xla")
    assert float(fn(h, w)) == 0.0
    g = jax.grad(fn, argnums=(0, 1))(h, w)
    assert np.isfinite(np.asarray(g[0])).all()
    assert float(jnp.abs(g[0]).max()) == 0.0
    assert float(jnp.abs(g[1]).max()) == 0.0


def test_reductions_and_dtype():
    h, w, lab = _mk(12, 16, 48, jnp.float32)
    nll = fused_linear_cross_entropy(h, w, lab, reduction="none",
                                     block_n=4, block_v=16, impl="xla")
    assert nll.shape == lab.shape and nll.dtype == jnp.float32
    assert float(nll[0]) == 0.0                      # ignored row
    tot = fused_linear_cross_entropy(h, w, lab, reduction="sum",
                                     block_n=4, block_v=16, impl="xla")
    np.testing.assert_allclose(float(jnp.sum(nll)), float(tot), rtol=1e-6)


def test_pallas_interpret_matches_xla():
    """The Pallas kernels (interpret mode on CPU) reproduce the XLA
    blockwise path exactly — fwd lse/tgt and both backward kernels."""
    h, w, lab = _mk(24, 16, 300, jnp.float32)   # vocab NOT block-divisible
    safe = jnp.where(lab == -100, -1, lab)
    ox = lse_and_target(h, w, safe, 8, 128, "xla", False)
    op = lse_and_target(h, w, safe, 8, 128, "pallas", True)
    np.testing.assert_allclose(np.asarray(ox[0]), np.asarray(op[0]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ox[1]), np.asarray(op[1]),
                               rtol=1e-6, atol=1e-6)
    gp = jax.grad(lambda h, w: fused_linear_cross_entropy(
        h, w, lab, block_n=8, block_v=128, impl="pallas", interpret=True),
        argnums=(0, 1))(h, w)
    gn = jax.grad(lambda h, w: _naive(h, w, lab), argnums=(0, 1))(h, w)
    for a, b in zip(gp, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (scan / cond / pjit bodies, a pallas_call's kernel)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub)


def test_pallas_backward_multiplies_three_times():
    """What ISSUE 35 is about: a block of logit cotangents is formed ONCE.
    The two backward kernels hold three products between them (logits and
    ``dlog @ W^T`` in dhidden's, ``h^T @ dlog`` in dW's), and the dW kernel
    is handed neither W nor the saved lse: it cannot recompute a softmax."""
    n, hd, v, bn, bv = 24, 16, 300, 8, 128
    h, w, lab = _mk(n, hd, v, jnp.float32)

    def loss(h, w):
        lse, tgt = lse_and_target(h, w, lab, bn, bv, "pallas", True)
        return jnp.sum(lse - tgt)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, w)
    calls = {}
    for eqn in _walk_eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] = eqn
    assert sorted(calls) == ["fused_vocab_ce_bwd_dh", "fused_vocab_ce_bwd_dw",
                             "fused_vocab_ce_fwd"]
    dots = {name: sum(e.primitive.name == "dot_general"
                      for e in _walk_eqns(eqn.params["jaxpr"]))
            for name, eqn in calls.items()}
    assert dots == {"fused_vocab_ce_fwd": 1, "fused_vocab_ce_bwd_dh": 2,
                    "fused_vocab_ce_bwd_dw": 1}
    # both calls stand in one scan body: the dW call takes the slab's index,
    # h, the stored dlog and the dW buffer it fills in place (aliased to its
    # result, never read) -- not the dhidden call's W, not its lse
    dh, dw = calls["fused_vocab_ce_bwd_dh"], calls["fused_vocab_ce_bwd_dw"]
    vp = -(-v // bv) * bv               # one slab: the whole padded vocab
    w_var, lse_var = dh.invars[2], dh.invars[4]
    assert w_var.aval.shape == (hd, vp) and lse_var.aval.shape == (n, 8)
    assert not {w_var, lse_var} & set(dw.invars)
    assert [tuple(x.aval.shape) for x in dw.invars] == [
        (1,), (n, hd), (n, vp), (hd, vp)]
    assert dw.invars[2] is dh.outvars[1]
    assert dw.params["input_output_aliases"] == ((3, 0),)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("v,block_v,slab_blocks", [
    (256, 64, None),     # one slab holds every block
    (256, 64, 2),        # two slabs of two blocks
    (300, 128, 1),       # padded vocabulary, a slab a block
    (600, 128, 2),       # padded AND ragged: five blocks in slabs of two
])
def test_pallas_backward_matches_xla_over_slabs(dtype, tol, v, block_v,
                                                slab_blocks, monkeypatch):
    """``lse_and_target``'s own VJP, Pallas (interpret) against XLA, with a
    DIFFERENT cotangent on every row of both outputs and rows whose label
    lies outside the vocabulary: dhidden is summed across the slabs in
    float32, dW is assembled from them, and neither depends on how many
    blocks a slab holds."""
    from paddle_tpu.ops.pallas import fused_vocab_ce
    n, hd, bn = 48, 32, 8
    if slab_blocks is not None:
        monkeypatch.setattr(fused_vocab_ce, "SLAB_BYTES",
                            slab_blocks * n * block_v
                            * jnp.dtype(dtype).itemsize)
    h, w, lab = _mk(n, hd, v, dtype, seed=3, ignore_rows=5)
    safe = jnp.where(lab == -100, -1, lab)
    rs = np.random.RandomState(4)
    g = (jnp.asarray(rs.uniform(0.5, 1.5, n), jnp.float32),
         jnp.asarray(rs.uniform(-1.5, -0.5, n), jnp.float32))

    def grads(impl, interpret):
        out, vjp = jax.vjp(lambda h, w: lse_and_target(
            h, w, safe, bn, block_v, impl, interpret), h, w)
        return out, vjp(g)
    (ox, gx), (op, gp) = grads("xla", False), grads("pallas", True)
    for a, b in zip(ox + gx, op + gp):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("hd", [128, 1024, 1536, 2048, 4096, 8192])
@pytest.mark.parametrize("n", [16384, 4096])
def test_default_blocks_pass_the_support_gate(hd, n):
    """The block chooser and the Mosaic/VMEM gate share one formula: a
    default config the gate then rejects would silently route every TPU
    call to the XLA fallback at production hidden sizes (the failure the
    first review caught) — pin that the defaults are gate-accepted across
    the Llama size range."""
    from paddle_tpu.ops.pallas.fused_vocab_ce import (
        VMEM_BUDGET, _dw_vmem_bytes, default_blocks, dw_block_n,
        fused_ce_supported, slab_blocks)
    bn, bv = default_blocks(n, hd, "bfloat16")
    assert bn is not None and n % bn == 0 and bv % 128 == 0
    assert fused_ce_supported(n, hd, 128256, jnp.bfloat16, bn, bv)
    # the dW kernel sweeps rows in a block of its own, under the same budget
    dw_n = dw_block_n(n, bn, bv, hd, 2)
    assert dw_n % bn == 0 and n % dw_n == 0
    assert _dw_vmem_bytes(dw_n, bv, hd, 2) <= VMEM_BUDGET
    # and a slab of the backward holds at least one vocab block of all rows
    assert slab_blocks(n, bv, 2, -(-128256 // bv)) >= 1


# -- model-level: fused is the default loss path ----------------------------

@pytest.mark.parametrize("tied", [False, True])
def test_model_fused_matches_naive(tied):
    """LlamaForCausalLM loss + ALL grads (incl. the tied embedding, which
    receives both the trunk-gather and the transposed-dW contributions)
    match between loss_impl='fused' (default) and 'naive'."""
    pt.seed(0)
    cfg = LlamaConfig.tiny(tie_word_embeddings=tied)
    m = LlamaForCausalLM(cfg)
    params = dict(m.raw_parameters())
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 40)))
    lab_np = rs.randint(0, cfg.vocab_size, (2, 40))
    lab_np[0, :5] = -100
    lab = jnp.asarray(lab_np)

    def loss_of(p):
        return m.functional_call(p, ids, labels=lab)[0]

    assert cfg.loss_impl == "fused"          # the default
    lf, gf = jax.value_and_grad(loss_of)(params)
    cfg.loss_impl = "naive"
    try:
        ln, gn = jax.value_and_grad(loss_of)(params)
    finally:
        cfg.loss_impl = "fused"
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-6)
    for k in gf:
        np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(gn[k]),
                                   rtol=2e-5, atol=2e-6, err_msg=k)


def test_escape_hatch_env(monkeypatch):
    """PT_NAIVE_LOSS_HEAD=1 flips the default back to the naive head."""
    from paddle_tpu.models.llama import fused_loss_enabled
    cfg = LlamaConfig.tiny()
    assert fused_loss_enabled(cfg)
    monkeypatch.setenv("PT_NAIVE_LOSS_HEAD", "1")
    assert not fused_loss_enabled(cfg)
    monkeypatch.delenv("PT_NAIVE_LOSS_HEAD")
    cfg.loss_impl = "naive"
    assert not fused_loss_enabled(cfg)
    with pytest.raises(ValueError):
        LlamaConfig.tiny(loss_impl="bogus")


def test_return_logits_false_scalar():
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 512, (2, 8)))
    out = m(ids, labels=ids, return_logits=False)
    assert out.shape == ()
    loss, logits = m(ids, labels=ids)
    np.testing.assert_allclose(float(out), float(loss), rtol=1e-6)
    assert logits.shape == (2, 8, 512)


# -- TP composition under shard_map (faked multi-device mesh) ---------------

def test_tp_parity_shard_map():
    """parallel_fused_linear_cross_entropy on a dp=2 x tp=4 mesh: per-token
    nll, mean loss and (dhidden, dw) all match the dense single-device
    oracle; works jitted with dp-sharded batch."""
    from paddle_tpu.parallel import HybridMesh, shard_tensor
    from paddle_tpu.parallel.mp_layers import (
        parallel_fused_linear_cross_entropy)
    rs = np.random.RandomState(0)
    B, S, H, V = 4, 32, 16, 64
    h = jnp.asarray(rs.randn(B, S, H).astype(np.float32))
    w = jnp.asarray(rs.randn(H, V).astype(np.float32) * 0.1)
    lab_np = rs.randint(0, V, (B, S))
    lab_np[0, :3] = -100
    lab = jnp.asarray(lab_np)

    logp = jax.nn.log_softmax((h @ w).astype(jnp.float32), axis=-1)
    safe = np.where(lab_np == -100, 0, lab_np)
    ref = -np.take_along_axis(np.asarray(logp), safe[..., None],
                              axis=-1)[..., 0]
    ref = np.where(lab_np == -100, 0.0, ref)

    hm = HybridMesh.build(dp=2, tp=4)
    with hm:
        h_s = shard_tensor(h, spec=P("dp", None, None))
        lab_s = shard_tensor(lab, spec=P("dp", None))
        w_s = shard_tensor(w, spec=P(None, "tp"))

        nll = parallel_fused_linear_cross_entropy(h_s, w_s, lab_s,
                                                  block_v=16, block_n=8)
        np.testing.assert_allclose(np.asarray(nll), ref, rtol=1e-5,
                                   atol=1e-5)

        def mean_loss(h, w):
            nll = parallel_fused_linear_cross_entropy(h, w, lab_s,
                                                      block_v=16, block_n=8)
            cnt = jnp.sum(lab_s != -100).astype(jnp.float32)
            return jnp.sum(nll) / cnt

        gf = jax.jit(jax.grad(mean_loss, argnums=(0, 1)))(h_s, w_s)
        gd = jax.grad(lambda hh, ww: _naive(hh, ww, lab),
                      argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gd[0]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gd[1]),
                                   rtol=2e-5, atol=2e-5)


def test_tp_block_not_dividing_shard_falls_back():
    """A block_v that doesn't divide the per-shard vocab must not pad
    inside the manual region (SPMD partitioner crash) — it falls back to a
    dividing block and stays correct."""
    from paddle_tpu.parallel import HybridMesh, shard_tensor
    from paddle_tpu.parallel.mp_layers import (
        parallel_fused_linear_cross_entropy)
    rs = np.random.RandomState(1)
    B, S, H, V = 2, 8, 8, 48            # shard = 12: 2048-cands don't divide
    h = jnp.asarray(rs.randn(B, S, H).astype(np.float32))
    w = jnp.asarray(rs.randn(H, V).astype(np.float32) * 0.1)
    lab = jnp.asarray(rs.randint(0, V, (B, S)))
    logp = jax.nn.log_softmax((h @ w).astype(jnp.float32), axis=-1)
    ref = -np.take_along_axis(np.asarray(logp),
                              np.asarray(lab)[..., None], axis=-1)[..., 0]
    hm = HybridMesh.build(dp=2, tp=4)
    with hm:
        w_s = shard_tensor(w, spec=P(None, "tp"))
        nll = jax.jit(lambda h, w, lab:
                      parallel_fused_linear_cross_entropy(h, w, lab,
                                                          block_v=32))(
            h, w_s, lab)
        np.testing.assert_allclose(np.asarray(nll), ref, rtol=1e-5,
                                   atol=1e-5)


# -- the HLO guard: no B*S*V intermediate in the compiled train step --------
# The detector itself moved to paddle_tpu.analysis (ISSUE 8): the one-off
# _bsv_buffers regex became the materialization analyzer's BanRule, so the
# "no logits buffer" check has ONE definition shared by this test, the
# train-step graph contract and tools/graph_lint.py.

def test_hlo_guard_no_bsv_intermediate():
    """THE regression this PR exists to prevent: the compiled fused train
    step (loss + grads, the Trainer's jit shape) must contain no buffer of
    size B*S*V in its optimized HLO. The naive path must trip the same
    detector — proving the guard can see the buffer it bans."""
    from paddle_tpu.analysis import BanRule, banned_buffers, parse_hlo
    pt.seed(0)
    cfg = LlamaConfig.tiny()            # V=512, H=128
    m = LlamaForCausalLM(cfg)
    params = dict(m.raw_parameters())
    B, S = 2, 40                        # B*S=80 collides with no other dim
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S)))
    lab = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S)))
    rule = BanRule(cfg.vocab_size, B * S, label="BSV-logits")

    def step(p):
        return m.functional_call(p, ids, labels=lab)[0]

    fused_hlo = jax.jit(jax.value_and_grad(step)).lower(params) \
        .compile().as_text()
    hits = banned_buffers(parse_hlo(fused_hlo), [rule])
    assert hits == [], (
        "fused train step materialized a B*S*V logits buffer:\n"
        + "\n".join(h.describe() for h in hits))
    # the profiler span: loss-head ops carry the named_scope in their op
    # metadata, so device traces (xplane/chrome) attribute the loss head
    assert "loss_head" in fused_hlo

    cfg.loss_impl = "naive"
    try:
        naive_hlo = jax.jit(jax.value_and_grad(step)).lower(params) \
            .compile().as_text()
    finally:
        cfg.loss_impl = "fused"
    assert banned_buffers(parse_hlo(naive_hlo), [rule]), \
        "guard sanity: the naive path should materialize logits"


def test_hlo_guard_jaxpr_return_logits_false():
    """Belt-and-braces jaxpr-level guard: with return_logits=False not
    even a DEAD logits equation is traced — no aval of size B*S*V appears
    anywhere in the closed jaxpr (including scan sub-jaxprs)."""
    pt.seed(0)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    params = dict(m.raw_parameters())
    B, S = 2, 40
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S)))
    lab = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S)))

    def step(p):
        return m.functional_call(p, ids, labels=lab, return_logits=False)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(step))(params)

    bad = []
    for eqn in _walk_eqns(jaxpr.jaxpr):      # scan/cond sub-jaxprs too
        for v in eqn.outvars:
            shape = getattr(getattr(v, "aval", None), "shape", ())
            if (len(shape) >= 2 and shape[-1] == cfg.vocab_size
                    and int(np.prod(shape[:-1])) == B * S):
                bad.append(shape)
    assert not bad, f"B*S*V avals traced: {bad}"
