"""Pallas flash-attention correctness vs the XLA reference (interpret mode
on CPU — the kernel-correctness strategy of the reference's OpTest applied
to the hand-written kernel; reference oracle: ops/attention._sdpa_xla)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import _sdpa_xla
from paddle_tpu.ops.pallas import autotune
from paddle_tpu.ops.pallas import flash_attention as flash
from paddle_tpu.ops.pallas.flash_attention import (NEG_INF, flash_attention_pallas,
                                                   flash_fwd_block,
                                                   flash_plan,
                                                   pallas_supported)


def make_qkv(b=1, sq=128, sk=128, h=2, h_kv=2, d=64, dtype=jnp.float32, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, sq, h, d), dtype) * 0.5
    k = jnp.asarray(rs.randn(b, sk, h_kv, d), dtype) * 0.5
    v = jnp.asarray(rs.randn(b, sk, h_kv, d), dtype) * 0.5
    return q, k, v


def _case(id, causal=True, bq=64, bk=64, budget=None, part_rows=None,
          **shape):
    """One call: its shape (``make_qkv``'s), its blocks, the VMEM the
    one-pass backward may keep (0 forces the two-pass form), and the rows
    of a block's row parts where the test cuts blocks finer than the chip
    does."""
    return pytest.param(dict(causal=causal, bq=bq, bk=bk, budget=budget,
                             part_rows=part_rows, shape=shape), id=id)


@pytest.fixture
def part_rows(monkeypatch):
    """Cut the blocks of a case that asks for it into parts of that many
    rows, forward and backward."""
    def cut(case):
        if case["part_rows"]:
            monkeypatch.setattr(flash, "FWD_PART_ROWS", case["part_rows"])
            monkeypatch.setattr(flash, "BWD_PART_ROWS", case["part_rows"])
    return cut


def _run(case, q, k, v, **kw):
    if case["budget"] is not None:
        kw["vmem_budget"] = case["budget"]
    return flash_attention_pallas(q, k, v, causal=case["causal"],
                                  interpret=True, block_q=case["bq"],
                                  block_k=case["bk"], **kw)


# lengths no block divides (the serving cells' 128-wide buckets), at the
# blocks the chip runs: the call pads to whole blocks and the edge blocks
# mask the keys' true end
RAGGED = [_case(f"s{s},{bq}x{bk}", sq=s, sk=s, bq=bq, bk=bk, d=32)
          for s in (384, 640, 896, 1920) for bq, bk in ((512, 512),
                                                        (512, 1024))]
# the same blocks in row parts of 128: a block ON the causal line multiplies,
# part by part, only the keys up to the part's last row (the chip cuts at
# 1024 and 512 rows)
PARTS = [_case(f"s{s},512x512,parts128", sq=s, sk=s, bq=512, bk=512, d=32,
               part_rows=128) for s in (384, 640, 896, 1920)]
# chunked prefill: fewer queries than keys (the causal line's offset), and
# a key length no block divides
OFFSET = [_case("sq256,sk640,128x256", sq=256, sk=640, bq=128, bk=256, d=32),
          _case("sq384,sk896,256x512", sq=384, sk=896, bq=256, bk=512, d=32,
                h=4, h_kv=2)]


@pytest.mark.parametrize("case", [
    _case("False", causal=False), _case("True"), *RAGGED, *PARTS, *OFFSET,
    _case("sq256,sk768,256x256,parts128", sq=256, sk=768, bq=256, bk=256,
          d=32, part_rows=128),
    _case("not_causal,s640,512x512", causal=False, sq=640, sk=640, bq=512,
          bk=512, d=32),
    _case("not_causal,s640,512x512,parts128", causal=False, sq=640, sk=640,
          bq=512, bk=512, d=32, part_rows=128)])
def test_fwd_matches_xla(case, part_rows):
    part_rows(case)
    q, k, v = make_qkv(**case["shape"])
    out = _run(case, q, k, v)
    ref = _sdpa_xla(q, k, v, causal=case["causal"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fwd_gqa():
    q, k, v = make_qkv(h=4, h_kv=2)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                 block_q=64, block_k=64)
    ref = _sdpa_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fwd_rectangular():
    """sq != sk (bottom-right aligned causal)."""
    q, k, v = make_qkv(sq=64, sk=128)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                 block_q=32, block_k=64)
    ref = _sdpa_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# a KV head's query heads share ONE accumulator of dk and dv: in VMEM
# scratch for the whole head (one pass) or a block at a time (two passes)
GROUPS = [_case(f"group{g},{form}", sq=128, sk=128, h=g, h_kv=1, d=32,
                bq=32, bk=64, budget=budget)
          for g in (4, 16, 20)
          for form, budget in (("one_pass", None), ("two_pass", 0))]


@pytest.mark.parametrize("case", [
    _case("False", causal=False, sq=64, sk=64, d=32, bq=32, bk=32),
    _case("True", sq=64, sk=64, d=32, bq=32, bk=32), *RAGGED, *PARTS,
    *OFFSET,
    _case("s640,512x512,parts128,two_pass", sq=640, sk=640, bq=512, bk=512,
          d=32, part_rows=128, budget=0),
    _case("sq256,sk768,256x256,parts128", sq=256, sk=768, bq=256, bk=256,
          d=32, part_rows=128, h=4, h_kv=2),
    _case("sq256,sk640,two_pass", sq=256, sk=640, bq=128, bk=256, d=32,
          budget=0),
    _case("not_causal,s640,two_pass", causal=False, sq=640, sk=640, bq=512,
          bk=512, d=32, budget=0),
    *GROUPS])
def test_grads_match_xla(case, part_rows):
    part_rows(case)
    causal = case["causal"]
    q, k, v = make_qkv(**case["shape"])
    assert flash_plan(
        q.shape[1], k.shape[1], q.shape[3], causal,
        q.shape[2] // k.shape[2], block_q=case["bq"], block_k=case["bk"],
        dtype="float32", **({} if case["budget"] is None else
                            {"vmem_budget": case["budget"]})
    ).backward == ("two_pass" if case["budget"] == 0 else "one_pass")

    def loss_pallas(q, k, v):
        o = _run(case, q, k, v)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = _sdpa_xla(q, k, v, causal=causal)
        return jnp.sum(o * o)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name} mismatch")


def test_grads_gqa():
    q, k, v = make_qkv(sq=64, sk=64, h=4, h_kv=2, d=32)

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, k, v) ** 2)
        return f

    fp = loss(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, interpret=True, block_q=32, block_k=32))
    fr = loss(lambda q, k, v: _sdpa_xla(q, k, v, causal=True))
    gp = jax.grad(fp, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


def test_bf16_fwd_close():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                 block_q=64, block_k=64)
    ref = _sdpa_xla(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_fallback_when_unsupported():
    q, k, v = make_qkv(sq=100, sk=100)  # one block of 100 rows: not 8-aligned
    assert not pallas_supported(q, k, v, None, 0.0, True)
    # a length no block divides is the kernel's (it pads to whole blocks)
    assert pallas_supported(*make_qkv(sq=1920, sk=1920), None, 0.0, True,
                            block_q=512, block_k=1024)
    # causal sq > sk would leave uninitialized online-softmax rows
    q2, k2, v2 = make_qkv(sq=128, sk=64)
    assert not pallas_supported(q2, k2, v2, None, 0.0, True)
    assert pallas_supported(q2, k2, v2, None, 0.0, False)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    ref = _sdpa_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_long_seq_multi_block():
    """Multiple q and kv blocks exercising the online-softmax carry."""
    q, k, v = make_qkv(sq=256, sk=256, d=32)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                 block_q=64, block_k=64)
    ref = _sdpa_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# segment-ids (varlen / packed sequences) — reference: flash_attn varlen
# entry (phi/kernels/gpu/flash_attn_kernel.cu:91, cu_seqlens API)
# ---------------------------------------------------------------------------

def _seg_ref(q, k, v, seg_q, seg_kv, causal):
    """Dense-mask oracle for segment attention."""
    from paddle_tpu.ops.attention import _sdpa_xla
    mask = (np.asarray(seg_q)[:, :, None] == np.asarray(seg_kv)[:, None, :])
    return _sdpa_xla(q, k, v, attn_mask=jnp.asarray(mask)[:, None],
                     causal=causal)


@pytest.mark.parametrize("causal,s,block", [
    (False, 64, 16), (True, 64, 16),
    # documents that cross from interior blocks into edge ones, and a
    # length the blocks do not divide
    (True, 80, 32), (False, 80, 32)], ids=[
        "False", "True", "True,s80,32x32", "False,s80,32x32"])
def test_segment_fwd_matches_dense_mask(causal, s, block):
    q, k, v = make_qkv(b=1, sq=s, sk=s, h=4, h_kv=4, d=32, seed=10)
    # two packed sequences + a padding tail with its own id
    seg = np.zeros((1, s), np.int32)
    seg[:, 24:52] = 1
    seg[:, 52:] = 2
    out = flash_attention_pallas(q, k, v, causal=causal, interpret=True,
                                 segment_ids=jnp.asarray(seg),
                                 block_q=block, block_k=block)
    ref = _seg_ref(q, k, v, seg, seg, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s,budget", [(32, None), (40, None), (40, 0)],
                         ids=["s32", "s40,one_pass", "s40,two_pass"])
def test_segment_grads_match_dense_mask(s, budget):
    """Documents across interior, edge and dead blocks; 40 is a length the
    blocks of 16 do not divide."""
    q, k, v = make_qkv(b=2, sq=s, sk=s, h=2, h_kv=2, d=32, seed=11)
    seg = np.zeros((2, s), np.int32)
    seg[0, 20:] = 1
    seg[1, 8:] = 3
    kw = {} if budget is None else {"vmem_budget": budget}

    def loss_pallas(q, k, v):
        o = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                   segment_ids=jnp.asarray(seg),
                                   block_q=16, block_k=16, **kw)
        return (o.astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        o = _seg_ref(q, k, v, seg, seg, True)
        return (o.astype(jnp.float32) ** 2).sum()

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_segment_gqa_grads():
    """GQA + segments together (dk/dv accumulate at kv-head resolution)."""
    q, k, v = make_qkv(b=1, sq=32, sk=32, h=4, h_kv=2, d=32, seed=12)
    seg = np.zeros((1, 32), np.int32)
    seg[:, 16:] = 1

    def loss_pallas(q, k, v):
        o = flash_attention_pallas(q, k, v, causal=False, interpret=True,
                                   segment_ids=jnp.asarray(seg),
                                   block_q=16, block_k=16)
        return (o.astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        o = _seg_ref(q, k, v, seg, seg, False)
        return (o.astype(jnp.float32) ** 2).sum()

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_segment_cross_attention_pair():
    q, k, v = make_qkv(b=1, sq=32, sk=64, h=2, h_kv=2, d=32, seed=13)
    sq = np.zeros((1, 32), np.int32); sq[:, 16:] = 1
    sk = np.zeros((1, 64), np.int32); sk[:, 40:] = 1
    out = flash_attention_pallas(q, k, v, causal=False, interpret=True,
                                 segment_ids=(jnp.asarray(sq), jnp.asarray(sk)),
                                 block_q=16, block_k=16)
    ref = _seg_ref(q, k, v, sq, sk, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_segment_fully_masked_rows():
    """Query rows whose segment id matches NO kv position must output
    exactly zero and produce zero grads (online-softmax NEG_INF edge)."""
    q, k, v = make_qkv(b=1, sq=32, sk=32, h=2, h_kv=2, d=32, seed=14)
    sq_ids = np.zeros((1, 32), np.int32)
    sq_ids[:, 16:] = 7            # id 7 absent from kv ids
    sk_ids = np.zeros((1, 32), np.int32)

    out = flash_attention_pallas(
        q, k, v, causal=False, interpret=True,
        segment_ids=(jnp.asarray(sq_ids), jnp.asarray(sk_ids)),
        block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out)[0, 16:], 0.0, atol=1e-6)

    def loss(q, k, v):
        o = flash_attention_pallas(
            q, k, v, causal=False, interpret=True,
            segment_ids=(jnp.asarray(sq_ids), jnp.asarray(sk_ids)),
            block_q=16, block_k=16)
        return (o[:, 16:].astype(jnp.float32) ** 2).sum() * 0 + \
            (o.astype(jnp.float32) ** 2).sum()

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # masked-row queries get zero grad; kv grads exist only from live rows
    np.testing.assert_allclose(np.asarray(gq)[0, 16:], 0.0, atol=1e-5)
    assert np.isfinite(np.asarray(gk)).all()


def test_additive_float_mask_with_segments_fallback():
    """attn_mask (additive float) + segment_ids goes down the XLA fallback
    and must combine, not crash."""
    q, k, v = make_qkv(b=1, sq=24, sk=24, h=2, h_kv=2, d=32, seed=15)
    seg = np.zeros((1, 24), np.int32)
    seg[:, 12:] = 1
    add_mask = jnp.zeros((1, 1, 24, 24), jnp.float32).at[..., :4].set(-1e9)
    out = flash_attention_pallas(q, k, v, attn_mask=add_mask,
                                 segment_ids=jnp.asarray(seg))
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# in-kernel dropout (reference: the philox dropout path of
# phi/kernels/gpu/flash_attn_kernel.cu) — counter-based PRNG seeded on
# semantic block coordinates so fwd/bwd replay identical masks
# ---------------------------------------------------------------------------

def _drop(q, k, v, p, seed, **kw):
    return flash_attention_pallas(q, k, v, dropout_p=p, dropout_seed=seed,
                                  interpret=True, block_q=64, block_k=64,
                                  **kw)


def test_dropout_deterministic_per_seed():
    q, k, v = make_qkv(b=2, h=2, seed=21)
    a = _drop(q, k, v, 0.3, 7, causal=True)
    b = _drop(q, k, v, 0.3, 7, causal=True)
    c = _drop(q, k, v, 0.3, 8, causal=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(a) - np.asarray(c)).max() > 1e-4


def test_dropout_zero_p_matches_baseline():
    q, k, v = make_qkv(seed=22)
    base = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                  block_q=64, block_k=64)
    out = _drop(q, k, v, 0.0, 3, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.slow     # 48 seeds: the full-matrix tier
def test_dropout_is_unbiased():
    """E[dropout(P)] = P, so averaging outputs over many seeds approaches
    the no-dropout output."""
    q, k, v = make_qkv(b=1, sq=64, sk=64, h=2, d=32, seed=23)
    base = np.asarray(flash_attention_pallas(
        q, k, v, interpret=True, block_q=64, block_k=64), np.float64)
    acc = np.zeros_like(base)
    n = 48
    for s in range(n):
        acc += np.asarray(_drop(q, k, v, 0.4, s), np.float64)
    err = np.abs(acc / n - base).max()
    assert err < 0.15, err   # ~1/sqrt(48) monte-carlo noise on O(1) values


@pytest.mark.parametrize("s,block", [(64, 64), (80, 32)],
                         ids=["s64,64x64", "s80,32x32"])
def test_dropout_grads_finite_and_deterministic(s, block):
    """The same keep-mask in the forward and in the backward, whichever
    class a block is in (80 rows in blocks of 32: interior, edge and dead
    ones, and a padded end) and whichever form the backward takes."""
    q, k, v = make_qkv(b=1, sq=s, sk=s, h=2, d=32, seed=24)

    def loss(q, k, v, seed, **kw):
        o = flash_attention_pallas(
            q, k, v, dropout_p=0.25, dropout_seed=seed, interpret=True,
            block_q=block, block_k=block, causal=True, **kw)
        return (o.astype(jnp.float32) ** 2).sum()

    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, 11)
    g2 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, 11)
    two = jax.grad(functools.partial(loss, vmem_budget=0),
                   argnums=(0, 1, 2))(q, k, v, 11)
    for a, b, c in zip(g1, g2, two):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s,kw", [
    (32, {}), (40, dict(causal=True, block_q=16, block_k=16))],
    ids=["s32", "s40,causal,16x16"])
def test_dropout_grad_matches_finite_difference(s, kw):
    """The custom VJP with dropout must be the true derivative of the
    (fixed-seed) forward: check dq against central differences."""
    q, k, v = make_qkv(b=1, sq=s, sk=s, h=1, d=32, seed=25)
    q = q.astype(jnp.float64) if jax.config.jax_enable_x64 else q
    drop = functools.partial(flash_attention_pallas, **dict(
        dict(dropout_p=0.3, dropout_seed=5, interpret=True, block_q=64,
             block_k=64), **kw))

    def f(q):
        return float(jnp.sum(drop(q, k, v).astype(jnp.float32)))

    g = jax.grad(lambda q: jnp.sum(drop(q, k, v).astype(jnp.float32)))(q)
    rs = np.random.RandomState(0)
    for _ in range(3):
        i = tuple(rs.randint(0, s) for s in q.shape)
        eps = 1e-2
        qp = np.asarray(q, np.float64); qp[i] += eps
        qm = np.asarray(q, np.float64); qm[i] -= eps
        fd = (f(jnp.asarray(qp, q.dtype)) - f(jnp.asarray(qm, q.dtype))) / (2 * eps)
        np.testing.assert_allclose(np.asarray(g)[i], fd, rtol=5e-2, atol=5e-3)


def test_dropout_with_segments():
    """Dropout composes with segment masking: cross-segment positions stay
    exactly masked regardless of the keep-mask."""
    q, k, v = make_qkv(b=1, sq=64, sk=64, h=2, d=32, seed=26)
    ids = np.zeros((1, 64), np.int32)
    ids[:, 32:] = 1
    out = flash_attention_pallas(
        q, k, v, dropout_p=0.3, dropout_seed=2, interpret=True,
        segment_ids=jnp.asarray(ids), block_q=64, block_k=64)
    # rows in segment 0 must not see any v from segment 1: zero out v's
    # second half and the first half of the output must be unchanged
    v2 = v.at[:, 32:].set(0.0)
    out2 = flash_attention_pallas(
        q, k, v2, dropout_p=0.3, dropout_seed=2, interpret=True,
        segment_ids=jnp.asarray(ids), block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out)[:, :32],
                               np.asarray(out2)[:, :32], rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# the plan: which blocks a call runs, and that the kernels run just those
# ---------------------------------------------------------------------------

def _grids(fn, *args):
    """{kernel name: grid} of the pallas calls ``fn`` traces to."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_the_plan_counts_the_training_cells_blocks(monkeypatch):
    """``olmoe.pretrain-4k``'s call (4,096 on 4,096, heads of 128). Before
    PR 42: 16 blocks a head of 1024 x 1024, 6 of them wholly above the
    causal line and every one a grid step fed with K and V, 10 masked.
    Since: the rule's 2048 x 2048 cut 4 blocks, 1 dead (no step, no copy), 1
    wholly below the line (no mask) and 2 on it, each run in row parts (4
    of 512 rows forward, 8 of 256 backward) against the keys up to the
    part's last row; a KV head's dk and dv (4 MiB of float32, 2 MiB of
    output blocks twice) stay in VMEM: one pass."""
    before = flash_plan(4096, 4096, 128, True, 1, block_q=1024, block_k=1024)
    assert before[:7] == (1024, 1024, 4, 4, 6, 4, 6)
    assert autotune._default_blocks(4096, 4096, 128) == (2048, 2048)
    monkeypatch.setattr("paddle_tpu.ops.registry.backend_kind", lambda: "tpu")
    monkeypatch.setattr(autotune, "_device_kind",
                        lambda default="cpu": "TPU v5 lite")
    plan = flash_plan(4096, 4096, 128, True, 1)
    assert plan == (2048, 2048, 2, 2, 1, 2, 1, 4, 8, "one_pass", 1)
    assert plan.interior + plan.edge + plan.dead == plan.nq * plan.nk
    # a block of up to 1024 rows runs whole in the forward
    assert flash_plan(896, 896, 128, True, block_q=896,
                      block_k=896)[7:9] == (1, 4)
    # 512 x 512: 28 of 36 live blocks wholly below the line
    assert flash_plan(4096, 4096, 128, True, block_q=512,
                      block_k=512)[4:7] == (28, 8, 28)
    # not causal: nothing is dead, and only a ragged end is an edge
    assert flash_plan(4096, 4096, 128, False, block_q=1024,
                      block_k=1024)[4:7] == (16, 0, 0)
    assert flash_plan(1920, 1920, 128, False, block_q=1024,
                      block_k=1024)[4:7] == (2, 2, 0)
    # a KV head too long for VMEM keeps the two-kernel backward
    assert flash_plan(16384, 16384, 128, True).backward == "one_pass"
    assert flash_plan(65536, 65536, 128, True).backward == "two_pass"


def test_a_block_on_the_causal_line_multiplies_the_keys_below_it():
    """The row parts of a step: cut at whole 128s; a part of an edge block
    that lies ON the causal line (equal blocks, an offset of whole blocks)
    takes the keys before its own end row, any other block's part all of
    them; a call with dropout runs whole blocks (its keep-mask is drawn a
    block at a time)."""
    plan = flash_plan(4096, 4096, 128, True, block_q=2048, block_k=2048)
    geom = dict(sq=4096, sk=4096, causal=True)
    assert flash._block_parts(plan, plan.fwd_parts, True, 0.0, **geom) == [
        (0, 512, 512), (512, 1024, 1024), (1024, 1536, 1536),
        (1536, 2048, 2048)]
    assert flash._block_parts(plan, plan.fwd_parts, False, 0.0, **geom) == [
        (0, 512, 2048), (512, 1024, 2048), (1024, 1536, 2048),
        (1536, 2048, 2048)]
    assert flash._block_parts(plan, plan.fwd_parts, True, 0.1, **geom) == [
        (0, 2048, 2048)]
    # executed score pairs a head: 8.9 M of the 10.5 M that 1024 x 1024
    # blocks multiplied, for 8.4 M required
    pairs = sum((b - a) * keys for edge in (True, True, False)
                for a, b, keys in flash._block_parts(plan, 8, edge, 0.0,
                                                     **geom))
    assert pairs == 2 * 2359296 + 2048 * 2048 == 8912896
    # 1,664 rows in four parts: 512 + 384 + 384 + 384
    odd = flash_plan(1664, 1664, 128, True, block_q=1664, block_k=1664)
    assert [b - a for a, b, _ in flash._block_parts(
        odd, odd.fwd_parts, True, 0.0, sq=1664, sk=1664, causal=True)] == [
        512, 384, 384, 384]
    # unequal blocks: the line crosses a block anywhere, no part is clipped
    wide = flash_plan(4096, 4096, 128, True, block_q=2048, block_k=1024)
    assert {keys for _, _, keys in flash._block_parts(
        wide, 4, True, 0.0, **geom)} == {1024}


@pytest.mark.parametrize("s,blocks", [
    (384, (384, 384)), (896, (896, 896)), (1664, (1664, 1664)),
    (1920, (1920, 1920)), (2688, (1408, 1408)), (3840, (1920, 1920)),
    (4096, (2048, 2048))])
def test_the_rule_cuts_every_length_into_wide_blocks(s, blocks):
    """One rule for every length: one block up to 2,048, a longer length
    cut evenly in whole 128s (the serving cells' buckets ran 128 x 128
    blocks wherever 512 did not divide them)."""
    assert autotune._default_blocks(s, s, 128) == blocks
    assert autotune._default_blocks(s, s, 256) == blocks
    plan = flash_plan(s, s, 128, True, block_q=blocks[0], block_k=blocks[1])
    assert (plan.nq * plan.block_q - s) < 128 * plan.nq
    assert plan.dead == plan.nq * (plan.nq - 1) // 2


@pytest.mark.parametrize("budget,names", [
    (None, {"flash_attention_fwd", "flash_attention_bwd"}),
    (0, {"flash_attention_fwd", "flash_attention_bwd_dq",
         "flash_attention_bwd_dkv"})], ids=["one_pass", "two_pass"])
def test_the_kernels_launch_the_plans_grid(budget, names):
    """The grids are built FROM the plan: (b, h, nq, nk) forward, (b, h_kv,
    group x nq, nk) for the one-pass backward and the dq kernel, (b, h_kv,
    nk, group x nq) for the dkv kernel."""
    q, k, v = make_qkv(b=2, sq=320, sk=320, h=4, h_kv=2, d=32)
    kw = {} if budget is None else {"vmem_budget": budget}
    plan = flash_plan(320, 320, 32, True, 2, block_q=128, block_k=256,
                      dtype="float32", **kw)
    assert (plan.nq, plan.nk) == (3, 2)

    def loss(q, k, v):
        return flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                      block_q=128, block_k=256, **kw).sum()
    grids = _grids(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert set(grids) == names
    assert grids["flash_attention_fwd"] == (2, 4, plan.nq, plan.nk)
    for name in names - {"flash_attention_fwd", "flash_attention_bwd_dkv"}:
        assert grids[name] == (2, 2, 2 * plan.nq, plan.nk)
    if budget == 0:
        assert grids["flash_attention_bwd_dkv"] == (2, 2, plan.nk,
                                                    2 * plan.nq)


def test_a_fully_masked_row_keeps_lse_at_neg_inf_for_the_rings_merge():
    """A query whose document has no key in THIS block of the ring comes
    back with lse = NEG_INF (weight zero in the ring's merge) and an output
    of zeros, in an interior block as in an edge one."""
    q, k, v = make_qkv(b=1, sq=64, sk=64, h=2, h_kv=2, d=32, seed=30)
    q_seg = np.zeros((1, 64), np.int32)
    q_seg[:, 40:] = 5                       # no key carries id 5
    kv_seg = np.zeros((1, 64), np.int32)
    for causal in (False, True):
        out, lse = flash_fwd_block(q, k, v, 32 ** -0.5, causal, 16, 16,
                                   interpret=True, q_seg=jnp.asarray(q_seg),
                                   kv_seg=jnp.asarray(kv_seg))
        assert (np.asarray(lse)[:, :, 40:] == np.float32(NEG_INF)).all()
        assert np.isfinite(np.asarray(lse)[:, :, :40]).all()
        np.testing.assert_array_equal(np.asarray(out)[:, 40:], 0.0)
