"""Pallas paged-KV decode attention vs the XLA gather composition.

Oracle: the dense softmax over gathered pages (the existing
incubate block_multihead_attention math — itself validated against the
reference semantics of block_multi_head_attention_kernel.cu)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.pallas.mosaic.interpret import (
    interpret_pallas_call as mosaic_interpret)
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.paged_attention import (paged_decode_attention,
                                                   paged_decode_supported,
                                                   paged_decode_xla)

slow = pytest.mark.slow  # full-matrix tier; default run stays <5min


def _setup(B=2, H=4, H_kv=2, D=32, page_size=16, pages_per_seq=4,
           num_pages=16, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.normal(0, 1, (B, H, D)).astype(np.float32))
    # head-major pools [H_kv, num_pages, page_size, D] (TPU-native layout)
    k_pages = jnp.asarray(
        rs.normal(0, 1, (H_kv, num_pages, page_size, D)).astype(np.float32))
    v_pages = jnp.asarray(
        rs.normal(0, 1, (H_kv, num_pages, page_size, D)).astype(np.float32))
    # distinct pools per sequence, permuted to exercise the indirection
    perm = rs.permutation(num_pages)[:B * pages_per_seq]
    tables = jnp.asarray(perm.reshape(B, pages_per_seq).astype(np.int32))
    lens = jnp.asarray(rs.randint(0, page_size * pages_per_seq - 1, (B,))
                       .astype(np.int32))
    return q, k_pages, v_pages, tables, lens


def _xla_ref(q, k_pages, v_pages, tables, lens):
    B, H, D = q.shape
    H_kv = k_pages.shape[0]
    page_size = k_pages.shape[2]
    T = tables.shape[1] * page_size
    group = H // H_kv
    k_seq = jnp.moveaxis(
        k_pages[:, jnp.maximum(tables, 0)].reshape(H_kv, B, T, D), 0, 2)
    v_seq = jnp.moveaxis(
        v_pages[:, jnp.maximum(tables, 0)].reshape(H_kv, B, T, D), 0, 2)
    k_seq = jnp.repeat(k_seq, group, axis=2)
    v_seq = jnp.repeat(v_seq, group, axis=2)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                        k_seq.astype(jnp.float32)) * scale
    valid = jnp.arange(T)[None, None, :] <= lens[:, None, None]
    logits = jnp.where(valid, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p, v_seq.astype(jnp.float32))
    return out.astype(q.dtype)


@slow
@pytest.mark.parametrize("H,H_kv", [(4, 4), (4, 2), (8, 1)])
def test_paged_decode_matches_xla(H, H_kv):
    q, kp, vp, tables, lens = _setup(H=H, H_kv=H_kv, seed=H * 10 + H_kv)
    out = paged_decode_attention(q, kp, vp, tables, lens, interpret=True)
    ref = _xla_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@slow
def test_paged_decode_short_and_page_boundary_lens():
    q, kp, vp, tables, _ = _setup(B=4, seed=3)
    # len 0 (only the new token), exact page boundaries, mid-page
    lens = jnp.asarray(np.array([0, 15, 16, 33], np.int32))
    out = paged_decode_attention(q[:4], kp, vp,
                                 jnp.tile(tables[:1], (4, 1)), lens,
                                 interpret=True)
    ref = _xla_ref(q[:4], kp, vp, jnp.tile(tables[:1], (4, 1)), lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@slow
def test_paged_decode_bf16():
    q, kp, vp, tables, lens = _setup(seed=4)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    out = paged_decode_attention(q, kp, vp, tables, lens, interpret=True)
    ref = _xla_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


@slow
def test_paged_decode_jittable():
    q, kp, vp, tables, lens = _setup(seed=5)
    fn = jax.jit(lambda *a: paged_decode_attention(*a, interpret=True))
    out = fn(q, kp, vp, tables, lens)
    assert out.shape == q.shape


@slow
def test_supported_gate():
    q, kp, *_ = _setup()
    assert paged_decode_supported(q, kp)
    assert not paged_decode_supported(jnp.zeros((1, 3, 48)),
                                      jnp.zeros((1, 4, 16, 48)))


# -- the GQA-grouped fallback (tier 1: the oracle itself is under test) -------

def _plain_f32(q, k_pages, v_pages, tables, lens, k_scales=None,
               v_scales=None):
    """Attention over positions 0..len inclusive, row by row and head by
    head in numpy float32: no gather composition, no grouping."""
    q, kp, vp = (np.asarray(x, np.float32) for x in (q, k_pages, v_pages))
    tables, lens = np.asarray(tables), np.asarray(lens)
    B, H, D = q.shape
    H_kv, _, page, _ = kp.shape
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        n = int(lens[b]) + 1
        pids = np.maximum(tables[b, :-(-n // page)], 0)
        for h in range(H):
            kh = h // (H // H_kv)
            k, v = kp[kh, pids], vp[kh, pids]        # [pages, page, D]
            if k_scales is not None:
                k = k * np.asarray(k_scales)[pids, None, None]
                v = v * np.asarray(v_scales)[pids, None, None]
            k, v = k.reshape(-1, D)[:n], v.reshape(-1, D)[:n]
            s = (k @ q[b, h]) / np.sqrt(D)
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ v
    return out


def _ragged(group, pool, seed, page=16, H_kv=2, per_seq=4, lens=None):
    """B=5 rows over per_seq-page tables: a lone token, a length on a page
    boundary on either side, a full span, and -1 in every unused slot."""
    D = 32
    rs = np.random.RandomState(seed)
    if lens is None:
        lens = [0, page - 1, page, 2 * page + 5, per_seq * page - 1]
    lens = np.array(lens, np.int32)
    B = len(lens)
    num_pages = max(32, B * per_seq)
    tables = rs.permutation(num_pages)[:B * per_seq].reshape(B, per_seq)
    used = -(-(lens + 1) // page)
    tables = np.where(np.arange(per_seq)[None] < used[:, None], tables, -1)
    shape = (H_kv, num_pages, page, D)
    q = rs.normal(0, 1, (B, H_kv * group, D)).astype(np.float32)
    scales = {}
    if pool == "int8":
        kp, vp = (jnp.asarray(rs.randint(-127, 128, shape), jnp.int8)
                  for _ in range(2))
        scales = {"k_scales": jnp.asarray(rs.uniform(.005, .02, num_pages),
                                          jnp.float32),
                  "v_scales": jnp.asarray(rs.uniform(.005, .02, num_pages),
                                          jnp.float32)}
        qd = jnp.bfloat16
    else:
        qd = jnp.dtype(pool)
        kp, vp = (jnp.asarray(rs.normal(0, 1, shape), qd) for _ in range(2))
    return (jnp.asarray(q, qd), kp, vp, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lens)), scales


@pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_grouped_fallback_matches_plain_float32(group, pool):
    args, scales = _ragged(group, pool, seed=group)
    out = paged_decode_xla(*args, **scales)
    assert out.dtype == args[0].dtype and out.shape == args[0].shape
    ref = _plain_f32(*args, **scales)
    tol = 2e-5 if pool == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=tol, atol=tol)


def _zaya_rows(page, per_seq=24):
    """Lengths on either side of every power-of-two count of pages (a
    chunk of the kernel's walk is such a count, whatever it is), a lone
    token and a full table."""
    edges = [page << i for i in range(5)]
    return [0, *(n - d for n in edges for d in (1, 0)), per_seq * page - 1]


_INTERPRET = {
    "interpret": True,
    # unfetched or unwritten memory reads NaN, a copy lands only when it is
    # waited for, and a buffer touched while a copy may write it is reported
    "nan+races": pltpu.InterpretParams(uninitialized_memory="nan",
                                       detect_races=True)}


@pytest.mark.parametrize("mode", list(_INTERPRET))
@pytest.mark.parametrize("pool,H_kv,per_seq", [
    ("float32", 2, 4), ("bfloat16", 2, 4), ("int8", 2, 4),
    # a table wider than its rows' live pages, and two blocks of eight KV
    # heads
    ("float32", 2, 6), ("float32", 16, 6), ("int8", 4, 6),
    # ZAYA's heads (8 query / 2 KV) over a 24-page table: several chunks a
    # row, rows that end on either side of a chunk's edge
    ("bfloat16", 2, 24)])
def test_kernel_interpret_matches_grouped_fallback(pool, H_kv, per_seq, mode):
    # the int8 sublane multiple is 32
    page = 32 if pool == "int8" else 16
    args, scales = _ragged(4 if H_kv < 16 else 1, pool, seed=11, H_kv=H_kv,
                           page=page, per_seq=per_seq,
                           lens=_zaya_rows(page) if per_seq == 24 else None)
    pltpu.reset_tpu_interpret_mode_state()
    out = paged_decode_attention(*args, **scales,
                                 interpret=_INTERPRET[mode])
    ref = paged_decode_xla(*args, **scales)
    tol = 2e-5 if pool == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    if mode == "nan+races":
        assert not mosaic_interpret.races.races_found


def test_kernel_takes_no_operand_per_page():
    """The pools enter the call once each and stay in HBM: a 24-page table
    gives the ``pallas_call`` the operands a 4-page table gives it."""
    def operands(per_seq):
        args, _ = _ragged(4, "bfloat16", seed=3, per_seq=per_seq)
        jaxpr = jax.make_jaxpr(paged_decode_attention)(*args)
        call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        return len(call.invars)
    # tables, lengths, q, K pool, V pool
    assert operands(4) == operands(24) == 5


def _arrays(jaxpr):
    """Every array a jaxpr (and its sub-jaxprs) defines or reads."""
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            if hasattr(v.aval, "shape"):
                yield eqn.primitive.name, v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _arrays(sub)


@pytest.mark.parametrize("pool", ["bfloat16", "int8"])
def test_fallback_reads_kv_once_in_stored_dtype(pool):
    """At the serving cell's shape (32 query / 8 KV heads, 32 rows of 16
    pages of 128) the fallback holds no float32 array of B*T*H*D elements,
    none at all at the query head count of K/V's size, and both products
    contract K/V at the KV head count in a 16-bit-or-narrower dtype."""
    B, H, H_kv, D, page, per_seq, num_pages = 32, 32, 8, 128, 128, 16, 512
    T = per_seq * page
    dt = jnp.dtype(pool)
    S = jax.ShapeDtypeStruct
    args = [S((B, H, D), jnp.bfloat16), S((H_kv, num_pages, page, D), dt),
            S((H_kv, num_pages, page, D), dt), S((B, per_seq), jnp.int32),
            S((B,), jnp.int32)]
    if pool == "int8":
        args += [S((num_pages,), jnp.float32)] * 2
        fn = lambda q, k, v, t, l, ks, vs: paged_decode_xla(
            q, k, v, t, l, k_scales=ks, v_scales=vs)
    else:
        fn = paged_decode_xla
    seen = list(_arrays(jax.make_jaxpr(fn)(*args).jaxpr))
    kv_elems = B * T * H_kv * D
    for prim, aval in seen:
        assert aval.size < B * T * H * D, (prim, aval)
        if aval.size >= kv_elems:
            assert aval.dtype.itemsize <= 2, (prim, aval)
    dots = [a for prim, a in seen if prim == "dot_general"]
    # K in QK^T and V in PV, each at the KV head count
    assert sum(a.size == kv_elems for a in dots) == 2, dots
    # and XLA's own lowering keeps it so: no f32 tensor of K/V's size
    hlo = jax.jit(fn).lower(*args).as_text()
    for m in re.finditer(r"tensor<([\dx]+)xf32>", hlo):
        n = int(np.prod([int(x) for x in m.group(1).split("x")]))
        assert n < kv_elems, m.group(0)
