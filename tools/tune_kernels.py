#!/usr/bin/env python
"""Pallas kernel autotune sweep + microbenchmark.

Reference analogue: tools/ci_op_benchmark.sh + check_op_benchmark_result.py
(the op perf-gating culture) and phi/kernels/autotune (runtime block-config
tuning, here done offline into a persistent DB like CINN's
auto_schedule/database).

On the chip:
  - sweeps (block_q, block_k) for flash attention fwd and fwd+bwd over the
    headline shapes and records the fastest config per (shape, dtype,
    device) — into the file named by --out, or into the in-repo DB with
    --write-shipped (nothing is written otherwise);
  - microbenches pallas-vs-XLA for flash attention and paged decode,
    printing one JSON line per case, so regressions are diffable (the
    in-repo analogue of ci_op_benchmark.sh).

Without a TPU it fails. --interpret validates the sweep machinery on any
backend with one tiny case in Pallas interpret mode (no timings recorded).

Usage:
    python tools/tune_kernels.py [--quick] [--out PATH] [--write-shipped]
    python tools/tune_kernels.py --interpret
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(r):
    import jax
    jax.block_until_ready(r)


def _time_fn(fn, *args, iters=5, warmup=2, reps=3):
    """Median over ``reps`` of (time of ``iters`` back-to-back dispatches,
    one sync) / iters — the host round-trip of a sync is amortized across
    a batch of queued executions."""
    for _ in range(warmup):
        r = fn(*args)
    _sync(r)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*args)
        _sync(r)
        ts.append((time.perf_counter() - t0) / iters)
    return statistics.median(ts)


def _mk_qkv(b, s, h, h_kv, d, dtype, seed=0):
    import jax.numpy as jnp
    import numpy as np
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.normal(0, 1, (b, s, h, d)), dtype)
    k = jnp.asarray(rs.normal(0, 1, (b, s, h_kv, d)), dtype)
    v = jnp.asarray(rs.normal(0, 1, (b, s, h_kv, d)), dtype)
    return q, k, v


def sweep_flash(shapes, candidates, interpret, record_db, quick=False):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import _sdpa_xla
    from paddle_tpu.ops.pallas.autotune import TuneDB, get_db
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas

    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    db = get_db()
    results = []
    for (b, s, h, h_kv, d, dtype, causal) in shapes:
        q, k, v = _mk_qkv(b, s, h, h_kv, d, dtype)

        def grad_of(attn):
            def loss(q, k, v):
                return attn(q, k, v).astype(jnp.float32).sum()
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        best = {}
        for mode in ("fwd", "fwdbwd"):
            timings = {}
            for (bq, bk) in candidates:
                if s % bq or s % bk:
                    continue
                attn = functools.partial(flash_attention_pallas,
                                         causal=causal, block_q=bq,
                                         block_k=bk, interpret=interpret)
                try:
                    fn = (jax.jit(attn) if mode == "fwd"
                          else grad_of(attn))
                    dt = _time_fn(fn, q, k, v,
                                  iters=2 if interpret else 10,
                                  warmup=1 if interpret else 2,
                                  reps=1 if interpret else 3)
                    timings[(bq, bk)] = dt
                except Exception as e:  # config invalid on this hw
                    print(f"  skip bq={bq} bk={bk}: "
                          f"{type(e).__name__}: {str(e)[:120]}",
                          file=sys.stderr)
            if not timings:
                continue
            (bq, bk), dt = min(timings.items(), key=lambda kv: kv[1])
            best[mode] = {"block_q": bq, "block_k": bk, "us": dt * 1e6}

            # XLA baseline for the microbench comparison; the dense [s, s]
            # score tensor OOMs at long seq (8GB at s=8K) — that is the
            # point of the flash kernel, so report pallas-only there
            try:
                xattn = functools.partial(_sdpa_xla, causal=causal)
                xfn = jax.jit(xattn) if mode == "fwd" else grad_of(xattn)
                xdt = _time_fn(xfn, q, k, v,
                               iters=2 if interpret else 10,
                               warmup=1 if interpret else 2,
                               reps=1 if interpret else 3)
            except Exception as e:
                print(f"  xla baseline failed (s={s}): "
                      f"{type(e).__name__}: {str(e)[:100]}", file=sys.stderr)
                xdt = None
            line = {"bench": f"flash_attention_{mode}",
                    "shape": f"b{b}_s{s}_h{h}x{h_kv}_d{d}",
                    "dtype": str(q.dtype),
                    "causal": causal, "device": kind,
                    "pallas_us": round(dt * 1e6, 1),
                    "xla_us": round(xdt * 1e6, 1) if xdt else None,
                    "speedup": round(xdt / dt, 3) if xdt else None,
                    "best_block": [bq, bk]}
            results.append(line)
            print(json.dumps(line))
        if record_db and "fwdbwd" in best:
            # fwd+bwd is the training-path config — that's what dispatch uses
            key = TuneDB.key("flash_attention", kind, str(q.dtype),
                             sq=s, sk=s, d=d, causal=int(causal))
            db.record(key, {"block_q": best["fwdbwd"]["block_q"],
                            "block_k": best["fwdbwd"]["block_k"],
                            "us": round(best["fwdbwd"]["us"], 1)})
    return results


def bench_paged_decode(interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    rs = np.random.RandomState(0)
    B, H, H_kv, D = 8, 8, 2, 128
    page, npages, per_seq = 128, 256, 16   # up to 2048 ctx
    dt = jnp.bfloat16
    q = jnp.asarray(rs.normal(0, 1, (B, H, D)), dt)
    # head-major pools [H_kv, num_pages, page_size, D]
    kp = jnp.asarray(rs.normal(0, 1, (H_kv, npages, page, D)), dt)
    vp = jnp.asarray(rs.normal(0, 1, (H_kv, npages, page, D)), dt)
    tables = jnp.asarray(rs.permutation(npages)[:B * per_seq]
                         .reshape(B, per_seq).astype(np.int32))
    lens = jnp.full((B,), page * per_seq - 2, jnp.int32)

    pfn = jax.jit(functools.partial(paged_decode_attention,
                                    interpret=interpret))
    pdt = _time_fn(pfn, q, kp, vp, tables, lens,
                   iters=2 if interpret else 20, warmup=1 if interpret else 3,
                   reps=1 if interpret else 3)

    def xla(q, kp, vp, tables, lens):
        T = per_seq * page
        ks = jnp.moveaxis(
            kp[:, jnp.maximum(tables, 0)].reshape(H_kv, B, T, D), 0, 2)
        vs = jnp.moveaxis(
            vp[:, jnp.maximum(tables, 0)].reshape(H_kv, B, T, D), 0, 2)
        ks = jnp.repeat(ks, H // H_kv, axis=2)
        vs = jnp.repeat(vs, H // H_kv, axis=2)
        lg = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                        ks.astype(jnp.float32)) / np.sqrt(D)
        lg = jnp.where(jnp.arange(T)[None, None, :] <= lens[:, None, None],
                       lg, -jnp.inf)
        p = jax.nn.softmax(lg, axis=-1)
        return jnp.einsum("bht,bthd->bhd", p, vs.astype(jnp.float32))

    xfn = jax.jit(xla)
    xdt = _time_fn(xfn, q, kp, vp, tables, lens,
                   iters=2 if interpret else 20, warmup=1 if interpret else 3,
                   reps=1 if interpret else 3)
    line = {"bench": "paged_decode", "device": kind,
            "shape": f"b{B}_h{H}x{H_kv}_d{D}_ctx{page * per_seq}",
            "pallas_us": round(pdt * 1e6, 1), "xla_us": round(xdt * 1e6, 1),
            "speedup": round(xdt / pdt, 3)}
    print(json.dumps(line))
    return [line]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--write-shipped", action="store_true",
                    help="write results into the in-repo tune_db.json")
    ap.add_argument("--out", default=None,
                    help="write results into this JSON file instead")
    ap.add_argument("--interpret", action="store_true",
                    help="validate the sweep machinery in Pallas interpret "
                         "mode (any backend, nothing recorded)")
    args = ap.parse_args()

    interpret = args.interpret
    if not interpret:
        from paddle_tpu.core.compile_cache import configure_compilation_cache
        from paddle_tpu.ops.registry import require_tpu
        configure_compilation_cache()
        require_tpu()

    import jax.numpy as jnp
    if interpret or args.quick:
        shapes = [(1, 256, 2, 2, 64, jnp.float32, True)]
        candidates = [(128, 128), (128, 256)]
    else:
        shapes = [
            (8, 2048, 12, 4, 128, jnp.bfloat16, True),    # bench.py shape
            (4, 4096, 12, 4, 128, jnp.bfloat16, True),
            (1, 8192, 32, 8, 128, jnp.bfloat16, True),    # Llama-3-8B @ 8K
            (8, 2048, 16, 16, 64, jnp.bfloat16, True),
            (4, 2048, 12, 4, 128, jnp.bfloat16, False),
        ]
        candidates = [(bq, bk) for bq in (128, 256, 512, 1024)
                      for bk in (128, 256, 512, 1024)]

    results = sweep_flash(shapes, candidates, interpret,
                          record_db=not interpret, quick=args.quick)
    results += bench_paged_decode(interpret)

    from paddle_tpu.ops.pallas.autotune import get_db
    if not interpret:
        if args.out:
            get_db().save(args.out)
        if args.write_shipped:
            get_db().save()
    print(json.dumps({"tuned": not interpret, "cases": len(results)}))


if __name__ == "__main__":
    main()
