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
  - microbenches pallas-vs-XLA for flash attention and paged decode
    (--paged-decode: that comparison alone, per context length at the two
    serving cells' shapes, with jax's library kernel beside it), printing
    one JSON line per case, so
    regressions are diffable (the in-repo analogue of
    ci_op_benchmark.sh);
  - --selective-update: a Mamba-1 layer's tick, each of its two kernels
    against XLA's fusion of its twin, alone and inside 26 whole layers
    chained as a tick chains them (the evidence a kernel stays by).

Without a TPU it fails. --interpret validates the sweep machinery on any
backend with one tiny case in Pallas interpret mode (no timings recorded).

Usage:
    python tools/tune_kernels.py [--quick] [--out PATH] [--write-shipped]
    python tools/tune_kernels.py --flash [--out PATH]
    python tools/tune_kernels.py --paged-decode
    python tools/tune_kernels.py --ssm-update
    python tools/tune_kernels.py --selective-update
    python tools/tune_kernels.py --selective-scan
    python tools/tune_kernels.py --power-update
    python tools/tune_kernels.py --power-prefill
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


# the cells' calls (BENCHMARK.json): olmoe.pretrain-4k's training step, and
# the serving cells' prefill buckets at lengths no power of two, with the
# Mistral / OLMoE (32/8), Nemotron (32/2) and Jamba (20/1) head groups
FLASH_CELL_SHAPES = (
    [(8, 4096, 16, 16, 128, "bfloat16", True, ("fwd", "fwdbwd"))]
    + [(1, s, 32, 8, 128, "bfloat16", True, ("fwd",))
       for s in (384, 896, 1664, 1920, 2688, 3840)]
    + [(1, s, h, h_kv, 128, "bfloat16", True, ("fwd",))
       for h, h_kv in ((32, 2), (20, 1)) for s in (1920, 3840)])
FLASH_CANDIDATES = [(512, 512), (512, 1024), (1024, 1024), (1408, 1408),
                    (1920, 1920), (2048, 1024), (2048, 2048), (4096, 4096)]
CHAIN = 4        # calls chained in one program: a layer's call feeds the next


def sweep_flash(shapes, candidates, interpret, record_db, xla=True):
    """Time every candidate block pair of every shape, ``CHAIN`` calls
    chained in one program (a call's output is the next one's queries, so
    the device runs them back to back and the host's dispatch is paid
    once), and print one line a candidate: what the kernel was ASKED to
    make (``flash_plan``: blocks as clipped, the classes' counts, the
    backward's form) beside what it timed. Blocks need not divide the
    length; candidates that clip to the same plan run once, and the rule's
    own choice (``autotune._default_blocks``) always runs."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import _sdpa_xla
    from paddle_tpu.ops.pallas.autotune import (TuneDB, _default_blocks,
                                                get_db)
    from paddle_tpu.ops.pallas.flash_attention import (flash_attention_pallas,
                                                       flash_plan)

    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    db = get_db()
    results = []
    timing = (dict(iters=2, warmup=1, reps=1) if interpret
              else dict(iters=5, warmup=2, reps=3))

    def program(attn, mode):
        def chain(q, k, v):
            for _ in range(CHAIN):
                q = attn(q, k, v)
            return q
        if mode == "fwd":
            return jax.jit(chain)
        return jax.jit(jax.grad(
            lambda q, k, v: chain(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    for (b, s, h, h_kv, d, dtype, causal, modes) in shapes:
        q, k, v = _mk_qkv(b, s, h, h_kv, d, dtype)
        rule = _default_blocks(s, s, d)
        best = {}
        for mode in modes:
            timings = {}
            for (bq, bk) in [rule] + [c for c in candidates if c != rule]:
                plan = flash_plan(s, s, d, causal, h // h_kv, block_q=bq,
                                  block_k=bk, dtype=str(q.dtype))
                if plan in timings:
                    continue
                attn = functools.partial(
                    flash_attention_pallas, causal=causal, block_q=bq,
                    block_k=bk, interpret=interpret)
                line = {"bench": f"flash_attention_{mode}",
                        "shape": f"b{b}_s{s}_h{h}x{h_kv}_d{d}",
                        "dtype": str(q.dtype), "causal": causal,
                        "device": kind, "rule": (bq, bk) == rule,
                        **plan._asdict()}
                try:
                    dt = _time_fn(program(attn, mode), q, k, v,
                                  **timing) / CHAIN
                    timings[plan] = dt
                    line["pallas_us"] = round(dt * 1e6, 1)
                except Exception as e:  # config invalid on this hw
                    line["skipped"] = f"{type(e).__name__}: {str(e)[:120]}"
                results.append(line)
                print(json.dumps(line), flush=True)
            if not timings:
                continue
            plan, dt = min(timings.items(), key=lambda kv: kv[1])
            best[mode] = {"block_q": plan.block_q, "block_k": plan.block_k,
                          "us": dt * 1e6}
            if not xla:
                continue
            # XLA baseline for the microbench comparison; the dense [s, s]
            # score tensor OOMs at long seq (8GB at s=8K) — that is the
            # point of the flash kernel, so report pallas-only there
            try:
                xdt = _time_fn(
                    program(functools.partial(_sdpa_xla, causal=causal),
                            mode), q, k, v, **timing) / CHAIN
            except Exception as e:
                print(f"  xla baseline failed (s={s}): "
                      f"{type(e).__name__}: {str(e)[:100]}", file=sys.stderr)
                continue
            line = {"bench": f"flash_attention_{mode}_vs_xla",
                    "shape": f"b{b}_s{s}_h{h}x{h_kv}_d{d}",
                    "pallas_us": round(dt * 1e6, 1),
                    "xla_us": round(xdt * 1e6, 1),
                    "speedup": round(xdt / dt, 3),
                    "best_block": [plan.block_q, plan.block_k]}
            results.append(line)
            print(json.dumps(line), flush=True)
        if record_db and "fwdbwd" in best:
            # fwd+bwd is the training-path config — that's what dispatch uses
            key = TuneDB.key("flash_attention", kind, str(q.dtype),
                             sq=s, sk=s, d=d, causal=int(causal))
            db.record(key, {"block_q": best["fwdbwd"]["block_q"],
                            "block_k": best["fwdbwd"]["block_k"],
                            "us": round(best["fwdbwd"]["us"], 1)})
    return results


def _ragged_batch_decode(rs, B, span):
    """``mistral-7b.batch-decode``'s rows: log-uniform over 5/64-5/8 of
    the table's span (160-1280 of 2048 tokens)."""
    import numpy as np
    return np.exp(rs.uniform(np.log(span * 5 / 64), np.log(span * 5 / 8), B))


def _ragged_reasoning(rs, B, span):
    """``zaya1-8b.reasoning``'s rows: a prompt of 128-1024 plus a uniform
    part of an output of 512-2048, both log-uniform."""
    import numpy as np
    lu = lambda lo, hi: np.exp(rs.uniform(np.log(lo), np.log(hi), B))
    return np.minimum(lu(128, 1024) + rs.uniform(0, 1, B) * lu(512, 2048),
                      span - 1)


def bench_paged_decode(interpret, B=32, H=32, H_kv=8, per_seq=16,
                       contexts=(256, 512, 1024, 2048, "ragged"),
                       ragged=_ragged_batch_decode, steps=128):
    """The Pallas paged-decode kernel against the XLA fallback and, for
    comparison only (the program does not import it), jax's library
    ``paged_attention`` over the same pools, at a serving cell's shape
    (``B`` rows, ``H`` query / ``H_kv`` KV heads of 128, tables of
    ``per_seq`` pages of 128, bf16), per context length. ``steps`` calls
    are chained inside ONE program (each step's output is the next one's
    query and its new K/V row), so the reading is device time a call; a
    single host-timed dispatch sits on a ~3 ms floor and ranks nothing.
    "ragged" is the cell's own mix of lengths (``ragged``). The reading
    that sets ``autotune.paged_decode_crossover``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.llama import _kv_scatter_tokens
    from paddle_tpu.ops.pallas.paged_attention import (paged_decode_attention,
                                                       paged_decode_xla)

    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    rs = np.random.RandomState(0)
    D = page = 128
    if interpret:
        B, H, H_kv, D, page, per_seq = 2, 4, 2, 32, 16, 4
        contexts, steps = (24, "ragged"), 2
    npages = B * per_seq
    span = page * per_seq
    dt = jnp.bfloat16
    q = jnp.asarray(rs.normal(0, 1, (B, H, D)), dt)
    # head-major pools [H_kv, num_pages, page_size, D]
    kp = jnp.asarray(rs.normal(0, 1, (H_kv, npages, page, D)), dt)
    vp = jnp.asarray(rs.normal(0, 1, (H_kv, npages, page, D)), dt)

    def library(q, kp, vp, tables, lens):
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention)
        # it masks positions < length and wants every table slot in range
        return paged_attention(
            q, kp, vp, lens + 1, jnp.maximum(tables, 0),
            pages_per_compute_block=max(n for n in (8, 4, 2, 1)
                                        if per_seq % n == 0))
    impls = {"pallas": functools.partial(paged_decode_attention,
                                         interpret=interpret),
             "xla": paged_decode_xla}
    if not interpret:                   # it has no interpret mode
        impls["lib"] = library

    def chained(fn):
        # the decode tick's own order: write the step's K/V into its page
        # slot, then attend. The pools are carried, or XLA hoists the
        # fallback's gather out of the loop and only its products are timed
        def run(q, kp, vp, tables, lens):
            phys, off = tables[jnp.arange(B), lens // page], lens % page

            def body(carry, _):
                q, kp, vp = carry
                new = jnp.swapaxes(q[:, :H_kv], 0, 1)
                kp, vp = _kv_scatter_tokens((kp, vp), phys, off, new, new)
                return (fn(q, kp, vp, tables, lens), kp, vp), None
            return jax.lax.scan(body, (q, kp, vp), None, length=steps)[0][0]
        return jax.jit(run)

    fns = {name: chained(fn) for name, fn in impls.items()}
    results = []
    for ctx in contexts:
        if ctx == "ragged":
            lens = ragged(rs, B, span).astype(np.int32)
        else:
            lens = np.full((B,), ctx - 1, np.int32)
        used = lens // page + 1
        tables = rs.permutation(npages)[:B * per_seq].reshape(B, per_seq)
        tables = np.where(np.arange(per_seq)[None] < used[:, None], tables, -1)
        args = (q, kp, vp, jnp.asarray(tables, jnp.int32), jnp.asarray(lens))
        live = int(lens.sum() + B)
        line = {"bench": "paged_decode", "device": kind, "ctx": ctx,
                "shape": f"b{B}_h{H}x{H_kv}_d{D}_pages{per_seq}x{page}",
                "live_tokens": live, "steps": steps,
                # what the live K and V rows take at the chip's HBM rate
                "bytes_us": round(live * H_kv * D * 2 * 2 / 819e3, 1)}
        for name, fn in fns.items():
            t = _time_fn(fn, *args, iters=1, warmup=1, reps=3)
            line[f"{name}_us"] = round(t / steps * 1e6, 1)
        print(json.dumps(line), flush=True)
        results.append(line)
    return results


def _pallas_calls(fn, *args):
    """Names of the Pallas calls a traced ``fn`` makes, in order: what was
    MADE, printed beside what was timed."""
    import jax

    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, out)
        return out
    return walk(jax.make_jaxpr(fn)(*args).jaxpr, [])


def _bench_update(bench, shape, impls, fresh_state, x, rest, steps,
                  moved_bytes, feed=None):
    """One in-place kernel against its ``jnp`` twin: ``steps`` calls chained
    inside ONE program with the state carried and donated, as the tick
    carries it (a call's reading feeds the next one's input through
    ``feed``): device time a call beside the time ``moved_bytes`` take at
    the chip's HBM rate, the winner, and the Pallas calls each side made."""
    import jax
    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    feed = feed or (lambda x, y: 0.5 * x + 0.01 * y.astype(x.dtype))

    def chained(fn):
        def run(state, x):
            def body(carry, _):
                state, x = carry
                y, state = fn(state, x, *rest)
                return (state, feed(x, y)), None
            return jax.lax.scan(body, (state, x), None, length=steps)[0]
        return jax.jit(run, donate_argnums=(0,))

    line = {"bench": bench, "device": kind, "steps": steps, "shape": shape,
            "bytes": moved_bytes, "bytes_us": round(moved_bytes / 819e3, 1)}
    for name, fn in impls.items():
        run, kept = chained(fn), [fresh_state()]

        def once(x):        # the state goes round: the last one was donated
            kept[0], out = run(kept[0], x)
            return out
        t = _time_fn(once, x, iters=1, warmup=1, reps=3)
        line[f"{name}_us"] = round(t / steps * 1e6, 1)
        line[f"{name}_calls"] = sorted(set(_pallas_calls(
            lambda s, x, fn=fn: fn(s, x, *rest), fresh_state(), x)))
    line["winner"] = min(impls, key=lambda name: line[f"{name}_us"])
    print(json.dumps(line), flush=True)
    return [line]


def bench_ssm_update(interpret, B=192, H=64, P=64, N=128, G=8, steps=64):
    """The Mamba-2 state update (``ops/pallas/ssm.py``) at a serving cell's
    shape (``nemotron-3-nano.agent-turns``: 192 slots of 64 heads' [64, 128]
    float32)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.ssm import (pack_state, ssm_state_update,
                                           ssm_state_update_xla)
    if interpret:
        B, H, P, G, steps = 2, 8, 8, 2, 2
    k = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(k[0], (B, H, P)).astype(jnp.bfloat16)
    rest = (jax.nn.softplus(jax.random.normal(k[1], (B, H))),
            -jnp.exp(0.02 * jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (B, G, N)).astype(jnp.bfloat16),
            jax.random.normal(k[4], (B, G, N)).astype(jnp.bfloat16))
    return _bench_update(
        "ssm_state_update", f"b{B}_h{H}_p{P}_n{N}_g{G}",
        {"pallas": functools.partial(ssm_state_update, interpret=interpret),
         "xla": ssm_state_update_xla},
        lambda: pack_state(jnp.zeros((B, H, P, N), jnp.float32), G), x, rest,
        steps, 2 * B * H * P * N * 4)


def _selective_inputs(B, L, D, N):
    """Seeded inputs of the Mamba-1 recurrence: x [B, L, D] bf16, the steps
    [B, L, D] after their softplus, A [N, D] ~ -1, B and C [B, L, N]."""
    import jax
    import jax.numpy as jnp
    k = jax.random.split(jax.random.key(0), 5)
    return (jax.random.normal(k[0], (B, L, D)).astype(jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(k[1], (B, L, D))),
            -jnp.exp(0.02 * jax.random.normal(k[2], (N, D))),
            jax.random.normal(k[3], (B, L, N)),
            jax.random.normal(k[4], (B, L, N)))


def bench_selective_update(interpret, B=256, d=2560, N=16, layers=26):
    """A Mamba-1 layer's tick (``ops/pallas/selective_ssm.py``) at a serving
    cell's shape (``jamba2-3b.batch-reasoning``: 256 slots of [16, 5120]
    float32 and a window [3, 5120] bf16), each half against XLA's fusion of
    its ``jnp`` twin: (1) the state update, from the raw step to the gated
    row, and (2) the window's step, each chained ``layers`` times alone with
    its state fed back; (3) ``layers`` whole layers (``Mamba1Mixer.decode``:
    in_proj to out_proj, each with weights and state of its own) chained in
    one program as a tick chains them, in the three forms ``state_path``
    names: the window's kernel is judged by "fused" against "kernel", the
    update's by "kernel" against "xla"."""
    from paddle_tpu.ops.pallas import selective_ssm as k
    if not interpret:
        return _bench_mamba1_tick(k, B, d, N, layers, "bfloat16")
    kernels = {name: getattr(k, name)
               for name in ("conv_window_step", "selective_state_update")}
    try:        # the layers look their kernels up in the module
        for name, fn in kernels.items():
            setattr(k, name, functools.partial(fn, interpret=True))
        return _bench_mamba1_tick(k, 8, 64, N, 2, "float32")
    finally:
        for name, fn in kernels.items():
            setattr(k, name, fn)


def _bench_mamba1_tick(k, B, d, N, layers, act):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import hybrid_lm
    D, act = 2 * d, jnp.dtype(act)
    key = jax.random.split(jax.random.key(0), 8)
    x, step, a_t, b_mat, c_mat = (
        t[:, 0] if t.ndim == 3 else t for t in _selective_inputs(B, 1, D, N))
    xz = jax.random.normal(key[0], (B, 2 * D)).astype(act)
    bias, skip = (0.02 * jax.random.normal(key[i], (D,)) for i in (1, 2))
    taps = 1.0 + 0.02 * jax.random.normal(key[3], (4, D))
    item = act.itemsize
    results = _bench_update(
        "selective_state_update", f"b{B}_d{D}_n{N}",
        {"pallas": k.selective_state_update,
         "xla": k.selective_state_update_xla},
        lambda: jnp.zeros((B, N, D), jnp.float32), x.astype(act),
        (step, bias, a_t, b_mat, c_mat, 1.0 + skip, xz), layers,
        B * D * (2 * N * 4 + 3 * item + 4))
    results += _bench_update(
        "conv_window_step", f"b{B}_k4_d{D}",
        {"pallas": k.conv_window_step, "xla": k.conv_window_step_xla},
        lambda: jnp.zeros((B, 3, D), act), xz, (taps, bias), layers,
        B * D * 8 * item,
        feed=lambda xz, y: 0.5 * xz + 0.01 * jnp.concatenate([y, y], 1))

    # (3) whole layers, the form forced where ``state_path`` would choose
    cfg = hybrid_lm.HybridConfig(pattern="m", hidden_size=d, vocab_size=8,
                                 mamba_d_state=N, dtype=act.name)
    mixer = hybrid_lm.Mamba1Mixer(cfg)
    seeded = [{n: (p + 0.02 * jax.random.normal(
        jax.random.fold_in(key[4], 97 * i + j), p.shape, jnp.float32
    ).astype(p.dtype)) for j, (n, p) in enumerate(
        sorted(mixer.raw_parameters().items()))} for i in range(layers)]

    def tick(path, params, u, states):
        # the form is the trace's, not an argument: one function a form
        mixer.state_path = lambda rows, slots: path
        out = []
        for p, state in zip(params, states):
            with mixer._bind(p):
                y, state = mixer.decode(u, state)
            u, out = 0.5 * u + 0.5 * y, out + [state]
        return u, out
    u = jax.random.normal(key[5], (B, 1, d)).astype(act)
    line = {"bench": "mamba1_decode_layer", "layers": layers,
            "device": getattr(jax.devices()[0], "device_kind", "cpu"),
            "shape": f"b{B}_h{d}_d{D}_n{N}",
            "weight_bytes": sum(p.size * p.dtype.itemsize
                                for p in seeded[0].values())}
    for path in ("fused", "kernel", "xla"):
        run = jax.jit(functools.partial(tick, path), donate_argnums=(2,))
        kept = [[mixer.alloc_slot_state(B) for _ in range(layers)]]

        def once(u):        # the states go round: the last were donated
            out, kept[0] = run(seeded, u, kept[0])
            return out
        line[f"{path}_calls"] = sorted(set(_pallas_calls(
            functools.partial(tick, path), seeded, u,
            [mixer.alloc_slot_state(B)] * layers)))
        line[f"{path}_us"] = round(_time_fn(
            once, u, iters=1, warmup=1, reps=3) / layers * 1e6, 1)
    line["window_winner"] = ("pallas" if line["fused_us"] < line["kernel_us"]
                             else "xla")
    line["update_winner"] = ("pallas" if line["kernel_us"] < line["xla_us"]
                             else "xla")
    print(json.dumps(line), flush=True)
    return results + [line]


def bench_selective_scan(interpret, lengths=(128, 1024), D=5120, N=16,
                         layers=26):
    """The Mamba-1 prompt scan (``selective_scan``) against the form XLA
    has for it (``selective_scan_xla``: L sequential steps of the update in
    a ``lax.scan``), one prompt at the cell's widths, ``layers`` calls
    chained inside ONE program as a prefill chains its layers (a call's
    reading feeds the next one's input): device time a call beside the
    time its bytes take (x in bf16, the steps and y in float32, once each)
    at the chip's HBM rate."""
    import jax
    from paddle_tpu.ops.pallas.selective_ssm import (selective_scan,
                                                     selective_scan_xla)
    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    if interpret:
        lengths, D, layers = (16,), 128, 2
    impls = {"pallas": functools.partial(selective_scan,
                                         interpret=interpret),
             "xla": selective_scan_xla}

    def chained(fn):
        def run(x, dt, a_t, b, c):
            def body(x, _):
                y, last = fn(x, dt, a_t, b, c)
                return (0.5 * x + 0.01 * y.astype(x.dtype)), last[0, 0, 0]
            return jax.lax.scan(body, x, None, length=layers)
        return jax.jit(run)

    results = []
    for L in lengths:
        args = _selective_inputs(1, L, D, N)
        line = {"bench": "selective_scan", "device": kind, "layers": layers,
                "shape": f"l{L}_d{D}_n{N}",
                "bytes_us": round(L * D * 10 / 819e3, 1)}
        for name, fn in impls.items():
            t = _time_fn(chained(fn), *args, iters=1, warmup=1, reps=3)
            line[f"{name}_us"] = round(t / layers * 1e6, 1)
        print(json.dumps(line), flush=True)
        results.append(line)
    return results


def _power_inputs(B, L, hq, hkv, d):
    """Seeded inputs of power retention: q [B, L, Hq, d] and k [B, L, Hkv,
    d] at unit RMS, v, all bf16, and log g [B, L, Hkv] ~ log sigmoid(N(0,
    1.4)) as the cell's seeded gate gives it."""
    import jax
    import jax.numpy as jnp
    k = jax.random.split(jax.random.key(0), 4)
    bf16 = jnp.bfloat16
    return (jax.random.normal(k[0], (B, L, hq, d)).astype(bf16),
            jax.random.normal(k[1], (B, L, hkv, d)).astype(bf16),
            jax.random.normal(k[2], (B, L, hkv, d)).astype(bf16),
            jax.nn.log_sigmoid(1.4 * jax.random.normal(k[3], (B, L, hkv))))


def bench_power_update(interpret, B=32, hq=40, hkv=8, d=128, layers=5):
    """The power-retention state update (``ops/pallas/power_retention.py``)
    at a serving cell's shape (``brumby-14b.context-answers``: 32 slots of 8
    KV heads' [65, 128, 128] float32 and their normaliser), ``layers`` calls
    chained with the state fed back as a tick chains its layers, against
    XLA's fusion of its ``jnp`` twin; and the widest difference of the two
    readings after one call on a state both started from."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import power_retention as k
    if interpret:
        B, layers = 2, 2
    q, key, v, log_g = (t[:, 0] for t in _power_inputs(B, 1, hq, hkv, d))

    def fresh():
        return (jnp.zeros((B, hkv, k.tiles(d), d, d), jnp.float32),
                jnp.zeros((B, hkv, k.z_rows(d), d), jnp.float32))

    def form(fn):
        def update(state, q, key, v, log_g):
            y, new, z = fn(*state, q, key, v, log_g)
            return y, (new, z)
        return update
    impls = {"pallas": form(functools.partial(k.power_state_update,
                                              interpret=interpret)),
             "xla": form(k.power_state_update_xla)}
    lines = _bench_update(
        "power_state_update", f"b{B}_hq{hq}_hkv{hkv}_d{d}", impls, fresh, q,
        (key, v, log_g), layers, 2 * sum(a.size * 4 for a in fresh()))
    warm, twin = fresh(), jax.jit(impls["xla"])
    for i in range(8):      # eight tokens in the state: a sum worth dividing by
        warm = twin(warm, q, jnp.roll(key, i, 0), jnp.roll(v, i, 0), log_g)[1]
    ys = [jax.jit(fn)(warm, q, key, v, log_g)[0] for fn in impls.values()]
    lines[0]["reading_max_diff"] = float(jnp.max(jnp.abs(ys[0] - ys[1])))
    print(json.dumps({"reading_max_diff": lines[0]["reading_max_diff"]}),
          flush=True)
    return lines


def bench_power_prefill(interpret, lengths=(512, 4096), hq=40, hkv=8, d=128,
                        layers=5):
    """The prompt's chunked power retention (``power_retention_chunked``)
    against its ``jnp`` twin (a ``lax.scan`` over chunks whose ``phi``
    lives in HBM a chunk at a time), one prompt at the cell's widths,
    ``layers`` calls chained inside ONE program as a prefill chains its
    layers: device time a call beside the time its required operations take
    at the chip's peak (2 (R + 1) D (d + 1) a position a KV head), and the
    widest difference of the two readings."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import power_retention as k
    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    if interpret:
        lengths, layers = (160,), 2
    impls = {"pallas": functools.partial(k.power_retention_chunked,
                                         interpret=interpret),
             "xla": k.power_retention_chunked_xla}

    def chained(fn):
        def run(q, key, v, log_g):
            def body(q, _):
                y, state, _ = fn(q, key, v, log_g)
                return (0.5 * q + 0.01 * y.astype(q.dtype)), state[0, 0, 0, 0]
            return jax.lax.scan(body, q, None, length=layers)
        return jax.jit(run)

    results = []
    rows = d * (d + 1) // 2
    for L in lengths:
        args = _power_inputs(1, L, hq, hkv, d)
        flops = L * hkv * 2 * (hq // hkv + 1) * rows * (d + 1)
        line = {"bench": "power_retention_chunked", "device": kind,
                "layers": layers, "shape": f"l{L}_hq{hq}_hkv{hkv}_d{d}",
                "flops_us": round(flops / 197e6, 1)}
        ys = {}
        for name, fn in impls.items():
            t = _time_fn(chained(fn), *args, iters=1, warmup=1, reps=3)
            line[f"{name}_us"] = round(t / layers * 1e6, 1)
            ys[name] = jax.jit(fn)(*args)[0]
        line["reading_max_diff"] = float(jnp.max(jnp.abs(
            ys["pallas"] - ys["xla"])))
        print(json.dumps(line), flush=True)
        results.append(line)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--write-shipped", action="store_true",
                    help="write results into the in-repo tune_db.json")
    ap.add_argument("--out", default=None,
                    help="write results into this JSON file instead")
    ap.add_argument("--flash", action="store_true",
                    help="only the flash-attention block sweep, at the "
                         "benchmark cells' shapes (blocks that do not "
                         "divide the length among the candidates)")
    ap.add_argument("--paged-decode", action="store_true",
                    help="only the paged-decode kernel-vs-XLA comparison")
    ap.add_argument("--ssm-update", action="store_true",
                    help="only the Mamba-2 state update against its twin")
    ap.add_argument("--selective-update", action="store_true",
                    help="only the Mamba-1 state update against its twin")
    ap.add_argument("--selective-scan", action="store_true",
                    help="only the Mamba-1 prompt scan against XLA's form")
    ap.add_argument("--power-update", action="store_true",
                    help="only the power-retention state update against its "
                         "twin")
    ap.add_argument("--power-prefill", action="store_true",
                    help="only the prompt's chunked power retention against "
                         "its twin")
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

    alone = [bench for flag, bench in (
        (args.ssm_update, bench_ssm_update),
        (args.selective_update, bench_selective_update),
        (args.selective_scan, bench_selective_scan),
        (args.power_update, bench_power_update),
        (args.power_prefill, bench_power_prefill)) if flag]
    if alone:
        results = [line for bench in alone for line in bench(interpret)]
        print(json.dumps({"tuned": False, "cases": len(results)}))
        return

    if args.paged_decode:
        # mistral-7b.*'s call, zaya1-8b.reasoning's, and a table four
        # times as long as the first
        results = bench_paged_decode(interpret)
        if not interpret:
            results += bench_paged_decode(
                interpret, B=128, H=8, H_kv=2, per_seq=24,
                contexts=(256, 1024, 3072, "ragged"),
                ragged=_ragged_reasoning)
            results += bench_paged_decode(interpret, per_seq=64,
                                          contexts=(1024, 2048, 8192))
        print(json.dumps({"tuned": False, "cases": len(results)}))
        return

    both = ("fwd", "fwdbwd")
    if interpret or args.quick:
        # 320 is no multiple of either candidate: a padded, masked end
        shapes = [(1, 320, 2, 2, 64, "float32", True, both)]
        candidates = [(128, 128), (128, 256)]
    elif args.flash:
        shapes, candidates = FLASH_CELL_SHAPES, FLASH_CANDIDATES
    else:
        shapes = [
            (8, 2048, 12, 4, 128, "bfloat16", True, both),  # chip_smoke train
            (4, 4096, 12, 4, 128, "bfloat16", True, both),
            (1, 8192, 32, 8, 128, "bfloat16", True, both),  # Llama-3-8B @ 8K
            (8, 2048, 16, 16, 64, "bfloat16", True, both),
            (4, 2048, 12, 4, 128, "bfloat16", False, both),
        ]
        candidates = [(bq, bk) for bq in (256, 512, 1024)
                      for bk in (256, 512, 1024)] + [(2048, 1024)]

    results = sweep_flash(shapes, candidates, interpret,
                          record_db=not interpret, xla=not args.flash)
    if not args.flash:
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
