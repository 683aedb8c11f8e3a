#!/usr/bin/env python
"""Loss-head microbench: fused vocab-CE vs the naive materialized-logits
path (fwd+bwd, the training profile).

The fused head (ops/pallas/fused_vocab_ce.py) computes
``CE(hidden @ W, labels)`` blockwise so the [N, V] logits never exist;
the naive path materializes them in fp32 and log-softmaxes. This tool
times BOTH as compiled grad(loss) programs over the same arrays and
reports RATIOS — on the shared/noisy CPU host absolute tok/s numbers are
meaningless (memory: bench-cpu-variance), and on TPU the ratio is the
MFU-gap claim the fused head exists for. Legs are interleaved
min-of-rounds (the bench.py A/B idiom) so both see the same contention.

Emitted keys (bench.py folds them into detail):
  loss_head_fused_s / loss_head_naive_s   — per-call wall time (min)
  loss_head_fused_speedup                 — naive / fused (>= 1.0 target)
  loss_head_logits_mb_avoided             — fp32 [N, V] bytes the fused
                                            path never allocates
  loss_head_share                         — fused loss-head time / a full
                                            train-step time (pass step_s)

Usage:
    python tools/loss_head_bench.py [--n 4096] [--h 512] [--v 32000]
                                    [--dtype bfloat16] [--rounds 5]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_loss_head_bench(n=4096, h=512, v=32000, dtype="bfloat16",
                        rounds=5, iters=2, step_time_s=None,
                        block_n=None, block_v=None):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops.pallas.fused_vocab_ce import (
        fused_linear_cross_entropy)
    _sync = jax.block_until_ready

    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    rs = np.random.RandomState(0)
    hid = jnp.asarray(rs.normal(0, 1, (n, h)), dt)
    w = jnp.asarray(rs.normal(0, 0.02, (h, v)), dt)
    lab = jnp.asarray(rs.randint(0, v, (n,)), jnp.int32)

    def naive(hid, w):
        return F.cross_entropy((hid @ w).astype(jnp.float32), lab)

    def fused(hid, w):
        return fused_linear_cross_entropy(hid, w, lab, block_n=block_n,
                                          block_v=block_v)

    legs = {}
    for name, fn in (("naive", naive), ("fused", fused)):
        g = jax.jit(jax.grad(fn, argnums=(0, 1)))
        r = g(hid, w)                       # compile + warm
        _sync(jax.tree.leaves(r)[0])
        legs[name] = g
    best = {name: float("inf") for name in legs}
    for _ in range(rounds):
        for name, g in legs.items():        # interleaved: same contention
            t0 = time.perf_counter()
            for _ in range(iters):
                r = g(hid, w)
            _sync(jax.tree.leaves(r)[0])
            best[name] = min(best[name], (time.perf_counter() - t0) / iters)

    out = {
        "loss_head_n": n, "loss_head_h": h, "loss_head_v": v,
        "loss_head_dtype": dtype,
        "loss_head_fused_s": round(best["fused"], 6),
        "loss_head_naive_s": round(best["naive"], 6),
        "loss_head_fused_speedup": round(best["naive"] / best["fused"], 4),
        "loss_head_logits_mb_avoided": round(n * v * 4 / 2 ** 20, 1),
    }
    if step_time_s:
        # share of a full train step the (fused) loss head costs — the
        # step-decomposition number the e2e-MFU-gap work tracks
        out["loss_head_share"] = round(best["fused"] / step_time_s, 4)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=4096,
                    help="tokens (B*S) per call")
    ap.add_argument("--h", type=int, default=512)
    ap.add_argument("--v", type=int, default=32000)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--step-time-s", type=float, default=None,
                    help="full train-step time to compute loss_head_share")
    args = ap.parse_args()
    out = run_loss_head_bench(args.n, args.h, args.v, args.dtype,
                              args.rounds, args.iters, args.step_time_s)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
