#!/usr/bin/env python
"""On-chip microbench of REAL Llama-3-8B shapes -> v5p-64 projection.

Round-4 verdict item #1: the north-star (Llama-3-8B pretrain >= 40% MFU on
v5p-64, BASELINE.json) was backed only by memory-fit math; the 428M bench
config was the only measured training point. This tool measures the actual
8B building blocks on the v5e chip — they fit its 16 GB HBM individually —
and feeds paddle_tpu.parallel.projection to produce a DERIVED projection
artifact (bench_artifacts/projection_llama3_8b_v5p64.json), recomputed by
tests/test_projection.py.

Measured here (b=1, s=8192, bf16, flash kernel, tuned blocks):
  - one decoder layer fwd+bwd (h=4096, ffn=14336, 32 q / 8 kv heads),
    with and without jax.checkpoint (the 1F1B plan runs remat)
  - the untied lm_head matmul + fp32 CE at vocab=128256 (s=2048 and 4096
    -> per-token slope; linearity asserted)
  - the embedding gather fwd+bwd

Timing discipline: each case takes min-of-rounds with several dispatches
amortized per sync.

Usage: python tools/bench_8b_layer.py [--rounds N] [--no-write]
"""

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "bench_artifacts",
                        "projection_llama3_8b_v5p64.json")


def _log(m):
    print(m, file=sys.stderr, flush=True)


def _min_rounds(fn, args, rounds, iters):
    import jax
    _sync = jax.block_until_ready
    r = fn(*args)
    _sync(jax.tree.leaves(r)[0])
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*args)
        _sync(jax.tree.leaves(r)[0])
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def measure(rounds=4, config="llama3_8b"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.models.llama import LlamaDecoderLayer, causal_lm_loss
    from paddle_tpu.ops import rope as rope_ops

    # 70B layer (h=8192, ffn=28672: 1.9 GB bf16 params) fits the v5e
    # chip for a per-layer microbench at a shorter sequence; the
    # projection rebuilds per-token cost at the target s (matmul part is
    # seq-independent, attention part scales linearly)
    cfg = getattr(LlamaConfig, config)(dtype="bfloat16")
    S = 8192 if config == "llama3_8b" else 2048
    out = {"config": config, "seq_len": S, "layer_seq": S, "batch": 1,
           "device": getattr(jax.devices()[0], "device_kind", "unknown")}

    pt.seed(0)
    layer = LlamaDecoderLayer(cfg)
    params = layer.raw_parameters()
    cos, sin = rope_ops.rope_freqs(cfg.head_dim, S, cfg.rope_theta)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.normal(0, 1, (1, S, cfg.hidden_size)), jnp.bfloat16)

    def run_layer(p, x):
        return layer.functional_call(p, x, cos, sin)

    def loss_plain(p, x):
        return run_layer(p, x).astype(jnp.float32).mean()

    def loss_remat(p, x):
        return jax.checkpoint(run_layer)(p, x).astype(jnp.float32).mean()

    # value_and_grad, NOT grad: under plain grad the primal loss value is
    # unused, and with remat that lets XLA DCE the entire first forward —
    # the "remat" microbench then measures re-fwd+bwd only and reads
    # FASTER than the plain layer (observed live on the 70B shapes)
    _log("compiling layer fwd+bwd (no remat)...")
    g_plain = jax.jit(jax.value_and_grad(loss_plain, argnums=(0, 1)))
    out["layer_us"] = round(_min_rounds(g_plain, (params, x),
                                        rounds, 6) * 1e6, 1)
    _log(f"layer fwd+bwd: {out['layer_us']} us")

    _log("compiling layer fwd+bwd (remat)...")
    g_remat = jax.jit(jax.value_and_grad(loss_remat, argnums=(0, 1)))
    out["layer_remat_us"] = round(_min_rounds(g_remat, (params, x),
                                              rounds, 6) * 1e6, 1)
    _log(f"layer fwd+bwd remat: {out['layer_remat_us']} us")
    del g_plain, g_remat, params, x, layer

    # --- lm_head + CE (fp32 logits), vocab=128256 ---
    w = jnp.asarray(rs.normal(0, 0.02, (cfg.hidden_size, cfg.vocab_size)),
                    jnp.bfloat16)
    head_ts = {}
    for sh in (2048, 4096):
        h = jnp.asarray(rs.normal(0, 1, (1, sh, cfg.hidden_size)),
                        jnp.bfloat16)
        lbl = jnp.asarray(rs.randint(0, cfg.vocab_size, (1, sh)), jnp.int32)

        def head_loss(w, h, lbl=lbl):
            return causal_lm_loss(jnp.matmul(h, w.astype(h.dtype)), lbl)

        _log(f"compiling lm_head+CE s={sh}...")
        g = jax.jit(jax.grad(head_loss, argnums=(0, 1)))
        head_ts[sh] = _min_rounds(g, (w, h), rounds, 4)
        out[f"head_us_s{sh}"] = round(head_ts[sh] * 1e6, 1)
        _log(f"head s={sh}: {out[f'head_us_s{sh}']} us")
        del g, h
    # per-token slope removes the fixed dispatch/epilogue cost
    slope = (head_ts[4096] - head_ts[2048]) / (4096 - 2048)
    out["head_us_per_token"] = round(slope * 1e6, 4)
    out["head_linearity"] = round(head_ts[4096] / (2 * head_ts[2048]), 4)
    del w

    # --- embedding gather fwd+bwd ---
    emb = jnp.asarray(rs.normal(0, 0.02, (cfg.vocab_size, cfg.hidden_size)),
                      jnp.bfloat16)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (1, S)), jnp.int32)

    def emb_loss(t):
        return jnp.take(t, ids, axis=0).astype(jnp.float32).mean()

    _log("compiling embedding gather fwd+bwd...")
    g = jax.jit(jax.grad(emb_loss))
    out["embed_us"] = round(_min_rounds(g, (emb,), rounds, 6) * 1e6, 1)
    _log(f"embed: {out['embed_us']} us")

    # observed per-layer MFU on v5e, for the artifact's sanity section
    from paddle_tpu.parallel.projection import (llama3_8b_counts,
                                                llama3_70b_counts,
                                                PEAK_BF16)
    counts = (llama3_8b_counts if config == "llama3_8b"
              else llama3_70b_counts)
    c = counts(S)
    out["layer_mfu_v5e"] = round(
        c["layer_flops_per_token"] * S / (out["layer_us"] * 1e-6)
        / PEAK_BF16["v5e"], 4)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--no-write", action="store_true")
    ap.add_argument("--config", default="llama3_8b",
                    choices=("llama3_8b", "llama3_70b"))
    args = ap.parse_args()

    from paddle_tpu.core.compile_cache import configure_compilation_cache
    from paddle_tpu.ops.registry import require_tpu
    configure_compilation_cache()
    require_tpu()     # measures real 8B/70B shapes: the chip or nothing

    measured = measure(args.rounds, config=args.config)
    from paddle_tpu.parallel.projection import (project_llama3_8b_v5p64,
                                                project_llama3_70b_v5p64)
    if args.config == "llama3_8b":
        proj = project_llama3_8b_v5p64(measured)
        summary = {
            "plan_a_mfu": round(proj["plan_a_fsdp64"]["projected_mfu"], 4),
            "plan_b_mfu": round(
                proj["plan_b_pp8_fsdp8_1f1b"]["projected_mfu"], 4)}
        artifact = ARTIFACT
    else:
        proj = project_llama3_70b_v5p64(measured)
        summary = {"plan_mfu": round(
            proj["plan_fsdp64_remat"]["projected_mfu"], 4)}
        artifact = ARTIFACT.replace("8b", "70b")

    try:
        head = subprocess.run(["git", "rev-parse", "HEAD"],
                              capture_output=True, text=True, cwd=REPO,
                              timeout=10).stdout.strip()
    except Exception:
        head = "unknown"
    art = {"kind": f"{args.config}_v5p64_projection",
           "git_head": head,
           "captured_at": datetime.datetime.now(
               datetime.timezone.utc).isoformat(),
           "measured": measured,
           "projection": proj}
    print(json.dumps({
        "config": args.config,
        "layer_us": measured["layer_us"],
        "layer_mfu_v5e": measured["layer_mfu_v5e"],
        "head_us_per_token": measured["head_us_per_token"],
        **summary,
        "meets_target": proj["north_star"]["meets_target"]}))
    if not args.no_write:
        os.makedirs(os.path.dirname(artifact), exist_ok=True)
        with open(artifact, "w") as f:
            json.dump(art, f, indent=1)
        _log(f"artifact written: {artifact} (commit it!)")


if __name__ == "__main__":
    main()
