#!/usr/bin/env python
"""Capability matrix: every BASELINE.json config family runs a REAL
train step on the live backend, and the evidence is committed.

BASELINE.json lists five capability configs (ERNIE-4.5, Llama-3,
DiT/SD3, PP-OCRv4, DeepSeek/Qwen2 MoE). The test suite proves each
family's math on the CPU mesh; this tool proves the same families
compile and TRAIN on the actual TPU chip, writing one auditable JSON
artifact per run (bench_artifacts/capability_matrix_*.json) with
per-family step time, params, and the loss trajectory.

Usage:
    python tools/capability_matrix.py [--steps N] [--out PATH]
"""

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jax import block_until_ready as _sync  # noqa: E402


def _n_params(model):
    import jax
    import numpy as np
    if hasattr(model, "num_params"):
        return model.num_params()
    return int(sum(int(np.prod(v.shape))
                   for v in jax.tree.leaves(model.raw_parameters())))


def _lm_family(name, model, vocab, b, s, steps):
    import jax
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.trainer import Trainer

    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (b, s + 1))
    batch = {"input_ids": jnp.asarray(ids[:, :-1]),
             "labels": jnp.asarray(ids[:, 1:])}
    tr = Trainer(model, AdamW(learning_rate=1e-3, parameters=model))
    losses = [float(tr.train_step(batch))]          # compile + step
    t0 = time.perf_counter()
    for _ in range(steps):
        losses.append(float(tr.train_step(batch)))
    dt = (time.perf_counter() - t0) / steps
    return {"family": name, "params": _n_params(model),
            "batch": [b, s], "step_time_s": round(dt, 4),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "loss_drops": losses[-1] < losses[0]}


def _sgd_family(name, model, loss_fn, batch_shape, steps, lr=1e-3):
    """Shared timed loop for families driven by raw value_and_grad + SGD
    (dit/ocr); _lm_family covers the Trainer-driven LM families."""
    import jax
    import time as _time
    vg = jax.jit(jax.value_and_grad(loss_fn))
    params = model.raw_parameters()
    l0, g = vg(params)
    _sync(l0)
    losses = [float(l0)]
    t0 = _time.perf_counter()
    for _ in range(steps):
        l, g = vg(params)
        params = jax.tree.map(lambda p_, g_: p_ - lr * g_, params, g)
        losses.append(float(l))
    dt = (_time.perf_counter() - t0) / steps
    return {"family": name, "params": _n_params(model),
            "batch": list(batch_shape), "step_time_s": round(dt, 4),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "loss_drops": losses[-1] < losses[0]}


def run_family(name, steps):
    import jax
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt

    pt.seed(0)
    if name == "llama":
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=8192, hidden_size=512,
                          intermediate_size=1536, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=4,
                          max_position_embeddings=1024)
        return _lm_family(name, LlamaForCausalLM(cfg), cfg.vocab_size,
                          4, 512, steps)
    if name == "ernie":
        from paddle_tpu.models import ErnieConfig, ErnieForCausalLM
        cfg = ErnieConfig(vocab_size=8192, hidden_size=512,
                          intermediate_size=1536, num_hidden_layers=4,
                          num_attention_heads=8,
                          max_position_embeddings=1024)
        return _lm_family(name, ErnieForCausalLM(cfg), cfg.vocab_size,
                          4, 512, steps)
    if name == "moe":
        from paddle_tpu.models import MoEConfig, MoEForCausalLM
        cfg = MoEConfig(vocab_size=8192, hidden_size=512,
                        intermediate_size=768, num_hidden_layers=4,
                        num_attention_heads=8, num_key_value_heads=8,
                        num_experts=8, num_experts_per_tok=2,
                        num_shared_experts=1,
                        max_position_embeddings=1024)
        m = MoEForCausalLM(cfg)
        out = _lm_family(name, m, cfg.vocab_size, 4, 512, steps)
        out["activated_params"] = m.num_activated_params()
        return out
    if name == "moe64":
        # DeepSeekMoE-scale expert COUNT (64 routed + 2 shared, top-6,
        # dropless ragged_dot path) at trainable-on-one-chip widths —
        # round-4 verdict: the matrix ran only 8 experts while
        # BASELINE.json targets DeepSeekMoE's 64+
        from paddle_tpu.models import MoEConfig, MoEForCausalLM
        cfg = MoEConfig(vocab_size=8192, hidden_size=512,
                        intermediate_size=1536, moe_intermediate_size=256,
                        num_hidden_layers=4, num_attention_heads=8,
                        num_key_value_heads=8,
                        num_experts=64, num_experts_per_tok=6,
                        num_shared_experts=2, capacity_factor=None,
                        max_position_embeddings=1024)
        m = MoEForCausalLM(cfg)
        out = _lm_family(name, m, cfg.vocab_size, 4, 512, steps)
        out["activated_params"] = m.num_activated_params()
        out["num_experts"] = 64
        return out
    if name == "dit":
        from paddle_tpu.models import DiTConfig, DiT
        cfg = DiTConfig(input_size=32, patch_size=4, in_channels=4,
                        hidden_size=384, depth=6, num_heads=6,
                        num_classes=100)
        model = DiT(cfg)
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(8, 4, 32, 32).astype(np.float32))
        t = jnp.asarray(rs.randint(0, 1000, (8,)))
        y = jnp.asarray(rs.randint(0, 100, (8,)))
        noise = jnp.asarray(rs.randn(8, 4, 32, 32).astype(np.float32))

        def loss_fn(p):
            pred = model.functional_call(p, x, t, y)
            return jnp.mean((pred[:, :4] - noise) ** 2)
        return _sgd_family(name, model, loss_fn, (8, 32, 32), steps)
    if name == "ocr":
        from paddle_tpu.models import OCRRecConfig, OCRRecModel
        cfg = OCRRecConfig(num_classes=96)
        model = OCRRecModel(cfg)
        rs = np.random.RandomState(0)
        img = jnp.asarray(rs.randn(8, 3, 32, 128).astype(np.float32))
        lab = jnp.asarray(rs.randint(1, 96, (8, 12)).astype(np.int32))
        import jax as _jax
        from paddle_tpu.nn.functional_extras import ctc_loss as _ctc

        def loss_fn(p):
            logits = model.functional_call(p, img)   # [B, T, C]
            lp = _jax.nn.log_softmax(logits, axis=-1)
            T = lp.shape[1]
            return _ctc(lp.transpose(1, 0, 2), lab,
                        jnp.full((8,), T, jnp.int32),
                        jnp.full((8,), 12, jnp.int32)).mean()
        return _sgd_family(name, model, loss_fn, (8, 3, 32, 128), steps)
    raise ValueError(name)


FAMILIES = ("llama", "ernie", "moe", "moe64", "dit", "ocr")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    backend = jax.default_backend()
    device = getattr(jax.devices()[0], "device_kind", "unknown")
    rows, errors = [], {}
    for fam in FAMILIES:
        t0 = time.perf_counter()
        try:
            row = run_family(fam, args.steps)
            row["total_s"] = round(time.perf_counter() - t0, 1)
            rows.append(row)
            print(f"[capability] {fam}: OK "
                  f"step={row['step_time_s']}s loss "
                  f"{row['loss_first']}->{row['loss_last']}",
                  file=sys.stderr, flush=True)
        except Exception as e:                       # noqa: BLE001
            errors[fam] = f"{type(e).__name__}: {str(e)[:300]}"
            print(f"[capability] {fam}: FAIL {errors[fam]}",
                  file=sys.stderr, flush=True)
    try:
        head = subprocess.run(["git", "rev-parse", "HEAD"],
                              capture_output=True, text=True,
                              timeout=10).stdout.strip()
    except Exception:
        head = "unknown"
    art = {"backend": backend, "device": device, "steps": args.steps,
           "families": rows, "errors": errors, "git_head": head,
           "captured_at": datetime.datetime.now(
               datetime.timezone.utc).isoformat()}
    out = args.out
    if out is None:
        d = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench_artifacts")
        os.makedirs(d, exist_ok=True)
        ts = datetime.datetime.now(datetime.timezone.utc) \
            .strftime("%Y%m%dT%H%M%S")
        out = os.path.join(d, f"capability_matrix_{backend}_{ts}.json")
    with open(out, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({"backend": backend,
                      "ok": [r["family"] for r in rows],
                      "failed": sorted(errors), "artifact": out}))


if __name__ == "__main__":
    main()
