#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls, and
checks what comes out by the repo's own means. One process; nothing here is
a benchmark and no number it prints is a performance claim.

    python chip_smoke.py              one TPU chip: phases ``train``, ``serve``
    python chip_smoke.py --chips 4    one four-chip host: phase ``train4`` only
    python chip_smoke.py --rehearse [--chips 4]
                                      tiny shapes on the CPU, Pallas kernels
                                      in interpret mode — control flow only

* ``train``  — ``Trainer.train_step`` fed by ``io.DataLoader`` (worker
  threads, collate, device prefetch) at ``TRAIN_SHAPE`` (b=8, s=2048). NOT
  a published model: it is the one training shape that has run on a chip
  before, so a failure points at the toolchain and not at size. Llama-3-8B
  widths cannot train on one 16 GB chip at any depth (embedding + head
  alone are 1.05 B parameters = 12.6 GB of bf16 weight and gradient plus
  two fp32 moments); that is ``train4``.
* ``serve``  — ``ContinuousBatchingEngine`` with its defaults over
  ``LlamaConfig.llama3_8b()`` widths in bf16, DEPTH CUT to 16 of 32 layers
  and nothing else; 8 greedy requests of 120–6000 prompt tokens in two
  waves. Which decode paths must run follows the engine's own crossover:
  its default is 0 since PR 25 (every tick paged); the rehearsal sets one,
  so both sides run there.
  Prefill-then-decode through the cache is held against the model's plain
  full forward over prompt + generated tokens.
* ``train4`` — Llama-3-8B widths, depth cut to 4 layers, s=4096, through
  ``HybridMesh.build`` + ``shard_layer`` + ``Trainer``: 3 steps under fsdp=4
  and 3 under fsdp=2 x tp=2 from the same seed and batches; the layouts'
  losses must agree, the HLO must hold each layout's collectives and the
  kernels, and no device's peak memory may exceed 1.5x the mean.

Every line of stdout is one JSON object; the LAST line is the verdict the
driver reads. Any failed check raises: there is no error that is recorded and
carried past. Without a TPU the script exits 2 before building anything.
Train runs before serve: the train step needs 14.8 of the chip's 15.75 GiB,
so it gets the chip while nothing else has touched it.
"""

from __future__ import annotations

import argparse
import base64
import dataclasses
import functools
import gc
import json
import math
import os
import re
import sys
import time

#: max |logit| difference, and the reference margin above which a greedy
#: disagreement is a failure. Logits are bf16 with std ~1.3 at these widths
#: (one bf16 ulp at 4.0 is 0.03); the cache path and the reference take
#: different kernels and block orders through 16 bf16 layers. A wrong page
#: or position moves logits by O(1).
LOGIT_TOL = 0.25
#: step-by-step relative loss agreement between the two four-chip layouts
LOSS_RTOL = 1e-2
#: how far step-0 loss may sit from what random init predicts (loss0_expected)
LOSS0_ATOL = 0.1

#: the ``train`` phase's model (why this one: the docstring)
TRAIN_SHAPE = dict(vocab_size=32000, hidden_size=1536,
                   intermediate_size=4608, num_hidden_layers=12,
                   num_attention_heads=12, num_key_value_heads=4,
                   max_position_embeddings=2048, dtype="bfloat16")

REHEARSAL = False
_COMPILE_S = [0.0]          # seconds jax spent tracing, lowering, compiling


def _count_compile(event: str, secs: float, **_):
    if event.startswith("/jax/core/compile/"):
        _COMPILE_S[0] += secs


def emit(**kw):
    if REHEARSAL:
        kw["rehearsal"] = True
    print(json.dumps(kw), flush=True)


def check(ok, what: str):
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def loss0_expected(cfg) -> float:
    """Cross entropy of a randomly initialised model: the logits of a
    unit-RMS hidden state against N(0, r^2) head columns are ~N(0, H r^2),
    and E[logsumexp] of V such Gaussians is ln V + H r^2 / 2 — 10.37 + 0.31
    at the headline shape (the chip said 10.680), 11.76 + 0.82 at Llama-3-8B
    widths."""
    return (math.log(cfg.vocab_size)
            + cfg.hidden_size * cfg.initializer_range ** 2 / 2)


def _sizes(rehearse: bool) -> dict:
    """Real sizes, or the tiny ones of a rehearsal."""
    from paddle_tpu.models import LlamaConfig
    if rehearse:
        tiny = functools.partial(LlamaConfig.tiny, dtype="bfloat16")
        return {
            "serve": dict(cfg=tiny(), config="LlamaConfig.tiny",
                          depth_cut=None, waves=[[10, 9, 40], [100, 130]],
                          new=8, max_len=256, num_pages=40,
                          engine_kw=dict(page_size=16, attn_crossover=64)),
            "train": dict(cfg=tiny(), config="LlamaConfig.tiny", batch=2,
                          seq=64, steps=5, warmup=2),
            "train4": dict(cfg=tiny(num_hidden_layers=4),
                           config="LlamaConfig.tiny", depth_cut=None,
                           batch=4, seq=64, steps=3),
        }
    wide = LlamaConfig.llama3_8b(dtype="bfloat16")
    return {
        # 16 layers = 8.46 GiB of weights + a 2.0 GiB pool of 256 pages
        # (32 K tokens at 64 KB/token); the compiler's memory analysis for
        # the described chip puts the largest program (dense decode) at
        # 11.7 GiB
        "serve": dict(cfg=dataclasses.replace(wide, num_hidden_layers=16),
                      config="LlamaConfig.llama3_8b",
                      depth_cut="16 of 32 layers",
                      waves=[[128, 120, 1500, 1480, 3000],
                             [4200, 6000, 5990]],
                      new=64, max_len=6144, num_pages=256, engine_kw={}),
        "train": dict(cfg=LlamaConfig(**TRAIN_SHAPE),
                      config="chip_smoke.TRAIN_SHAPE", batch=8, seq=2048,
                      steps=5, warmup=2),
        # 4 layers = 1.92 B parameters = 26.9 GiB of train state over four
        # chips; compiled for a described v5e:2x2 at 10.1 (fsdp=4) and
        # 10.9 (fsdp=2 x tp=2) GiB a device
        "train4": dict(cfg=dataclasses.replace(wide, num_hidden_layers=4),
                       config="LlamaConfig.llama3_8b",
                       depth_cut="4 of 32 layers", batch=4, seq=4096,
                       steps=3),
    }


def _rehearsal_kernels():
    """Rehearsal only: put the Pallas kernels, in INTERPRET mode, where
    dispatch finds them on the CPU backend. Interpret mode is asked for
    here, by argument — dispatch never chooses it."""
    from paddle_tpu.ops.pallas import fused_vocab_ce
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas
    from paddle_tpu.ops.pallas.fused_norm import rms_norm_pallas
    from paddle_tpu.ops.registry import register_kernel
    from paddle_tpu.parallel import mp_layers
    register_kernel("flash_attention", "cpu")(
        functools.partial(flash_attention_pallas, interpret=True))
    register_kernel("rms_norm", "cpu")(
        lambda x, weight=None, epsilon=1e-6: rms_norm_pallas(
            x, weight, epsilon, interpret=True))
    # the loss head calls these two by name, not through the registry
    fused_vocab_ce.fused_linear_cross_entropy = functools.partial(
        fused_vocab_ce.fused_linear_cross_entropy, interpret=True)
    mp_layers.parallel_fused_linear_cross_entropy = functools.partial(
        mp_layers.parallel_fused_linear_cross_entropy, interpret=True)


_KERNEL_FILES = ("flash_attention", "fused_vocab_ce", "fused_norm",
                 "fused_rope", "paged_attention")


def kernel_census(hlo: str) -> dict:
    """Pallas kernels in a compiled program, by the source file named in
    each custom call's embedded Mosaic module."""
    out = {"tpu_custom_call": hlo.count('custom_call_target="tpu_custom_call"')}
    for body in re.findall(r'"body":"([A-Za-z0-9+/=]+)"', hlo):
        blob = base64.b64decode(body)
        for name in _KERNEL_FILES:
            if (name + ".py").encode() in blob:
                out[name] = out.get(name, 0) + 1
    return out


def collective_census(hlo: str) -> dict:
    out = {}
    for m in re.finditer(r"\b(all-reduce|reduce-scatter|all-gather|"
                         r"collective-permute|all-to-all)(?:-start)?\(", hlo):
        out[m.group(1)] = out.get(m.group(1), 0) + 1
    return out


def _cache_counts():
    from paddle_tpu.core import compile_cache
    s = compile_cache.stats()
    return {"persistent_hits": s["persistent_hits"],
            "persistent_misses": s["persistent_misses"],
            "step_compiles": s["misses"], "traces": s["traces"]}


def _release():
    """Drop what the last phase left on the device; returns bytes still in
    use on device 0 (None where the backend does not say)."""
    import jax
    gc.collect()
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_in_use") if stats else None


def _peaks():
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


# -- train ------------------------------------------------------------------

def synthetic_loader(cfg, batch_size, seq_len, steps):
    """Synthetic LM batches through the real input pipeline (worker
    threads, collate, device prefetch); four batches more than the steps
    ask for, so the prefetcher never runs dry."""
    import numpy as np
    from paddle_tpu.io import DataLoader, Dataset

    class SyntheticLM(Dataset):
        def __len__(self):
            return batch_size * (steps + 4)

        def __getitem__(self, i):
            rs = np.random.RandomState(i)
            ids = rs.randint(0, cfg.vocab_size, (seq_len + 1,), np.int32)
            return {"input_ids": ids[:-1], "labels": ids[1:]}

    return DataLoader(SyntheticLM(), batch_size=batch_size, num_workers=2,
                      prefetch_factor=4, prefetch_to_device=True,
                      drop_last=True)


def phase_train(sz, seed):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import fused_loss_enabled
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.trainer import Trainer

    cfg, b, s = sz["cfg"], sz["batch"], sz["seq"]
    steps, warmup = sz["steps"], sz["warmup"]
    emit(phase="train", config=sz["config"], published_model=False,
         why="the one training shape with a chip record; not a model anyone "
             "publishes — llama3_8b widths train in --chips 4",
         hidden=cfg.hidden_size, layers=cfg.num_hidden_layers,
         vocab=cfg.vocab_size, batch=b, seq=s, steps=steps, warmup=warmup,
         fused_loss_configured=fused_loss_enabled(cfg))
    t0 = time.perf_counter()
    c0 = _COMPILE_S[0]
    pt.seed(seed)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01, parameters=model)
    tr = Trainer(model, opt)
    it = iter(synthetic_loader(cfg, b, s, steps + warmup))

    losses = [float(tr.train_step(next(it))) for _ in range(warmup)]
    t_warm = time.perf_counter() - t0
    warm_compile = _COMPILE_S[0] - c0
    before = _cache_counts()
    t1 = time.perf_counter()
    losses += [float(tr.train_step(next(it))) for _ in range(steps)]
    t_run = time.perf_counter() - t1
    after = _cache_counts()

    kernels = kernel_census(tr._last_exec.as_text())
    emit(phase="train", losses=[round(x, 4) for x in losses],
         loss0_expected=round(loss0_expected(cfg), 4),
         warmup_s=round(t_warm, 2), warmup_compile_s=round(warm_compile, 2),
         steps_s=round(t_run, 2), cache_before=before, cache_after=after,
         kernels=kernels, peak_bytes=_peaks())
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(abs(losses[0] - loss0_expected(cfg)) <= LOSS0_ATOL,
          f"step-0 loss {losses[0]} vs {loss0_expected(cfg):.3f} expected "
          f"at random init")
    check(before == after, f"compiled after warm-up: {before} -> {after}")
    if not REHEARSAL:
        check(fused_loss_enabled(cfg), "fused loss head not configured")
        # fwd + dhidden + dW: the fused head visible in the program
        check(kernels.get("fused_vocab_ce", 0) >= 3,
              f"fused-CE kernels missing from the step: {kernels}")
        check(kernels.get("flash_attention", 0) >= 2,
              f"flash-attention kernels missing from the step: {kernels}")
    del tr, opt, model, it
    return {"losses": losses}


# -- serve ------------------------------------------------------------------

def phase_serve(sz, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.models import LlamaForCausalLM

    cfg, new, waves = sz["cfg"], sz["new"], sz["waves"]
    emit(phase="serve", config=sz["config"], dtype=cfg.dtype,
         hidden=cfg.hidden_size, ffn=cfg.intermediate_size,
         heads=[cfg.num_attention_heads, cfg.num_key_value_heads],
         vocab=cfg.vocab_size, layers=cfg.num_hidden_layers,
         depth_cut=sz["depth_cut"], prompts=waves, new_tokens=new,
         max_len=sz["max_len"], num_pages=sz["num_pages"],
         logit_tol=LOGIT_TOL)
    t0 = time.perf_counter()
    c0 = _COMPILE_S[0]
    pt.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    eng = ContinuousBatchingEngine(
        model, max_len=sz["max_len"], num_pages=sz["num_pages"],
        generation_config=GenerationConfig(max_new_tokens=new,
                                           do_sample=False),
        **sz["engine_kw"])
    jax.block_until_ready(eng._params)
    t_build = time.perf_counter() - t0

    rs = np.random.RandomState(seed)
    prompts = [[rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                for n in wave] for wave in waves]
    # held against the reference: the shortest and the longest request
    probes = {(0, int(np.argmin(waves[0]))), (len(waves) - 1,
                                              int(np.argmax(waves[-1])))}

    # first-token logits of the probes from the ENGINE's own compiled
    # prefill, its K/V routed to the reserved garbage page (a zero table);
    # this also compiles the bucket the engine is about to use
    first = {}
    for w, i in probes:
        p = prompts[w][i]
        bucket = eng._bucket(len(p))
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(p)] = p
        logits, eng.pools, eng.slot_state = eng._prefill_fn(bucket)(
            eng._params, jnp.asarray(ids), eng.pools,
            jnp.zeros((1, eng.pages_per_seq), jnp.int32),
            jnp.int32(len(p) - 1), eng.slot_state, np.int32(0))
        first[(w, i)] = np.asarray(logits.astype(jnp.float32))

    out, wave_s = {}, []
    for w, wave in enumerate(prompts):
        t1 = time.perf_counter()
        rids = [eng.submit(p) for p in wave]
        done = eng.run()
        wave_s.append(round(time.perf_counter() - t1, 2))
        for i, rid in enumerate(rids):
            check(rid in done and len(done[rid]) == new,
                  f"request {w}.{i} (prompt {len(wave[i])}) incomplete")
            out[(w, i)] = done[rid]
    ticks = dict(eng.attn_path_ticks)
    t_serve = time.perf_counter() - t0
    emit(phase="serve", requests=len(out), tokens_out=len(out) * new,
         build_s=round(t_build, 2), wave_s=wave_s,
         total_s=round(t_serve, 2),
         compile_s=round(_COMPILE_S[0] - c0, 2), attn_path_ticks=ticks,
         prefill_buckets=sorted(eng._prefill_cache), cache=_cache_counts())
    sides = ("dense", "paged") if eng.attn_crossover else ("paged",)
    check(all(ticks[side] > 0 for side in sides),
          f"of {sides} (crossover {eng.attn_crossover}) one never ran: "
          f"{ticks}")

    # the reference: the model's plain full forward over prompt +
    # generated tokens (padded to whole kernel blocks; causal, so the pad
    # cannot reach back), teacher-forced on what the engine emitted
    @jax.jit
    def reference(params, ids, start):
        logits = model.functional_call(params, ids)
        return jax.lax.dynamic_slice_in_dim(logits[0], start, new,
                                            0).astype(jnp.float32)

    for w, i in sorted(probes):
        p, gen = prompts[w][i], out[(w, i)]
        L = len(p)
        S = -(-(L + new) // 128) * 128
        ids = np.zeros((1, S), np.int32)
        ids[0, :L], ids[0, L:L + new] = p, gen
        ref = np.asarray(reference(eng._params, jnp.asarray(ids),
                                   jnp.int32(L - 1)))       # [new, V]
        first_diff = float(np.max(np.abs(first[(w, i)] - ref[0])))
        pick = ref.argmax(-1)
        margin = ref[np.arange(new), pick] - ref[np.arange(new), gen]
        disagree = int(np.sum(pick != gen))
        emit(phase="serve", check="cache_vs_full_forward", prompt=L,
             first_token_logit_max_abs_diff=round(first_diff, 4),
             greedy_agree=new - disagree, of=new,
             near_ties=int(np.sum((pick != gen) & (margin <= LOGIT_TOL))),
             worst_margin=round(float(margin.max()), 4),
             disagree_at=np.flatnonzero(pick != gen).tolist(),
             tokens_head=[int(t) for t in gen[:8]])
        check(np.isfinite(ref).all() and np.isfinite(first[(w, i)]).all(),
              "non-finite logits")
        check(first_diff <= LOGIT_TOL,
              f"first-token logits differ by {first_diff} (prompt {L})")
        check(margin.max() <= LOGIT_TOL,
              f"engine token loses to the reference's by {margin.max()} "
              f"(prompt {L})")

    # the programs that ran: lowering them again finds them in the cache
    args = eng._decode_args(False)
    programs = {f"decode_{k[2]}": fn.lower(*args).compile().as_text()
                for k, fn in eng._decode_fns.items()}
    bucket = max(eng._prefill_cache)
    programs[f"prefill_{bucket}"] = eng._prefill_cache[bucket].lower(
        eng._params, jnp.zeros((1, bucket), jnp.int32), eng.pools,
        jnp.zeros((1, eng.pages_per_seq), jnp.int32),
        jnp.int32(0), eng.slot_state, np.int32(0)).compile().as_text()
    kernels = {k: kernel_census(v) for k, v in programs.items()}
    emit(phase="serve", kernels=kernels, peak_bytes=_peaks())
    if not REHEARSAL:
        check(kernels[f"prefill_{bucket}"].get("flash_attention", 0) > 0,
              f"no flash kernel in the prefill program: {kernels}")
        check(kernels["decode_paged"].get("paged_attention", 0) > 0,
              f"no paged kernel in the paged decode program: {kernels}")
        if "dense" in sides:
            check(kernels["decode_dense"]["tpu_custom_call"] > 0,
                  f"no Pallas kernel in the dense decode program: {kernels}")
    del eng, model, reference, programs
    return {"tokens": out}


# -- train4 -----------------------------------------------------------------

def phase_train4(sz, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as pt
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW, ClipGradByGlobalNorm
    from paddle_tpu.parallel import (HybridMesh, param_spec_tree,
                                     shard_layer, shard_optimizer_state,
                                     shard_tensor)
    from paddle_tpu.trainer import Trainer

    cfg, b, s, steps = sz["cfg"], sz["batch"], sz["seq"], sz["steps"]
    emit(phase="train4", config=sz["config"], dtype=cfg.dtype,
         hidden=cfg.hidden_size, ffn=cfg.intermediate_size,
         heads=[cfg.num_attention_heads, cfg.num_key_value_heads],
         vocab=cfg.vocab_size, layers=cfg.num_hidden_layers,
         depth_cut=sz["depth_cut"], batch=b, seq=s, steps=steps,
         loss_rtol=LOSS_RTOL)
    rs = np.random.RandomState(seed)
    data = rs.randint(0, cfg.vocab_size, (steps, b, s + 1)).astype(np.int32)
    runs = {}
    for name, layout in (("fsdp4", dict(fsdp=4)),
                         ("fsdp2_tp2", dict(fsdp=2, tp=2))):
        t0 = time.perf_counter()
        c0 = _COMPILE_S[0]
        pt.seed(seed)
        model = LlamaForCausalLM(cfg)
        hm = HybridMesh.build(devices=jax.devices(), **layout)
        with hm:
            shard_layer(model)
            opt = AdamW(learning_rate=1e-4, weight_decay=0.01,
                        parameters=model,
                        grad_clip=ClipGradByGlobalNorm(1.0))
            tr = Trainer(model, opt)
            tr.opt_state = shard_optimizer_state(tr.opt_state,
                                                 param_spec_tree(model))
            losses = []
            for ids in data:
                spec = P(("dp", "fsdp"), None)
                batch = {"input_ids": shard_tensor(jnp.asarray(ids[:, :-1]),
                                                   spec=spec),
                         "labels": shard_tensor(jnp.asarray(ids[:, 1:]),
                                                spec=spec)}
                losses.append(float(tr.train_step(batch)))
            hlo = tr._last_exec.as_text()
        colls, kernels, peaks = (collective_census(hlo), kernel_census(hlo),
                                 _peaks())
        emit(phase="train4", layout=name, mesh=dict(hm.mesh.shape),
             losses=[round(x, 4) for x in losses],
             loss0_expected=round(loss0_expected(cfg), 4),
             total_s=round(time.perf_counter() - t0, 2),
             compile_s=round(_COMPILE_S[0] - c0, 2), collectives=colls,
             kernels=kernels, peak_bytes=peaks, cache=_cache_counts())
        check(all(math.isfinite(x) for x in losses),
              f"{name}: non-finite loss {losses}")
        check(abs(losses[0] - loss0_expected(cfg)) <= LOSS0_ATOL,
              f"{name}: step-0 loss {losses[0]} vs "
              f"{loss0_expected(cfg):.3f} expected at random init")
        # fsdp gathers weights and reduces gradients (the compiler may
        # spell the latter reduce-scatter or all-reduce); tp all-reduces
        check("all-gather" in colls
              and ("reduce-scatter" in colls or "all-reduce" in colls)
              and ("tp" not in layout or "all-reduce" in colls),
              f"{name}: collectives {colls}")
        if not REHEARSAL:
            check(kernels.get("flash_attention", 0) >= 2
                  and kernels.get("fused_vocab_ce", 0) >= 3,
                  f"{name}: kernels missing from the step: {kernels}")
            check(all(p is not None for p in peaks)
                  and max(peaks) <= 1.5 * sum(peaks) / len(peaks),
                  f"{name}: peak bytes unbalanced across devices: {peaks}")
        runs[name] = losses
        del tr, opt, model
        emit(phase="train4", layout=name, bytes_in_use_after=_release())
    a, c = runs["fsdp4"], runs["fsdp2_tp2"]
    rel = [abs(x - y) / max(abs(x), 1e-9) for x, y in zip(a, c)]
    emit(phase="train4", check="layouts_agree",
         rel_diff=[round(r, 6) for r in rel], rtol=LOSS_RTOL)
    check(max(rel) <= LOSS_RTOL, f"layouts disagree: {a} vs {c}")
    return runs


# -- entry ------------------------------------------------------------------

def main(argv=None) -> int:
    global REHEARSAL
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU, Pallas in interpret mode; "
                         "proves control flow, never the chip")
    args = ap.parse_args(argv)
    REHEARSAL = args.rehearse
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}").strip()

    from paddle_tpu.distributed.overlap import enable_overlap
    # before jax initializes; one chip has no collectives to overlap
    overlap = enable_overlap(args.chips == 4 and not args.rehearse)
    import jax
    from paddle_tpu.core import compile_cache
    cache_dir = compile_cache.configure_compilation_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax found {device}); this script runs "
              f"on the chip only — see --rehearse", file=sys.stderr)
        return 2
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found "
              f"{device['count']} device(s)", file=sys.stderr)
        return 2
    if args.rehearse:
        _rehearsal_kernels()
    emit(phase="start", device=device, chips=args.chips, seed=args.seed,
         jax=jax.__version__, cache_dir=cache_dir,
         cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         cache=_cache_counts(), overlap=overlap["reason"],
         libtpu_init_args=os.environ.get("LIBTPU_INIT_ARGS", ""))

    sizes = _sizes(args.rehearse)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_train4(sizes["train4"], args.seed)
    else:
        phase_train(sizes["train"], args.seed)
        emit(phase="train", bytes_in_use_after=_release())
        phase_serve(sizes["serve"], args.seed)
    emit(phase="done", wall_s=round(time.perf_counter() - t0, 2),
         compile_s=round(_COMPILE_S[0], 2), cache=_cache_counts())
    verdict = {"ok": True, "device": device}
    if args.rehearse:
        verdict["rehearsal"] = True
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
