"""Benchmark entry: prints ONE JSON line with the headline metric.

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Metric: Llama pretraining tokens/sec/chip (the BASELINE.json north-star
metric); vs_baseline = achieved MFU / 0.40 target MFU (the reference
publishes no absolute numbers — BASELINE.md).

- The measured loop trains THROUGH the input pipeline: an io.DataLoader
  (worker threads + device prefetch) feeds Trainer.train_step, and the
  time spent blocked on the loader is reported as input_stall_s — SURVEY
  §7 hard-part 7 ("input pipeline feeds the chip") is on the clock.
- One configuration, as configured, or an error: without a TPU, or when a
  kernel does not compile, the run fails with a non-zero exit and prints
  no metric. Nothing is retried on a weaker configuration or on the CPU.
- Serving numbers ride along in "detail": compiled decode (generate_scan,
  dense KV cache) tokens/s and the paged-decode kernel microbench.
- One process holds the chip. The multi-device detail probes that need a
  mesh run in children pinned to the CPU platform (ROADMAP D3 removes
  them with the rest of this file).
"""

import json
import os
import sys
import time
import traceback

def _emit(payload):
    print(json.dumps(payload), flush=True)


def _log(msg):
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def _sync(x):
    import jax
    jax.block_until_ready(x)


def _cpu_child_env():
    """Environment of the CPU-mesh children: pinned to the CPU platform,
    so a child can never reach for the chip its parent holds."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _make_loader(cfg, batch_size, seq_len, steps, extra_batches=4):
    """Synthetic LM batches through the real input pipeline (worker
    threads, collate, device prefetch)."""
    import numpy as np
    from paddle_tpu.io import DataLoader, Dataset

    class SyntheticLM(Dataset):
        def __len__(self):
            return batch_size * (steps + extra_batches)

        def __getitem__(self, i):
            rs = np.random.RandomState(i)
            ids = rs.randint(0, cfg.vocab_size, (seq_len + 1,), np.int32)
            return {"input_ids": ids[:-1], "labels": ids[1:]}

    return DataLoader(SyntheticLM(), batch_size=batch_size, num_workers=2,
                      prefetch_factor=4, prefetch_to_device=True,
                      drop_last=True)


def _train_bench(cfg, batch_size, seq_len, steps, warmup,
                 superstep_probe=False):
    """Returns (tokens_per_sec_total, step_time_s, input_stall_s, loss,
    model, fenced_per_step_times, superstep_detail, cost_attr).

    ``cost_attr`` is the cost observatory's analytical attribution of the
    HEADLINE step executable's optimized HLO (flops/bytes/comm bytes +
    roofline-predicted step seconds), or None when the executable can't
    render HLO — it prices the very program the timed loop ran."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.trainer import Trainer

    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01, parameters=model)
    tr = Trainer(model, opt)

    # the superstep A/B leg consumes K(warm) + 2*n_ab extra batches
    loader = _make_loader(cfg, batch_size, seq_len, steps + warmup,
                          extra_batches=4 + (24 if superstep_probe else 0))
    it = iter(loader)

    loss = None
    _log("train: compiling + warmup")
    for _ in range(warmup):
        batch = next(it)
        loss = tr.train_step(batch)
    _sync(loss)
    _log("train: warmup done, timing")

    stall = 0.0
    t0 = time.perf_counter()
    for _ in range(steps):
        s0 = time.perf_counter()
        batch = next(it)
        stall += time.perf_counter() - s0
        loss = tr.train_step(batch)
    _sync(loss)
    dt = time.perf_counter() - t0
    _log("train: timed loop done")

    # a few FENCED steps for the auditable artifact: per-step wall times
    # with a host round-trip fence each (excluded from the headline, which
    # keeps the async-dispatch profile). Never let a transient failure
    # here discard the already-successful headline measurement.
    per_step = []
    try:
        for _ in range(3):
            batch = next(it)
            s0 = time.perf_counter()
            loss2 = tr.train_step(batch)
            _sync(loss2)
            per_step.append(round(time.perf_counter() - s0, 4))
    except Exception as e:
        _log(f"fenced-step loop failed (headline kept): {e}")

    # superstep A/B (ISSUE 2): per-step HOST dispatch overhead (wall time
    # spent enqueueing compiled programs, not waiting on them) with K=1 vs
    # K=4 over the same trainer — the amortization the superstep runtime
    # exists for. Never lets a probe failure touch the headline.
    superstep = {}
    if superstep_probe:
        try:
            K, n_ab = 4, 8
            _log("superstep: compiling K=4 scan")
            warm = [next(it) for _ in range(K)]
            tr.fit(iter(warm), steps=K, log_every=10 ** 9,
                   steps_per_dispatch=K)          # compile off the clock
            ab1 = [next(it) for _ in range(n_ab)]
            abk = [next(it) for _ in range(n_ab)]
            _log("superstep: timing K=1 vs K=4 dispatch overhead")
            tr.dispatch_stats = {"steps": 0, "dispatches": 0,
                                 "dispatch_host_s": 0.0}
            tr.fit(iter(ab1), steps=n_ab, log_every=10 ** 9)
            o1 = (tr.dispatch_stats["dispatch_host_s"]
                  / max(tr.dispatch_stats["steps"], 1))
            tr.dispatch_stats = {"steps": 0, "dispatches": 0,
                                 "dispatch_host_s": 0.0}
            tr.fit(iter(abk), steps=n_ab, log_every=10 ** 9,
                   steps_per_dispatch=K)
            ok = (tr.dispatch_stats["dispatch_host_s"]
                  / max(tr.dispatch_stats["steps"], 1))
            superstep = {
                "steps_per_dispatch": K,
                "dispatch_overhead_s_per_step_k1": round(o1, 7),
                f"dispatch_overhead_s_per_step_k{K}": round(ok, 7),
                # headline key = the superstep value (K>1 must beat k1)
                "dispatch_overhead_s_per_step": round(ok, 7),
            }
        except Exception as e:
            superstep = {"superstep_error":
                         f"{type(e).__name__}: {str(e)[:150]}"}

    # analytical attribution of the step executable that just ran (ISSUE
    # 9): ONE flop definition — the observability/costs analyzer over the
    # optimized HLO — shared with the live gauge and graph_lint's floor
    cost_attr = None
    try:
        from paddle_tpu.analysis.hlo import parse_hlo
        from paddle_tpu.observability import costs
        fn = next(iter(tr._step_exec.values()), None)
        if fn is not None and hasattr(fn, "as_text"):
            rep = costs.attribute_costs(parse_hlo(fn.as_text()))
            cost_attr = {"flops": rep.total_flops,
                         "bytes": rep.total_bytes,
                         "comm_bytes": rep.total_comm_bytes,
                         "predicted_s": rep.predicted_step_s,
                         "unmodeled_ops": sum(rep.unmodeled.values())}
    except Exception as e:
        _log(f"cost attribution failed (headline kept): {e}")

    tokens = batch_size * seq_len * steps
    return (tokens / dt, dt / steps, stall / steps, float(loss),
            model, per_step, superstep, cost_attr)


# the headline TPU training config (also chip_smoke.py's train phase)
_HEADLINE_TPU_CFG = dict(vocab_size=32000, hidden_size=1536,
                         intermediate_size=4608, num_hidden_layers=12,
                         num_attention_heads=12, num_key_value_heads=4,
                         max_position_embeddings=2048, dtype="bfloat16")


def _decode_bench(cfg, on_tpu):
    """Serving-path numbers (detail): compiled dense-cache decode via
    generate_scan, and the paged-decode kernel step time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference.generation import (GenerationConfig,
                                                 generate_paged,
                                                 generate_scan)
    out = {}
    # shared serving-model setup in its OWN try: a failure here (e.g. OOM
    # building a second model next to the training one) must degrade to a
    # decode_error detail, never zero the already-measured training number
    try:
        # max_position 1152 covers the chunked-prefill leg's 896-token
        # long prompt + 32 new + page padding (a 512 table crashed that
        # leg: rope cos [512] broadcast against 896 positions)
        dcfg = LlamaConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            num_hidden_layers=cfg.num_hidden_layers,
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            max_position_embeddings=1152, dtype=cfg.dtype) \
            if on_tpu else LlamaConfig.tiny()
        pt.seed(0)
        dmodel = LlamaForCausalLM(dcfg)
        B, prompt_len, new_tokens = (8, 128, 128) if on_tpu else (2, 8, 8)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, dcfg.vocab_size, (B, prompt_len)))
        gc = GenerationConfig(max_new_tokens=new_tokens, do_sample=False)
    except Exception as e:
        out["decode_error"] = f"setup: {type(e).__name__}: {str(e)[:150]}"
        return out
    try:
        _log("decode: compiling generate_scan")
        toks = generate_scan(dmodel, ids, gc)          # compile
        _sync(toks)
        t0 = time.perf_counter()
        toks = generate_scan(dmodel, ids, gc)
        _sync(toks)
        dt = time.perf_counter() - t0
        _log("decode: generate_scan timed")
        out["decode_tokens_per_sec"] = round(B * new_tokens / dt, 1)
        out["decode_batch"] = B
        out["decode_new_tokens"] = new_tokens
    except Exception as e:
        out["decode_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # paged-KV serving path (vLLM-style): same decode through page
        # pools + the Pallas paged kernel on TPU
        _log("decode: compiling generate_paged")
        toks = generate_paged(dmodel, ids, gc, page_size=128 if on_tpu else 8)
        _sync(toks)
        t0 = time.perf_counter()
        toks = generate_paged(dmodel, ids, gc, page_size=128 if on_tpu else 8)
        _sync(toks)
        dt = time.perf_counter() - t0
        _log("decode: generate_paged timed")
        out["paged_decode_tokens_per_sec"] = round(B * new_tokens / dt, 1)
    except Exception as e:
        out["paged_generate_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # continuous-batching engine throughput: staggered prompts through
        # fewer slots than requests (admission + retirement + lazy paging
        # on the clock) — the serving-system layer over the paged kernel
        from paddle_tpu.inference import ContinuousBatchingEngine
        # decode_block: one compiled K-token scan per scheduler tick, so
        # the host round trip is paid per-block, not per-token (the
        # raw kernel decode rate is decode_tokens_per_sec above). The
        # async engine's on-device stop detection keeps any K exact, and
        # its depth-2 dispatch window (inflight_depth below) hides the
        # host bookkeeping of block N under the device's block N+1.
        n_req, slots = (16, 4) if on_tpu else (4, 2)
        s_new = min(new_tokens, 64 if on_tpu else 24)
        s_block = 16 if on_tpu else 8
        eng = ContinuousBatchingEngine(
            dmodel, max_batch=slots, page_size=128 if on_tpu else 8,
            max_len=(prompt_len + new_tokens + 128) if on_tpu else 32,
            generation_config=GenerationConfig(max_new_tokens=s_new,
                                               do_sample=False),
            decode_block=s_block)
        rs = np.random.RandomState(1)
        stag = 8 if on_tpu else 2
        lens = [prompt_len - (i % 3) * stag for i in range(n_req)]
        reqs = [rs.randint(0, dcfg.vocab_size, (L,)).astype(np.int32)
                for L in lens]
        # every 3rd request SAMPLES (temp/top-k/top-p inside the compiled
        # block, round-4 verdict missing #2) — per-slot knob arrays, so
        # greedy and sampled share executables
        sample_gc = GenerationConfig(max_new_tokens=s_new, do_sample=True,
                                     temperature=0.8, top_k=40, top_p=0.95)

        def _submit_mix(eng, prompts):
            n_sampled = 0
            for i, r in enumerate(prompts):
                if i % 3 == 2:
                    eng.submit(r, generation_config=sample_gc)
                    n_sampled += 1
                else:
                    eng.submit(r)
            return n_sampled
        _log("decode: continuous-batching engine (warmup)")
        # warm the engine's compiled surfaces (one prefill per distinct
        # bucket + greedy AND sampling decode blocks) so the TIMED window
        # measures serving, not jit compiles — the steady-state number a
        # serving deployment sees. Warmup latencies are dropped from the
        # percentile stats.
        for L in sorted(set(lens)):        # greedy-only pass: (K, False)
            eng.submit(reqs[lens.index(L)][:L])
        eng.run()
        for L in sorted(set(lens)):        # sampled pass: (K, True)
            eng.submit(reqs[lens.index(L)][:L],
                       generation_config=sample_gc)
        eng.run()
        eng.reset_latency_stats()
        _log("decode: continuous-batching engine")
        n_sampled = _submit_mix(eng, reqs)
        pre0 = eng.preemptions
        t0 = time.perf_counter()
        results = eng.run()
        dt = time.perf_counter() - t0
        total = sum(len(v) for v in results.values())
        out["serving_tokens_per_sec"] = round(total / dt, 1)
        out["serving_requests"] = n_req
        out["serving_sampled_requests"] = n_sampled
        out["serving_slots"] = slots
        out["serving_decode_block"] = s_block
        out["inflight_depth"] = eng.async_depth
        # context-aware dense/paged dispatch (VERDICT item 4): which
        # attention path each decode block actually took
        out["serving_attn_dense_ticks"] = eng.attn_path_ticks["dense"]
        out["serving_attn_paged_ticks"] = eng.attn_path_ticks["paged"]
        out["serving_attn_crossover"] = eng.attn_crossover
        # how much of the raw paged-decode rate the serving layer keeps:
        # the host-overhead tax the async engine exists to eliminate
        if out.get("paged_decode_tokens_per_sec"):
            out["serving_decode_efficiency"] = round(
                out["serving_tokens_per_sec"]
                / out["paged_decode_tokens_per_sec"], 3)
        # per-window delta: eng.preemptions is a lifetime counter
        out["serving_preemptions"] = eng.preemptions - pre0
        lat = eng.latency_stats()
        if lat:
            out["serving_ttft_p50_s"] = round(lat["ttft_p50_s"], 4)
            out["serving_ttft_p99_s"] = round(lat["ttft_p99_s"], 4)
            out["serving_latency_p50_s"] = round(lat["latency_p50_s"], 4)
            out["serving_latency_p99_s"] = round(lat["latency_p99_s"], 4)

        # strict per-tick row (decode_block=1, CPU tier): like-for-like
        # with rounds <= 5, which timed the engine at K=1 — isolates the
        # async-loop win (device-resident state + pipelined dispatch)
        # from the larger decode block on-device stop detection enables
        if not on_tpu:
            eng1 = ContinuousBatchingEngine(
                dmodel, max_batch=slots, page_size=8, max_len=32,
                generation_config=GenerationConfig(max_new_tokens=s_new,
                                                   do_sample=False),
                decode_block=1)
            for L in sorted(set(lens)):
                eng1.submit(reqs[lens.index(L)][:L])
            eng1.run()
            for L in sorted(set(lens)):
                eng1.submit(reqs[lens.index(L)][:L],
                            generation_config=sample_gc)
            eng1.run()
            _submit_mix(eng1, reqs)
            t0 = time.perf_counter()
            results1 = eng1.run()
            dt1 = time.perf_counter() - t0
            out["serving_k1_tokens_per_sec"] = round(
                sum(len(v) for v in results1.values()) / dt1, 1)

        # 64-request mixed-length load ON the chip (round-4 weak #3: the
        # load test ran only on CPU). Same buckets + decode blocks as the
        # window above — zero extra compiles, this times scheduling +
        # paging + decode at queue depth 16x slots.
        if on_tpu:
            eng.reset_latency_stats()
            reqs64 = [rs.randint(0, dcfg.vocab_size,
                                 (lens[i % n_req],)).astype(np.int32)
                      for i in range(64)]
            _log("decode: 64-request load")
            n_sampled64 = _submit_mix(eng, reqs64)
            pre0 = eng.preemptions
            t0 = time.perf_counter()
            results = eng.run()
            dt = time.perf_counter() - t0
            total = sum(len(v) for v in results.values())
            lat = eng.latency_stats()
            out["serving_load64_tokens_per_sec"] = round(total / dt, 1)
            out["serving_load64_sampled"] = n_sampled64
            out["serving_load64_preemptions"] = eng.preemptions - pre0
            if lat:
                out["serving_load64_ttft_p99_s"] = round(
                    lat["ttft_p99_s"], 4)
                out["serving_load64_latency_p99_s"] = round(
                    lat["latency_p99_s"], 4)
    except Exception as e:
        out["serving_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # token-level speculative decoding (ISSUE 6): spec-on ÷ spec-off
        # A/B on a REPETITIVE-text workload (the n-gram prompt-lookup
        # drafter's target regime — quoting/templated/code-ish traffic).
        # Interleaved min-of-rounds, identical engines modulo the spec_k
        # knob, greedy (so both legs emit bit-identical streams and the
        # ratio is pure speed). Ratios, not absolute tok/s, are the
        # signal on this host (memory: bench-cpu-variance).
        from paddle_tpu.inference import ContinuousBatchingEngine
        sp_rs = np.random.RandomState(3)
        sp_len, sp_new, sp_k, sp_rounds = \
            (96, 48, 4, 3) if on_tpu else (64, 48, 4, 3)
        sp_page = 128 if on_tpu else 8
        # the workload: each prompt is the MODEL'S OWN greedy text (seed
        # + generate_scan continuation) — generation then continues the
        # pattern already present in the prompt, which is the regime
        # prompt-lookup drafting targets (quoting / templated /
        # input-grounded output). Random-token prompts would measure the
        # drafter's worst case, not the feature.
        sp_seeds = jnp.asarray(sp_rs.randint(0, dcfg.vocab_size, (4, 6)))
        sp_gc = GenerationConfig(max_new_tokens=sp_len - 6,
                                 do_sample=False)
        sp_prompts = np.asarray(
            generate_scan(dmodel, sp_seeds, sp_gc)).astype(np.int32)
        for nbatch, sfx in ((1, ""), (4, "_b4")):
            _log(f"decode: speculative A/B (batch {nbatch})")
            prompts = [sp_prompts[i] for i in range(nbatch)]
            legs, engines = {}, {}
            # two off legs: decode_block=1 (the default-config knob flip
            # the headline ratio measures) AND decode_block=spec_k+1
            # (same host-round-trip amortization as a spec tick, so the
            # _vs_block row isolates speculation's per-weight-pass win
            # from the block amortization decode_block already buys)
            for name, k, blk in (("off", 0, 1), ("offblk", 0, sp_k + 1),
                                 ("on", sp_k, 1)):
                eng = ContinuousBatchingEngine(
                    dmodel, max_batch=nbatch, page_size=sp_page,
                    max_len=sp_len + sp_new + sp_page,
                    generation_config=GenerationConfig(
                        max_new_tokens=sp_new, do_sample=False),
                    decode_block=blk, spec_k=k)
                for p in prompts:                  # warm the executables
                    eng.submit(p)
                legs[name] = {r: v.tolist() for r, v in eng.run().items()}
                engines[name] = eng
            assert (list(legs["on"].values())
                    == list(legs["off"].values())
                    == list(legs["offblk"].values())), \
                "spec-on stream diverged from spec-off"
            best = {name: float("inf") for name in engines}
            for _ in range(sp_rounds):
                for name, eng in engines.items():  # interleaved legs
                    for p in prompts:
                        eng.submit(p)
                    t0 = time.perf_counter()
                    res = eng.run()
                    dt = time.perf_counter() - t0
                    ntok = sum(len(v) for v in res.values())
                    best[name] = min(best[name], dt / max(ntok, 1))
            out[f"spec_decode_speedup{sfx}"] = round(
                best["off"] / best["on"], 3)
            out[f"spec_decode_speedup_vs_block{sfx}"] = round(
                best["offblk"] / best["on"], 3)
            out[f"spec_on_tokens_per_sec{sfx}"] = round(1 / best["on"], 1)
            out[f"spec_off_tokens_per_sec{sfx}"] = round(1 / best["off"], 1)
            out[f"spec_offblk_tokens_per_sec{sfx}"] = round(
                1 / best["offblk"], 1)
            sp = engines["on"].spec_stats()
            out[f"spec_accept_rate{sfx}"] = round(
                sp.get("spec_accept_rate", 0.0), 3)
            out[f"spec_mean_accepted_len{sfx}"] = round(
                sp.get("spec_mean_accepted_len", 1.0), 2)
        out["spec_k"] = sp_k
    except Exception as e:
        out["spec_decode_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # radix prefix-shared KV (ISSUE 7): N requests over a COMMON long
        # system prompt, prefix sharing ON vs OFF — identical engines
        # modulo the knob, streams asserted identical, interleaved
        # min-of-rounds, reported as RATIOS (memory: bench-cpu-variance).
        # The warmup run seeds the ON leg's radix tree (the steady state
        # for shared-prompt traffic), so the timed rounds measure
        # mapped-pages admission (COW + 1-token re-forward) against full
        # prefills; TTFT is the metric admission controls, so the
        # headline is mean-TTFT-off / mean-TTFT-on at p50.
        from paddle_tpu.inference import ContinuousBatchingEngine
        px_rs = np.random.RandomState(5)
        px_shared, px_tail, px_new = (512, 32, 16) if on_tpu \
            else (160, 8, 4)
        px_page = 128 if on_tpu else 8
        px_n, px_rounds = 8, 3
        shared_ids = px_rs.randint(0, dcfg.vocab_size,
                                   (px_shared,)).astype(np.int32)
        px_prompts = [
            np.concatenate([shared_ids,
                            px_rs.randint(0, dcfg.vocab_size,
                                          (px_tail,)).astype(np.int32)])
            for _ in range(px_n)]
        _log("decode: prefix-sharing A/B")
        px_engines, px_legs = {}, {}
        for name, knob in (("off", False), ("on", True)):
            eng = ContinuousBatchingEngine(
                dmodel, max_batch=px_n, page_size=px_page,
                max_len=px_shared + px_tail + px_new + px_page,
                generation_config=GenerationConfig(
                    max_new_tokens=px_new, do_sample=False),
                prefix_cache=knob)
            for p in px_prompts:       # warm executables (+ the tree)
                eng.submit(p)
            px_legs[name] = [v.tolist() for v in eng.run().values()]
            px_engines[name] = eng
        assert px_legs["on"] == px_legs["off"], \
            "prefix-on stream diverged from prefix-off"
        best = {name: float("inf") for name in px_engines}
        for _ in range(px_rounds):
            streams = {}
            for name, eng in px_engines.items():   # interleaved legs
                eng.reset_latency_stats()
                for p in px_prompts:
                    eng.submit(p)
                streams[name] = [v.tolist() for v in eng.run().values()]
                best[name] = min(best[name],
                                 eng.latency_stats()["ttft_p50_s"])
            # warm-tree rounds are all COW fast-path admits — the path
            # the timed window measures must stay parity-checked too
            assert streams["on"] == streams["off"], \
                "prefix fast-path stream diverged from prefix-off"
        out["prefix_reuse_ttft_speedup"] = round(
            best["off"] / best["on"], 3)
        out["prefix_ttft_off_p50_s"] = round(best["off"], 5)
        out["prefix_ttft_on_p50_s"] = round(best["on"], 5)
        pxs = px_engines["on"].prefix_stats()
        out["prefix_hit_rate"] = round(pxs.get("prefix_hit_rate", 0.0), 3)
        out["prefix_cow_copies"] = int(pxs.get("prefix_cow_copies", 0))
        out["prefix_shared_pages"] = int(
            pxs.get("prefix_shared_pages", 0))
        out["prefix_shared_prompt_tokens"] = px_shared
        del px_engines
    except Exception as e:
        out["prefix_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # chunked-prefill in its long-prompt regime (round-4 weak #3: it
        # was only measured at short prompts, where it costs throughput).
        # One long prompt + 8 short ones; chunked ON bounds the per-tick
        # stall the long prefill inflicts on the shorts' TTFT.
        if on_tpu:
            long_len, short_len, s_new2 = 896, 128, 32
            rs2 = np.random.RandomState(4)
            longp = rs2.randint(0, dcfg.vocab_size, (long_len,)) \
                .astype(np.int32)
            shorts = [rs2.randint(0, dcfg.vocab_size, (short_len,))
                      .astype(np.int32) for _ in range(8)]
            cp_res = {}
            for label, ck in (("chunked", True), ("unchunked", False)):
                eng2 = ContinuousBatchingEngine(
                    dmodel, max_batch=4, page_size=128,
                    max_len=long_len + s_new2 + 128,
                    generation_config=GenerationConfig(
                        max_new_tokens=s_new2, do_sample=False),
                    decode_block=8, chunked_prefill=ck,
                    prefill_chunk=128 if ck else None)
                # warm compiles (prefill buckets / chunk fn + decode)
                _log(f"decode: chunked-prefill A/B warmup ({label})")
                eng2.submit(longp)
                eng2.submit(shorts[0])
                eng2.run()
                eng2.reset_latency_stats()
                eng2.submit(longp)
                for r in shorts:
                    eng2.submit(r)
                t0 = time.perf_counter()
                res = eng2.run()
                dt = time.perf_counter() - t0
                lat = eng2.latency_stats()
                cp_res[label] = (sum(len(v) for v in res.values()) / dt,
                                 lat.get("ttft_p99_s", 0.0),
                                 lat.get("itl_p99_s", 0.0))
            out["chunked_prefill_long_tokens_per_sec"] = round(
                cp_res["chunked"][0], 1)
            out["unchunked_long_tokens_per_sec"] = round(
                cp_res["unchunked"][0], 1)
            out["chunked_prefill_long_ttft_p99_s"] = round(
                cp_res["chunked"][1], 4)
            out["unchunked_long_ttft_p99_s"] = round(
                cp_res["unchunked"][1], 4)
            # the fairness metric chunked prefill exists for: the worst
            # per-tick stall a RUNNING request sees while the long
            # prompt prefills
            out["chunked_prefill_long_itl_p99_s"] = round(
                cp_res["chunked"][2], 4)
            out["unchunked_long_itl_p99_s"] = round(
                cp_res["unchunked"][2], 4)
    except Exception as e:
        out["chunked_prefill_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # serving fabric (ISSUE 12): 2 in-process replicas under a mixed
        # two-tenant trace — 4 shared-prefix families (tenant "shared")
        # + cold long prompts (tenant "cold") — affinity vs round-robin,
        # interleaved min-of-rounds, RATIO rows (bench-variance policy).
        # The pool is sized so ONE replica cannot hold every family's
        # prefix: affinity partitions families across replicas and every
        # admit hits; round-robin scatters them and the trees thrash.
        from paddle_tpu.serving_fabric import (InProcTransport,
                                               ServingFabric,
                                               TenantFairPolicy,
                                               build_replicas)
        fb_page = 128 if on_tpu else 8
        # family prefixes sized so a MISS costs a real prefill (the PR 7
        # leg's scale: 160 shared tokens on cpu, 512 on tpu); TPU cold
        # prompts capped at 896 — the dcfg rope table (max_position
        # 1152) must cover prompt + new, same bound the chunked leg
        # lives with
        fb_fam_pages, fb_tail, fb_new = (4, 32, 16) if on_tpu \
            else (20, 4, 6)
        fb_cold_pages = 7 if on_tpu else 10
        n_fam, per_fam, n_cold, fb_rounds = 4, 3, 2, 3
        fb_rs = np.random.RandomState(6)
        fam_heads = [fb_rs.randint(0, dcfg.vocab_size,
                                   (fb_fam_pages * fb_page,))
                     .astype(np.int32) for _ in range(n_fam)]
        colds = [fb_rs.randint(0, dcfg.vocab_size,
                               (fb_cold_pages * fb_page,))
                 .astype(np.int32) for _ in range(n_cold)]

        # ONE fixed trace — shuffled so round-robin cannot accidentally
        # partition the families — reused by every leg and round: the
        # A/B compares routing policies, so both legs must see the same
        # prompts (and repeat rounds measure the steady state)
        fb_fixed_trace = []
        for j in range(per_fam):
            for h in fam_heads:
                fb_fixed_trace.append(("shared", np.concatenate(
                    [h, fb_rs.randint(0, dcfg.vocab_size, (fb_tail,))
                     .astype(np.int32)])))
        for c in colds:
            fb_fixed_trace.append(("cold", c))
        fb_order = np.random.RandomState(3).permutation(
            len(fb_fixed_trace))
        fb_fixed_trace = [fb_fixed_trace[i] for i in fb_order]

        def fb_trace():
            return fb_fixed_trace

        fb_max_len = (max(fb_fam_pages, fb_cold_pages) + 3) * fb_page
        # per-replica pool: HALF the families' prefixes + a working set
        # fit, all four do NOT — affinity partitions 2 families per
        # replica and keeps hitting, round-robin sprays all 4 onto both
        # and the trees thrash (the regime the router exists for)
        fb_pages = (n_fam // 2) * fb_fam_pages + (8 if on_tpu else 4)

        def fb_build(policy):
            reps = build_replicas(
                dmodel, 2, page_size=fb_page, max_len=fb_max_len,
                max_batch=8, num_pages=fb_pages,
                names=[f"{policy[:2]}0", f"{policy[:2]}1"],
                generation_config=GenerationConfig(
                    max_new_tokens=fb_new, do_sample=False))
            return ServingFabric(InProcTransport(reps), policy=policy,
                                 fair=TenantFairPolicy(),
                                 name=f"bench-{policy}")

        _log("decode: serving-fabric affinity-vs-round-robin A/B")
        legs = {p: fb_build(p) for p in ("affinity", "round-robin")}
        warm_streams = {}
        for p, fb in legs.items():
            # TWO warmup rounds: round 1 compiles the cold-prefill
            # buckets and seeds the trees, round 2 reaches the steady
            # eviction state whose suffix-prefill widths the timed
            # rounds reuse (a fresh width mid-round is a ~1s retrace
            # that would poison a TTFT percentile)
            for _ in range(2):
                fids = [fb.submit(pr, fb_new, tenant=tn)
                        for tn, pr in fb_trace()]
                res = fb.run()
            warm_streams[p] = [res[f].tolist() for f in fids]
        assert warm_streams["affinity"] == warm_streams["round-robin"], \
            "fabric streams diverged across routing policies"
        best_ttft = {p: float("inf") for p in legs}
        best_tps = {p: 0.0 for p in legs}
        for _ in range(fb_rounds):
            for p, fb in legs.items():   # interleaved legs
                fb.reset_latency_stats()
                fids = [fb.submit(pr, fb_new, tenant=tn)
                        for tn, pr in fb_trace()]
                t0 = time.perf_counter()
                res = fb.run()
                dt = time.perf_counter() - t0
                toks = sum(len(v) for v in res.values())
                best_tps[p] = max(best_tps[p], toks / dt)
                best_ttft[p] = min(
                    best_ttft[p], fb.latency_stats()["ttft_p50_s"])
        out["fabric_affinity_ttft_speedup"] = round(
            best_ttft["round-robin"] / best_ttft["affinity"], 3)
        out["fabric_goodput_ratio"] = round(
            best_tps["affinity"] / best_tps["round-robin"], 3)
        out["fabric_affinity_ttft_p50_s"] = round(
            best_ttft["affinity"], 5)
        out["fabric_rr_ttft_p50_s"] = round(
            best_ttft["round-robin"], 5)
        st = legs["affinity"].stats()
        out["fabric_affinity_hits"] = st["affinity_hits"]
        out["fabric_routed"] = st["routed"]
        for p, fb in legs.items():
            hr = [round(r.engine.prefix_hit_tokens
                        / max(r.engine._prefix_prompt_tokens, 1), 3)
                  for r in fb.transport._replicas.values()]
            out[f"fabric_{p.replace('-', '_')}_hit_rates"] = hr
        del legs

        # disaggregation A/B: same 3-replica capacity, mixed trace of
        # decode-heavy shorts + the cold long prompts; WITH a dedicated
        # prefill replica + handoff the decode replicas never run the
        # long cold prefill, so their ITL p99 holds — the ratio row is
        # disagg ÷ no-disagg p99 ITL (< 1 is the win, worse=higher)
        _log("decode: serving-fabric disaggregation A/B")
        shorts = [fb_rs.randint(0, dcfg.vocab_size, (fb_page - 2,))
                  .astype(np.int32) for _ in range(6)]

        def dg_build(disagg):
            reps = build_replicas(
                dmodel, 3,
                roles=(["prefill", "both", "both"] if disagg
                       else ["both"] * 3),
                page_size=fb_page, max_len=fb_max_len, max_batch=4,
                names=[f"dg{'a' if disagg else 'b'}{i}"
                       for i in range(3)],
                generation_config=GenerationConfig(
                    max_new_tokens=fb_new, do_sample=False))
            return ServingFabric(
                InProcTransport(reps), policy="least-loaded",
                disagg_threshold_tokens=(2 * fb_page if disagg
                                         else None),
                name=f"bench-{'disagg' if disagg else 'plain'}")

        def dg_run(fb):
            fids = [fb.submit(s, fb_new, tenant="short")
                    for s in shorts[:3]]
            fids += [fb.submit(c, fb_new, tenant="cold")
                     for c in colds]
            fids += [fb.submit(s, fb_new, tenant="short")
                     for s in shorts[3:]]
            res = fb.run()
            return [res[f].tolist() for f in fids]

        dg_legs = {lbl: dg_build(d) for lbl, d in (("disagg", True),
                                                   ("plain", False))}
        dg_warm = {lbl: dg_run(fb) for lbl, fb in dg_legs.items()}
        assert dg_warm["disagg"] == dg_warm["plain"], \
            "disaggregated streams diverged from plain fabric"
        dg_itl = {lbl: float("inf") for lbl in dg_legs}
        for _ in range(fb_rounds):
            for lbl, fb in dg_legs.items():
                fb.reset_latency_stats()
                dg_run(fb)
                dg_itl[lbl] = min(dg_itl[lbl],
                                  fb.latency_stats()["itl_p99_s"])
        out["fabric_p99_itl_with_disagg_ratio"] = round(
            dg_itl["disagg"] / dg_itl["plain"], 3)
        out["fabric_disagg_itl_p99_s"] = round(dg_itl["disagg"], 5)
        out["fabric_plain_itl_p99_s"] = round(dg_itl["plain"], 5)
        out["fabric_handoffs"] = dg_legs["disagg"].stats()["handoffs"]
        out["fabric_handoff_bytes"] = \
            dg_legs["disagg"].stats()["handoff_bytes"]
        del dg_legs
    except Exception as e:
        out["fabric_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # front-door robustness (ISSUE 16): two pinned ratio rows over
        # the full client → FrontDoor → fabric stack (legs live in
        # tools/load_test.py so the CI smoke and the bench share one
        # harness).
        # 1) goodput under 2x+ offered load, shed ladder on ÷ off: both
        #    legs share ONE calibrated deadline; shed-off admits deep
        #    queue positions, burns their prefill/partial decode, then
        #    the deadline cancels them — shed-on refuses them typed at
        #    admission and finishes what it admits (>1 = shedding wins).
        # 2) p99 TTFT with a replica HUNG mid-run, breaker budgets
        #    tight ÷ loose (8x): "off" is a loose budget, not none — an
        #    unbounded poll on a hung replica wedges the driver forever
        #    (<1 = the breaker converts the hang into a fast failover).
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import load_test as _lt
        _log("decode: front-door shed-on-vs-off goodput under overload")
        fd_on = _lt.overload_leg(dmodel, shed=True)
        fd_off = _lt.overload_leg(dmodel, shed=False,
                                  deadline_ms=fd_on["deadline_ms"])
        out["frontdoor_goodput_under_overload"] = round(
            fd_on["goodput_tps"] / max(fd_off["goodput_tps"], 1e-9), 3)
        out["frontdoor_shed_on_goodput_tps"] = round(
            fd_on["goodput_tps"], 1)
        out["frontdoor_shed_off_goodput_tps"] = round(
            fd_off["goodput_tps"], 1)
        out["frontdoor_shed_on_completed"] = fd_on["completed"]
        out["frontdoor_shed_off_completed"] = fd_off["completed"]
        _log("decode: front-door hung-replica breaker-vs-loose TTFT")
        fd_tight = _lt.hang_leg(dmodel, poll_budget_s=0.75)
        fd_loose = _lt.hang_leg(dmodel, poll_budget_s=6.0)
        out["frontdoor_p99_ttft_with_breaker_ratio"] = round(
            fd_tight["ttft_p99_s"] / max(fd_loose["ttft_p99_s"], 1e-9),
            3)
        out["frontdoor_breaker_ttft_p99_s"] = round(
            fd_tight["ttft_p99_s"], 4)
        out["frontdoor_nobreaker_ttft_p99_s"] = round(
            fd_loose["ttft_p99_s"], 4)
    except Exception as e:
        out["frontdoor_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # distributed request tracing (ISSUE 19): one fabric wave traced
        # ÷ untraced, interleaved min-of-rounds on the same warmed
        # replicas. Prices the span machinery (router queue/route/submit
        # + engine queue/resident/prefill/decode spans, per-request) —
        # healthy is ~1.0; a drift means a hot-path site stopped
        # honoring the attribute-load-plus-branch disabled contract.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import load_test as _lt2
        _log("decode: request-tracing overhead (traced vs untraced wave)")
        tr_leg = _lt2.trace_overhead_legs(dmodel)
        out["trace_overhead_ratio"] = round(tr_leg["ratio"], 3)
        out["trace_traced_wall_s"] = round(tr_leg["wall_on_s"], 4)
        out["trace_untraced_wall_s"] = round(tr_leg["wall_off_s"], 4)
        out["trace_complete_traces"] = tr_leg["traces"]
    except Exception as e:
        out["trace_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # quantized serving A/B (ISSUE 17): int8 weights + int8 KV pages
        # vs the bf16 engine — identical engines modulo the quant knobs,
        # interleaved min-of-rounds, RATIO rows (memory:
        # bench-cpu-variance). The bf16 leg is re-checked against an
        # independent generate_scan stream so quant-knob bleed between
        # the A/B engines is caught, not averaged in.
        from paddle_tpu.inference import ContinuousBatchingEngine
        from paddle_tpu.quantization import quantize_model
        _log("decode: quantizing serving model (int8 weights + int8 KV)")
        qmodel = quantize_model(dmodel, kv_dtype="int8")
        qz_rs = np.random.RandomState(7)
        qz_page = 128 if on_tpu else 8
        # the A/B runs at EQUAL HBM budget — the deployment question
        # quantization answers is "what does this pool buy me", not
        # "what does a pool of unbounded pages buy me". Both engines
        # get the pages the SAME byte budget affords; the workload's
        # working set exceeds the bf16 allotment, so the bf16 leg pays
        # recompute-preemptions while the int8 leg stays resident.
        # (Unconstrained, the int8 leg LOSES on CPU — per-call dequant
        # with no HBM to save; TPU is the target regime.)
        # 4-page prompt + 3 pages of decode growth = 7 pages per slot;
        # the budget holds ~3 bf16 slots, so the bf16 leg both preempts
        # (prefill replay) and decodes NARROW — the int8 leg's pages
        # keep all 8 slots resident, and the per-tick cost of a decode
        # batch is nearly flat in width, so wider residency is the win
        qz_len, qz_new, qz_rounds = 4 * qz_page, 3 * qz_page, 3
        qz_n = 8
        qz_budget_pages = 22     # bf16 pages: ~3 resident 7-page slots

        def _qz_page_bytes(model):
            core = getattr(model, "model", model)
            sizes = []
            for np_ in (1, 2):
                pools, _ = core.alloc_paged_caches(1, np_ * qz_page,
                                                   qz_page)
                sizes.append(sum(a.size * a.dtype.itemsize
                                 for e in pools for a in e))
            return sizes[1] - sizes[0]

        qz_pb = {"bf16": _qz_page_bytes(dmodel),
                 "int8": _qz_page_bytes(qmodel)}
        qz_budget = qz_budget_pages * qz_pb["bf16"]
        qz_prompts = [qz_rs.randint(0, dcfg.vocab_size, (qz_len,))
                      .astype(np.int32) for _ in range(qz_n)]
        ref_gc = GenerationConfig(max_new_tokens=qz_new, do_sample=False)
        qz_ref = [np.asarray(generate_scan(
            dmodel, jnp.asarray(p)[None], ref_gc))[0, len(p):].tolist()
            for p in qz_prompts]
        qz_engines, qz_streams = {}, {}
        for name, mdl in (("bf16", dmodel), ("int8", qmodel)):
            eng = ContinuousBatchingEngine(
                mdl, max_batch=qz_n, page_size=qz_page,
                max_len=qz_len + qz_new + qz_page,
                num_pages=int(qz_budget // qz_pb[name]),
                generation_config=ref_gc)
            for p in qz_prompts:               # warm the executables
                eng.submit(p)
            qz_streams[name] = [v.tolist() for v in eng.run().values()]
            qz_engines[name] = eng
        # preemption replay is exact (recompute policy), so the budget
        # squeeze cannot change the bf16 stream — this assert holds
        # under thrash, and catches quant-knob bleed between the legs
        assert qz_streams["bf16"] == qz_ref, \
            "bf16 reference leg diverged from generate_scan (knob bleed)"
        # greedy agreement of the quantized streams vs the bf16
        # reference (free-running, so one near-tie flip cascades — the
        # pinned floor lives in the tests; here it's a tracked row)
        agree = [sum(a == b for a, b in zip(s, r)) / max(len(r), 1)
                 for s, r in zip(qz_streams["int8"], qz_ref)]
        out["quant_stream_agreement"] = round(sum(agree) / len(agree), 3)
        _log("decode: quantized A/B timed rounds")
        best = {name: float("inf") for name in qz_engines}
        preempt = {name: 0 for name in qz_engines}
        for _ in range(qz_rounds):
            for name, eng in qz_engines.items():   # interleaved legs
                for p in qz_prompts:
                    eng.submit(p)
                pre0 = eng.preemptions
                t0 = time.perf_counter()
                res = eng.run()
                dt = time.perf_counter() - t0
                preempt[name] += eng.preemptions - pre0
                ntok = sum(len(v) for v in res.values())
                best[name] = min(best[name], dt / max(ntok, 1))
        out["quant_decode_speedup"] = round(best["bf16"] / best["int8"],
                                            3)
        out["quant_int8_tokens_per_sec"] = round(1 / best["int8"], 1)
        out["quant_bf16_tokens_per_sec"] = round(1 / best["bf16"], 1)
        out["quant_bf16_preemptions"] = preempt["bf16"]
        out["quant_int8_preemptions"] = preempt["int8"]
        out["quant_budget_pages_bf16"] = int(qz_budget // qz_pb["bf16"])
        out["quant_budget_pages_int8"] = int(qz_budget // qz_pb["int8"])
        out["quant_kv_ticks"] = qz_engines["int8"].kv_quant_ticks
        # serving_decode_efficiency re-measured on the quantized leg:
        # int8 engine tok/s over the raw int8 paged-decode rate (same
        # definition as the bf16 row above)
        toks = generate_paged(qmodel, ids, gc, page_size=qz_page)
        _sync(toks)
        t0 = time.perf_counter()
        toks = generate_paged(qmodel, ids, gc, page_size=qz_page)
        _sync(toks)
        qraw = B * new_tokens / (time.perf_counter() - t0)
        out["quant_paged_decode_tokens_per_sec"] = round(qraw, 1)
        out["quant_serving_decode_efficiency"] = round(
            (1 / best["int8"]) / qraw, 3)
        del qz_engines
    except Exception as e:
        out["quant_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # KV capacity at EQUAL HBM budget (ISSUE 17): fix a byte budget,
        # give each pool dtype the pages that budget affords, then ramp
        # concurrent slots until the first recompute-preemption — the
        # ratio is the "~2x users per replica" claim, measured through
        # the engine's own allocator/preemption machinery rather than
        # arithmetic on dtype widths.
        from paddle_tpu.inference import ContinuousBatchingEngine
        from paddle_tpu.quantization import quantize_model
        qz_page = 128 if on_tpu else 8
        qmodel2 = quantize_model(dmodel, kv_dtype="int8")
        cap_rs = np.random.RandomState(8)

        def _page_bytes(model):
            core = getattr(model, "model", model)
            sizes = []
            for np_ in (1, 2):
                pools, _ = core.alloc_paged_caches(1, np_ * qz_page,
                                                   qz_page)
                sizes.append(sum(a.size * a.dtype.itemsize
                                 for e in pools for a in e))
            return sizes[1] - sizes[0]

        pb = {"bf16": _page_bytes(dmodel), "int8": _page_bytes(qmodel2)}
        out["quant_kv_page_bytes_ratio"] = round(
            pb["bf16"] / pb["int8"], 3)
        # budget = 13 bf16 pages: 1 reserved + 4 slots x 3 pages each
        # (2-page prompt + growth page); int8 affords ~2x the pages
        cap_budget = 13 * pb["bf16"]
        cap_prompt, cap_new, cap_max = 2 * qz_page, qz_page, 12
        cap_gc = GenerationConfig(max_new_tokens=cap_new,
                                  do_sample=False)
        cap_slots = {}
        _log("decode: quantized KV capacity ramp (equal HBM budget)")
        for name, mdl in (("bf16", dmodel), ("int8", qmodel2)):
            eng = ContinuousBatchingEngine(
                mdl, max_batch=cap_max, page_size=qz_page,
                max_len=cap_prompt + cap_new + qz_page,
                num_pages=int(cap_budget // pb[name]),
                generation_config=cap_gc)
            cap = 0
            for n in range(1, cap_max + 1):
                pre0 = eng.preemptions
                for _ in range(n):
                    eng.submit(cap_rs.randint(0, dcfg.vocab_size,
                                              (cap_prompt,))
                               .astype(np.int32))
                eng.run()
                if eng.preemptions - pre0:
                    break
                cap = n
            cap_slots[name] = cap
        out["quant_kv_capacity_ratio"] = round(
            cap_slots["int8"] / max(cap_slots["bf16"], 1), 3)
        out["quant_kv_slots_int8"] = cap_slots["int8"]
        out["quant_kv_slots_bf16"] = cap_slots["bf16"]
        out["quant_kv_budget_pages_bf16"] = int(cap_budget // pb["bf16"])
        out["quant_kv_budget_pages_int8"] = int(cap_budget // pb["int8"])
    except Exception as e:
        out["quant_capacity_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    def _amortized_ab_us(fa, fb, x0, length=20, rounds=6):
        """A/B kernel timing robust to a SHARED chip: each leg runs
        `length` applications chained in one compiled scan (per-call
        timing measures dispatch latency, not the kernel), the two legs' repeats are INTERLEAVED so both see the
        same contention profile (the chip has been observed 2-3x slower
        for whole seconds — un-interleaved legs flip the verdict run to
        run), and each leg reports its MIN round (discards spikes)."""
        def mk(f):
            lp = jax.jit(lambda a: jax.lax.scan(
                lambda c, _: (f(c), ()), a, None, length=length)[0])
            r = lp(x0)
            _sync(jax.tree.leaves(r)[0])
            return lp
        la, lb = mk(fa), mk(fb)
        best = [float("inf"), float("inf")]
        for _ in range(rounds):
            for i, lp in enumerate((la, lb)):
                t0 = time.perf_counter()
                r = lp(x0)
                _sync(jax.tree.leaves(r)[0])
                best[i] = min(best[i], time.perf_counter() - t0)
        return (best[0] / length * 1e6, best[1] / length * 1e6)

    try:
        # weight-only int8 linear: fused Pallas kernel vs XLA dequant
        # (reference: cutlass weight-only GEMM). Kernel called DIRECTLY —
        # production dispatch consults the tune DB's measured winner, so
        # weight_only_linear alone would A/B XLA against itself. TPU-only.
        if on_tpu:
            from paddle_tpu.nn.quantized_linear import weight_quantize
            from paddle_tpu.ops.pallas import int8_matmul as im
            # n_ == k_ REQUIRED: the A/B harness feeds each [m, n] output
            # back as the next [m, k] activation (scan carry)
            m_, k_, n_ = 512, 4096, 4096
            assert n_ == k_, "A/B scan chaining needs shape-preserving f"
            rs2 = np.random.RandomState(2)
            xw = jnp.asarray(rs2.normal(0, 1, (m_, k_)), jnp.bfloat16)
            w = jnp.asarray(rs2.normal(0, 0.05, (k_, n_)), jnp.float32)
            qw, sc = weight_quantize(w, algo="weight_only_int8")
            scf = jnp.asarray(sc, jnp.float32)
            wdq = (qw.astype(jnp.float32) * scf[:, None]).astype(jnp.bfloat16)
            p_us, x_us = _amortized_ab_us(
                lambda a: im.int8_matmul_pallas(a, qw, scf),
                lambda a: jax.lax.dot_general(
                    a, wdq, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(a.dtype),
                xw)
            out["int8_matmul_pallas_us"] = round(p_us, 1)
            out["int8_matmul_xla_us"] = round(x_us, 1)
    except Exception as e:
        out["int8_matmul_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # fused rope: Pallas q+k single-pass vs XLA elementwise fusion
        # (keep-only-if-it-wins: the ledger records both numbers)
        if on_tpu:
            from paddle_tpu.ops import rope as rope_ops
            from paddle_tpu.ops.pallas.fused_rope import fused_rope_pallas
            from paddle_tpu.ops.registry import pallas_disabled_scope
            b_, s_, h_, hk_, d_ = 8, 2048, 16, 4, 128
            rs3 = np.random.RandomState(3)
            q_ = jnp.asarray(rs3.normal(0, 1, (b_, s_, h_, d_)), jnp.bfloat16)
            k_ = jnp.asarray(rs3.normal(0, 1, (b_, s_, hk_, d_)), jnp.bfloat16)
            cos_, sin_ = rope_ops.rope_freqs(d_, s_)

            def _rope_xla(qk):
                with pallas_disabled_scope():
                    return rope_ops.apply_rotary_pos_emb(
                        qk[0], qk[1], cos_, sin_)
            p_us, x_us = _amortized_ab_us(
                lambda qk: fused_rope_pallas(qk[0], qk[1], cos_, sin_),
                _rope_xla, (q_, k_))
            out["rope_pallas_us"] = round(p_us, 1)
            out["rope_xla_us"] = round(x_us, 1)
    except Exception as e:
        out["rope_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    if on_tpu:
        try:
            # paged vs dense decode CROSSOVER over context length (round-4
            # weak #2: paged was only measured at ctx 2048, where dense
            # wins — the point of paged KV is long/ragged contexts). One
            # decode step, B=8 sequences, both paths attending the same
            # ctx; dense = the models' contiguous-cache einsum path.
            from paddle_tpu.ops.pallas.paged_attention import (
                paged_decode_attention)
            B, H, H_kv, D = 8, 8, 2, 128
            page = 128
            rs = np.random.RandomState(0)
            q = jnp.asarray(rs.normal(0, 1, (B, H, D)), jnp.bfloat16)

            def dense_step(q, kc, vc, lens):
                rep = H // H_kv
                kf = jnp.repeat(kc, rep, axis=2).astype(jnp.float32)
                vf = jnp.repeat(vc, rep, axis=2).astype(jnp.float32)
                lg = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                                kf) / np.sqrt(D)
                t_idx = jnp.arange(kc.shape[1])[None, None, :]
                lg = jnp.where(t_idx <= lens[:, None, None], lg, -jnp.inf)
                p = jax.nn.softmax(lg, axis=-1)
                return jnp.einsum("bht,bthd->bhd", p, vf)

            for per_seq in (16, 64, 128):
                ctx = page * per_seq
                npages = B * per_seq + 8
                kp = jnp.asarray(rs.normal(0, 1, (H_kv, npages, page, D)),
                                 jnp.bfloat16)
                vp = kp
                tables = jnp.asarray(
                    rs.permutation(npages)[:B * per_seq]
                    .reshape(B, per_seq).astype(np.int32))
                lens = jnp.full((B,), ctx - 2, jnp.int32)
                _log(f"decode: paged vs dense kernel, ctx={ctx}")
                fp = jax.jit(paged_decode_attention)
                r = fp(q, kp, vp, tables, lens)
                _sync(r)
                kc = jnp.asarray(rs.normal(0, 1, (B, ctx, H_kv, D)),
                                 jnp.bfloat16)
                vc = kc
                fd = jax.jit(dense_step)
                r2 = fd(q, kc, vc, lens)
                _sync(r2)
                n = 20
                best_p = best_d = float("inf")
                for _ in range(3):       # interleaved min-of-rounds
                    t0 = time.perf_counter()
                    for _ in range(n):
                        r = fp(q, kp, vp, tables, lens)
                    _sync(r)
                    best_p = min(best_p, (time.perf_counter() - t0) / n)
                    t0 = time.perf_counter()
                    for _ in range(n):
                        r2 = fd(q, kc, vc, lens)
                    _sync(r2)
                    best_d = min(best_d, (time.perf_counter() - t0) / n)
                out[f"paged_decode_us_ctx{ctx}"] = round(best_p * 1e6, 1)
                out[f"dense_decode_us_ctx{ctx}"] = round(best_d * 1e6, 1)
                del kp, vp, kc, vc
        except Exception as e:
            out["paged_decode_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # long-context leg: s=8192 training on the flash kernel — the
        # dense XLA attention path fails to COMPILE at this length on
        # v5e (tune-sweep evidence), so the leg is flash-kernel-only and
        # SKIPPED when the degradation ladder disabled Pallas. Runs LAST,
        # after the serving model is dropped, to free HBM first.
        # Round-5 A/B (temp/exp_longctx*.py): b=2 + NO recompute fits v5e
        # HBM and reads MFU 0.626 vs full-remat-b1's 0.49 — full remat was
        # costing the extra forward; the ladder below keeps b1/full as the
        # OOM fallback.
        if on_tpu and not os.environ.get("PT_DISABLE_PALLAS"):
            try:
                del dmodel
            except NameError:
                pass
            from paddle_tpu.models import LlamaConfig as _LC
            from paddle_tpu.trainer import device_peak_flops as _pk
            last_exc = None
            for lb, lrec in ((2, "none"), (1, "full")):
                lcfg = _LC(vocab_size=32000, hidden_size=1024,
                           intermediate_size=3072, num_hidden_layers=8,
                           num_attention_heads=8, num_key_value_heads=4,
                           max_position_embeddings=8192, dtype="bfloat16",
                           recompute=lrec)
                _log(f"long-context: compiling s=8192 b={lb} recompute={lrec}")
                try:
                    (ltps, lstep, _stall, _loss, lmodel,
                     _ps, _ss, _ca) = _train_bench(lcfg, lb, 8192, 5, 2)
                    break
                except Exception as e:
                    # clear frame locals: the traceback pins the failed
                    # tier's model/opt device arrays, which would keep HBM
                    # allocated while the fallback tier compiles
                    traceback.clear_frames(e.__traceback__)
                    last_exc = e
            else:
                raise RuntimeError("all longctx tiers failed") from last_exc
            ltps_chip = ltps / jax.device_count()
            out["longctx_seq_len"] = 8192
            out["longctx_batch"] = lb
            out["longctx_recompute"] = lrec
            out["longctx_tokens_per_sec_per_chip"] = round(ltps_chip, 1)
            out["longctx_mfu"] = round(
                ltps_chip * lmodel.flops_per_token(8192) / _pk(), 4)
            out["longctx_mfu_causal"] = round(
                ltps_chip * lmodel.flops_per_token(8192, causal=True)
                / _pk(), 4)
            out["longctx_params"] = lmodel.num_params()
            _log("long-context: timed")

            # sequence-packing sub-leg: two 4096-token documents packed per
            # row via the flash kernel's segment-id path (reference varlen:
            # flash_attn_kernel.cu:91) — same s=8192 compute budget, zero
            # padding waste; per-segment positions restart and boundary
            # labels are masked, so this is exact packed-pretraining
            # semantics, not an approximation.
            try:
                import numpy as _n
                from paddle_tpu.optimizer import AdamW as _AW
                from paddle_tpu.trainer import Trainer as _Tr
                ptr = _Tr(lmodel, _AW(learning_rate=1e-4,
                                      parameters=lmodel))
                rs = _n.random.RandomState(7)
                ids = rs.randint(0, lcfg.vocab_size, (lb, 8192 + 1),
                                 _n.int32)
                lbl = ids[:, 1:].copy()
                lbl[:, 4095] = -100          # no cross-document target
                pos = _n.concatenate([_n.arange(4096), _n.arange(4096)])
                pbatch = {
                    "input_ids": jnp.asarray(ids[:, :-1]),
                    "labels": jnp.asarray(lbl),
                    "position_ids": jnp.broadcast_to(
                        jnp.asarray(pos, jnp.int32)[None], (lb, 8192)),
                    "segment_ids": jnp.broadcast_to(
                        jnp.asarray(_n.repeat(_n.arange(2), 4096),
                                    jnp.int32)[None], (lb, 8192)),
                }
                _log("long-context: compiling packed (segment-id) step")
                # 3 warmup calls: the FIRST post-compile step re-specializes
                # on the donated buffers' layouts (observed live: one ~15 s
                # stall exactly once, then steady 216 ms) — time min-of-
                # rounds after it
                for _ in range(3):
                    l2 = ptr.train_step(pbatch)
                _sync(l2)
                pdt = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(3):
                        l2 = ptr.train_step(pbatch)
                    _sync(l2)
                    pdt = min(pdt, (time.perf_counter() - t0) / 3)
                out["longctx_packed_tokens_per_sec_per_chip"] = round(
                    lb * 8192 / pdt / jax.device_count(), 1)
                out["longctx_packed_segments"] = 2
            except Exception as e:
                out["longctx_packed_error"] = (f"{type(e).__name__}: "
                                               f"{str(e)[:150]}")
    except Exception as e:
        out["longctx_error"] = f"{type(e).__name__}: {str(e)[:150]}"

    try:
        # MoE leg (round-4 verdict missing #5): dropless grouped-matmul vs
        # capacity-dense at DeepSeekMoE expert scale (e=64, d=2048, f=1408,
        # top-6), fwd+bwd, interleaved min-of-rounds. Dropless runs
        # lax.ragged_dot (tune_db moe_grouped_mm: 1.7x over megablox gmm);
        # capacity=1.25 computes 1.25/6 the routed rows via one batched
        # einsum but DROPS overflow tokens — both are reported, the
        # semantics choice stays with the user (parallel/moe.py).
        if on_tpu:
            import numpy as _n

            import paddle_tpu as _pt
            from paddle_tpu.parallel.moe import MoELayer as _ML
            _B, _S, _D, _F, _E, _K = 1, 4096, 2048, 1408, 64, 6
            rsm = _n.random.RandomState(0)
            xm = jnp.asarray(rsm.normal(0, 1, (_B, _S, _D)), jnp.bfloat16)
            moe_legs = {}
            for nm, cf in (("moe_dropless_us", None),
                           ("moe_dense_cap125_us", 1.25)):
                _pt.seed(0)
                lyr = _ML(_D, _F, _E, top_k=_K, capacity_factor=cf,
                          dtype="bfloat16")
                prm = lyr.raw_parameters()

                def _mloss(p, x, lyr=lyr):
                    o, aux = lyr.functional_call(p, x)
                    return o.astype(jnp.float32).mean() + 0.01 * aux
                _log(f"moe: compiling {nm}")
                gfn = jax.jit(jax.grad(_mloss, argnums=(0, 1)))
                r = gfn(prm, xm)
                _sync(jax.tree.leaves(r)[0])
                moe_legs[nm] = (gfn, prm)
            best = {nm: float("inf") for nm in moe_legs}
            for _ in range(4):
                for nm, (gfn, prm) in moe_legs.items():
                    t0 = time.perf_counter()
                    for _ in range(3):
                        r = gfn(prm, xm)
                    _sync(jax.tree.leaves(r)[0])
                    best[nm] = min(best[nm],
                                   (time.perf_counter() - t0) / 3)
            for nm, v in best.items():
                out[nm] = round(v * 1e6, 1)
            out["moe_experts"] = _E
            out["moe_top_k"] = _K
            _log("moe: timed")
    except Exception as e:
        out["moe_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    return out


def _loss_head_probe(cfg, on_tpu, step_time_s):
    """Loss-head step-decomposition (ISSUE 5): fused vocab-CE vs the naive
    materialized-logits head, compiled grad(loss) over the same arrays,
    interleaved min-of-rounds — reported as RATIOS (noisy shared host).
    ``loss_head_share`` = fused head time / full train-step time, the
    decomposition the 0.63→0.81 e2e-MFU-gap work tracks;
    ``loss_head_logits_mb_avoided`` = the fp32 [B*S, V] activation the
    fused path never allocates."""
    out = {}
    try:
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        from loss_head_bench import run_loss_head_bench
        if on_tpu:
            # the headline training shape: the decomposition then speaks
            # to the measured e2e step directly
            kw = dict(n=8 * 2048, h=cfg.hidden_size, v=cfg.vocab_size,
                      dtype="bfloat16", rounds=4, iters=2)
        else:
            # CPU tier: a loss-head-bound shape (V >> H — the regime the
            # fused head targets; tiny-vocab configs are trunk-bound and
            # time nothing but matmul noise). step share is only
            # meaningful when the probe shape IS the headline shape, so
            # it's TPU-only
            kw = dict(n=2048, h=128, v=16000, dtype="bfloat16",
                      rounds=6, iters=1)
            step_time_s = None
        _log("loss-head: fused vs naive A/B")
        out.update(run_loss_head_bench(step_time_s=step_time_s, **kw))
    except Exception as e:
        out["loss_head_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    return out


def _obs_probe(on_tpu):
    """Metrics-plane probe (ISSUE 4): A/B a short Trainer.fit with the
    observability registry off vs on, SAME process and trainer, rounds
    interleaved min-of-rounds — the overhead is reported as a RATIO
    (absolute tok/s is too noisy on a shared host). Then snapshots the
    enabled-leg telemetry (goodput buckets, compile counters, serving
    percentiles via a micro serving leg) into the detail section."""
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.observability as obs
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.trainer import Trainer
    out = {}
    try:
        cfg = LlamaConfig.tiny()
        pt.seed(0)
        model = LlamaForCausalLM(cfg)
        tr = Trainer(model, AdamW(learning_rate=1e-4, parameters=model))
        rs = np.random.RandomState(0)

        def batches(n):
            bs = []
            for _ in range(n):
                ids = rs.randint(0, cfg.vocab_size, (4, 129), np.int32)
                bs.append({"input_ids": jnp.asarray(ids[:, :-1]),
                           "labels": jnp.asarray(ids[:, 1:])})
            return bs

        n, rounds = 50, 4
        _log("obs: compiling probe trainer")
        tr.fit(iter(batches(4)), steps=4, log_every=10 ** 9)
        legs = {"off": float("inf"), "on": float("inf")}
        data = {k: [batches(n) for _ in range(rounds)] for k in legs}
        _log("obs: timing metrics off vs on (interleaved)")
        for r in range(rounds):
            obs.REGISTRY.disable()
            t0 = time.perf_counter()
            tr.fit(iter(data["off"][r]), steps=n, log_every=10)
            legs["off"] = min(legs["off"], time.perf_counter() - t0)
            obs.ledger().reset()
            obs.REGISTRY.enable()
            t0 = time.perf_counter()
            tr.fit(iter(data["on"][r]), steps=n, log_every=10)
            legs["on"] = min(legs["on"], time.perf_counter() - t0)
        out["obs_step_time_off_s"] = round(legs["off"] / n, 6)
        out["obs_step_time_on_s"] = round(legs["on"] / n, 6)
        out["obs_overhead_ratio"] = round(legs["on"] / legs["off"], 4)
        # deterministic half of the ≤2% claim (the A/B ratio above rides
        # a noisy shared host): the disabled-path cost of one instrument
        # call — the price every hot path pays in a run that never opts in
        obs.REGISTRY.disable()
        c = obs.REGISTRY.counter("pt_bench_disabled_probe")
        t0 = time.perf_counter()
        for _ in range(100_000):
            c.inc()
        out["obs_disabled_ns_per_inc"] = round(
            (time.perf_counter() - t0) / 100_000 * 1e9, 1)

        # micro serving leg with the plane on -> percentile gauges.
        # The default SLO packs (ISSUE 10) ride this leg: installed
        # AFTER the timing A/B so the sentry's snapshot-per-tick cost
        # can't tilt obs_overhead_ratio, ticked by the engine's own
        # drain-boundary wiring — the slo_incidents row records which
        # default rules this round trips. On the CPU tier the
        # cost-model drift band legitimately fires (the roofline does
        # not model tiny-model CPU dispatch overhead — documented in
        # DESIGN_DECISIONS ISSUE 9); an honest row beats a quiet one.
        obs.REGISTRY.enable()
        from paddle_tpu.observability import sentry as sn
        # min_interval_s keeps the engine's per-drain maybe_tick from
        # paying a full collect() inside the very leg whose ITL/TTFT
        # percentiles the serving rules then judge (the README's own
        # recommended hot-path setting)
        sentry = sn.install(sn.SloSentry(sn.default_rules(),
                                         min_interval_s=1.0))
        from paddle_tpu.inference import ContinuousBatchingEngine
        from paddle_tpu.inference.generation import GenerationConfig
        eng = ContinuousBatchingEngine(
            model, max_batch=2, page_size=8, max_len=32,
            generation_config=GenerationConfig(max_new_tokens=8,
                                               do_sample=False),
            decode_block=4)
        for L in (6, 8, 5):
            eng.submit(rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32))
        eng.run()
        lat = eng.publish_metrics()
        # final evaluation over the freshly published percentile gauges
        # — drop the hot-path rate limit so it can't be skipped
        sentry.min_interval_s = 0.0
        sentry.tick()
        out["slo_incidents"] = {
            "count": len(sentry.incidents),
            "ticks": sentry.ticks,
            "rules_fired": sorted({i.rule for i in sentry.incidents})}
        sn.uninstall()
        snap = obs.collect()
        t = obs.ledger().totals()
        from paddle_tpu.core import compile_cache as _cc
        out["obs_metrics"] = {
            "series": len(snap),
            "goodput": {k: t[k] for k in
                        list(obs.goodput.BUCKETS) + ["total_s",
                                                     "goodput_fraction"]},
            "compile_cache": {k: v for k, v in _cc.stats().items()
                              if k != "persistent_dir"},
            "serving": {k: round(v, 5) for k, v in lat.items()
                        if k.endswith("_s")},
        }
    except Exception as e:
        out["obs_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    finally:
        try:
            from paddle_tpu.observability import sentry as _sn
            _sn.uninstall()
        except Exception:
            pass
        try:
            obs.REGISTRY.disable()
        except Exception:
            pass
    return out


def _graph_contracts_probe(on_tpu):
    """Graph-contract rows (ISSUE 8): run the static analyzers over the
    canonical compiled entrypoints and report count/byte metrics — per the
    bench-variance policy these are structural (deterministic per build),
    not wall-time. ``train_step_collective_count`` counts collectives in
    the canonical train-step graph (0 single-chip; a sharded trainer on a
    pod shows its real comm load), ``serving_tick_donated_bytes`` is the
    aliased (donated) input bytes of the serving decode tick — the number
    that drops when a refactor silently loses a donation.

    ISSUE 14 adds ``overlap_exposed_comm_fraction``: the exposed
    (un-overlapped) comm fraction of the dp2xtp2 canonical step
    (``tp_fused_ce``) from the same start→done pairing the budget gate
    enforces. The graph needs a 2x2 mesh, so a single-device host
    delegates to a ``tools/graph_lint.py --json`` subprocess on 8
    virtual CPU devices (it self-forces the count) and reads the
    snapshot; ``overlap_backend`` records which path the number rode."""
    out = {}
    try:
        import paddle_tpu.analysis as A
        _log("graph contracts: analyzing canonical train/serving graphs")
        g = A.build_graph("train_step_k1")
        rep = A.analyze(g.compiled, g.name, g.contract)
        out["train_step_collective_count"] = \
            rep.collectives["total_collectives"]
        out["train_step_largest_intermediate_mb"] = round(
            rep.materialization["largest_intermediate_bytes"] / 2 ** 20, 3)
        g = A.build_graph("serving_tick")
        rep = A.analyze(g.compiled, g.name, g.contract)
        out["serving_tick_donated_bytes"] = rep.donation["donated_bytes"]
        out["serving_tick_host_transfers"] = \
            rep.transfers["host_transfer_count"]
    except Exception as e:
        out["graph_contracts_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    try:
        import jax

        import paddle_tpu.analysis as A
        if jax.device_count() >= 4:
            _log("graph contracts: overlap report on the dp2xtp2 step")
            g = A.build_graph("tp_fused_ce")
            rep = A.analyze(g.compiled, g.name, g.contract, mesh=g.mesh)
            snap = A.snapshot_report(rep)
            out["overlap_backend"] = "inline"
        else:
            import subprocess

            _log("graph contracts: overlap report via graph_lint "
                 "subprocess (8 virtual devices)")
            env = _cpu_child_env()
            cmd = [sys.executable,
                   os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools", "graph_lint.py"),
                   "--graphs", "tp_fused_ce", "--json"]
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=600, env=env)
            # --json prints one indented JSON object (verbose output is
            # suppressed); tolerate stray preamble lines before it
            d = json.loads(res.stdout[res.stdout.index("{"):])
            snap = d["snapshots"]["tp_fused_ce"]
            out["overlap_backend"] = "cpu-subprocess"
        out["overlap_exposed_comm_fraction"] = \
            snap["exposed_comm_fraction"]
        out["overlap_min_distance"] = snap["min_overlap_distance"]
    except Exception as e:
        out["overlap_row_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    return out


def _planner_probe(on_tpu):
    """Sharding-planner rows (ISSUE 11): predicted-vs-measured rank
    order over the legal configs of a small mesh, on the micro model.

    Ratio rows per the bench-variance policy:
    ``planner_rank_agreement`` (pairwise concordance of the predicted
    and measured step-time orderings), ``planner_top1_is_measured_top2``
    (1.0 when the planner's pick lands in the measured top 2 — the
    acceptance bar), ``planner_predicted_mfu`` (the chosen config's
    predicted MFU), plus the chosen config string as a detail row.

    With ≥4 local devices the validation runs inline on the real mesh;
    a single-device host delegates to ``tools/plan.py --validate`` in a
    subprocess on 8 virtual CPU devices (the dryrun tier) —
    ``planner_backend`` records which, so cross-round readers know what
    the numbers rode on."""
    out = {}
    try:
        import jax
        if jax.device_count() >= 4:
            from paddle_tpu.distributed import auto_parallel as ap
            from paddle_tpu.models import LlamaConfig
            _log("planner: pricing configs on the local mesh")
            mcfg = LlamaConfig(
                vocab_size=320, hidden_size=64, intermediate_size=96,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=128)
            n = 8 if jax.device_count() >= 8 else 4
            rep = ap.plan(mcfg, n_devices=n, global_batch=8, seq_len=64,
                          keep_builds=True, model_name="llama-micro")
            v = ap.validate_rank_order(rep)
            chosen_cfg = str(rep.chosen.config)
            chosen_mfu = rep.chosen.predicted_mfu
            out["planner_backend"] = "inline"
        else:
            import subprocess
            _log("planner: validating on an 8-virtual-device subprocess")
            env = _cpu_child_env()
            cmd = [sys.executable,
                   os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools", "plan.py"),
                   "--mesh", "4x2", "--model", "llama-micro",
                   "--batch", "8", "--seq", "64",
                   "--validate", "--json", "--virtual-devices", "8"]
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=900, env=env)
            if res.returncode != 0:
                raise RuntimeError(f"plan.py rc={res.returncode}: "
                                   f"{res.stderr[-300:]}")
            d = json.loads(res.stdout.strip().splitlines()[-1])
            v = d["validation"]
            chosen_cfg = d["chosen"]
            chosen_mfu = d["ranked"][0]["predicted_mfu"]
            out["planner_backend"] = "cpu-subprocess"
        out["planner_rank_agreement"] = round(v["agreement"], 4)
        out["planner_top1_is_measured_top2"] = \
            float(v["top1_is_measured_top2"])
        out["planner_predicted_mfu"] = round(chosen_mfu, 4)
        out["planner_chosen_config"] = chosen_cfg
        out["planner_n_configs"] = v["n_configs"]
    except Exception as e:
        out["planner_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    return out


def _fsdp_probe(on_tpu):
    """ZeRO/FSDP rows (ISSUE 18): what the fsdp axis costs and buys at
    EQUAL device count, on the micro model.

    ``fsdp_step_overhead_ratio`` — measured fsdp4 ÷ dp4 step time over
    4 devices (the gather/reduce-scatter tax; interleaved min-of-rounds
    via the planner's own rank-order measurement). ``fsdp_hbm_ratio`` —
    closed-form ``estimate_hbm`` total for the same pair (params+slots+
    grads ÷4 plus the one-layer gather working set vs pure dp): the
    memory the axis exists to save, deterministic arithmetic so a tight
    band. With ≥4 local devices the A/B runs inline; otherwise two
    ``tools/plan.py --config`` subprocesses on 4 virtual CPU devices —
    ``fsdp_backend`` records which."""
    out = {}
    from paddle_tpu.distributed import auto_parallel as ap
    from paddle_tpu.models import LlamaConfig
    mcfg = LlamaConfig(
        vocab_size=320, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128)
    cfg_off = ap.ParallelConfig(dp=4)
    cfg_on = ap.ParallelConfig(fsdp=4)
    try:
        m_off = ap.estimate_hbm(mcfg, cfg_off, global_batch=8, seq_len=64)
        m_on = ap.estimate_hbm(mcfg, cfg_on, global_batch=8, seq_len=64)
        out["fsdp_hbm_ratio"] = round(m_on.total_bytes
                                      / m_off.total_bytes, 4)
    except Exception as e:
        out["fsdp_hbm_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    try:
        import jax
        meas = {}
        if jax.device_count() >= 4:
            _log("fsdp: A/B pricing dp4 vs fsdp4 on the local mesh")
            rep = ap.plan(mcfg, n_devices=4, global_batch=8, seq_len=64,
                          configs=[cfg_off, cfg_on], keep_builds=True,
                          drift="ignore", model_name="llama-micro")
            ap.validate_rank_order(rep)
            for pc in rep.ranked:
                meas[str(pc.config)] = pc.measured_step_s
            out["fsdp_backend"] = "inline"
        else:
            import subprocess
            _log("fsdp: A/B via plan.py on 4 virtual devices")
            for cfg in (cfg_off, cfg_on):
                env = _cpu_child_env()
                cmd = [sys.executable,
                       os.path.join(os.path.dirname(
                           os.path.abspath(__file__)), "tools", "plan.py"),
                       "--devices", "4", "--model", "llama-micro",
                       "--batch", "8", "--seq", "64",
                       "--config", str(cfg),
                       "--validate", "--json", "--virtual-devices", "4"]
                res = subprocess.run(cmd, capture_output=True, text=True,
                                     timeout=900, env=env)
                if res.returncode != 0:
                    raise RuntimeError(f"plan.py rc={res.returncode}: "
                                       f"{res.stderr[-300:]}")
                d = json.loads(res.stdout.strip().splitlines()[-1])
                meas[d["chosen"]] = d["ranked"][0]["measured_step_s"]
            out["fsdp_backend"] = "cpu-subprocess"
        t_off = meas[str(cfg_off)]
        t_on = meas[str(cfg_on)]
        out["fsdp_step_overhead_ratio"] = round(t_on / t_off, 4)
        out["fsdp_step_dp4_s"] = t_off
        out["fsdp_step_fsdp4_s"] = t_on
    except Exception as e:
        out["fsdp_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    return out


def _moe_ep_probe(on_tpu):
    """Expert-parallelism rows (ISSUE 20), micro MoE model.

    ``moe_ep_step_speedup`` — replicated-experts dp2 ÷ dp2_ep2 measured
    step time at EQUAL devices and experts (interleaved min-of-rounds
    via the planner's rank-order measurement; the ep leg pays the
    all-to-all, buys per-rank expert HBM). ``moe_ep_a2a_pred_over_
    measured`` — the priced census's per-a2a seconds ÷ a wall-clock
    shard_map all-to-all of the same dispatch buffer on the same mesh
    (cost-model drift for the NEW collective, healthy ~1.0 on TPU,
    nominal on CPU)."""
    out = {}
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import auto_parallel as ap
    from paddle_tpu.models.moe_lm import MoEConfig
    mcfg = MoEConfig(
        vocab_size=320, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, num_experts=4,
        num_experts_per_tok=2, num_shared_experts=1,
        first_k_dense_replace=1, capacity_factor=None,
        max_position_embeddings=128)
    cfg_rep = ap.ParallelConfig(dp=2)
    cfg_ep = ap.ParallelConfig(dp=2, ep=2)
    try:
        if jax.device_count() < 2:
            raise RuntimeError("needs >= 2 devices for the ep=2 mesh")
        _log("moe-ep: A/B pricing dp2 vs dp2_ep2 on the micro MoE")
        rep = ap.plan(mcfg, n_devices=2, global_batch=8, seq_len=64,
                      configs=[cfg_rep, cfg_ep], keep_builds=True,
                      drift="ignore", model_name="moe-micro")
        ap.validate_rank_order(rep)
        meas = {str(pc.config): pc.measured_step_s for pc in rep.ranked}
        out["moe_ep_step_speedup"] = round(
            meas[str(cfg_rep)] / meas[str(cfg_ep)], 4)
        out["moe_ep_step_dp2_s"] = meas[str(cfg_rep)]
        out["moe_ep_step_ep2_s"] = meas[str(cfg_ep)]

        pc_ep = next(pc for pc in rep.ranked if pc.config.ep > 1)
        rows = [r for r in pc_ep.graph.priced_census["per_op"]
                if r["opcode"] == "all-to-all"]
        if rows and pc_ep.build is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            mesh_ = getattr(pc_ep.build.mesh, "mesh", pc_ep.build.mesh)
            # the dropless dispatch buffer of THIS config: [e, t_local, d]
            t_local = 8 * 64 // 2
            buf = jnp.ones((mcfg.num_experts, t_local, mcfg.hidden_size),
                           jnp.float32)
            fn = jax.jit(shard_map(
                lambda x: jax.lax.all_to_all(
                    x, "ep", split_axis=0, concat_axis=1, tiled=True),
                mesh=mesh_, axis_names=frozenset({"ep"}),
                in_specs=P("ep", None, None),
                out_specs=P("ep", None, None), check_vma=False))
            fn(buf).block_until_ready()
            t_meas = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                fn(buf).block_until_ready()
                t_meas = min(t_meas, time.perf_counter() - t0)
            pred_one = sum(r["seconds"] for r in rows) / len(rows)
            if t_meas > 0:
                out["moe_ep_a2a_pred_over_measured"] = round(
                    pred_one / t_meas, 4)
        out["moe_ep_backend"] = "inline"
    except Exception as e:
        out["moe_ep_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    return out


def _elastic_probe(on_tpu):
    """Elastic scale-in rows (ISSUE 15): a timed mini kill→reshard cycle
    on the micro model. ``elastic_reshard_seconds`` = wall time to
    verify + reshard + place a checkpoint saved under the big mesh onto
    half the devices; ``elastic_resume_steps_replayed`` = killed_step −
    restored_step under the probe's save-every-4/kill-at-6 schedule
    (2 by construction — any other value means the cadence or the
    commit/fallback logic regressed). With ≥2 local devices the cycle
    runs inline; a single-device host delegates to
    ``paddle_tpu.testing._elastic_train --probe-reshard`` on 4 virtual
    CPU devices — ``elastic_probe_backend`` records which."""
    out = {}
    try:
        import jax
        if jax.device_count() >= 2:
            _log("elastic: timing reshard cycle on the local mesh")
            from paddle_tpu.testing._elastic_train import reshard_probe
            out.update(reshard_probe())
            out["elastic_probe_backend"] = "inline"
        else:
            import subprocess
            _log("elastic: reshard cycle on a 4-virtual-device subprocess")
            env = _cpu_child_env()
            env.pop("XLA_FLAGS", None)
            cmd = [sys.executable, "-m",
                   "paddle_tpu.testing._elastic_train",
                   "--ckpt-dir", "unused", "--probe-reshard",
                   "--virtual-devices", "4"]
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=900, env=env)
            if res.returncode != 0:
                raise RuntimeError(f"_elastic_train rc={res.returncode}: "
                                   f"{res.stderr[-300:]}")
            for line in res.stdout.splitlines():
                if line.startswith("ELASTIC_PROBE "):
                    out.update(json.loads(line[len("ELASTIC_PROBE "):]))
            out["elastic_probe_backend"] = "cpu-subprocess"
    except Exception as e:
        out["elastic_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    return out


_ARTIFACT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_artifacts")


def _write_tpu_artifact(payload, early: bool = False):
    """Persist every successful real-TPU measurement as an auditable JSON
    (round-3 verdict: TPU claims without committed artifacts are
    unauditable). Includes git HEAD so the artifact pins the exact code.

    ``early=True`` writes the headline-only capture the moment the
    training number exists: the detail probes that follow take many
    minutes, and a driver timeout in them must not lose the headline. The
    final full artifact is written afterwards with a later captured_at."""
    import datetime
    import subprocess
    try:
        os.makedirs(_ARTIFACT_DIR, exist_ok=True)
        try:
            head = subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
                cwd=os.path.dirname(_ARTIFACT_DIR),
                timeout=10).stdout.strip() or "unknown"
        except Exception:
            head = "unknown"
        art = dict(payload)
        art["git_head"] = head
        if early:
            art["early_capture"] = True
        now = datetime.datetime.now(datetime.timezone.utc)
        art["captured_at"] = now.isoformat()
        d = payload.get("detail", {})
        # timestamp + attention path in the name: a later degraded run must
        # never clobber an earlier good artifact of the same config
        name = (f"tpu_{d.get('device', 'unknown').replace(' ', '_')}"
                f"_{d.get('params', 0) // 1_000_000}M"
                f"_s{d.get('seq_len', 0)}"
                f"_{d.get('attention_path', 'x').split(' ')[0]}"
                f"{'_early' if early else ''}"
                f"_{now.strftime('%Y%m%dT%H%M%S')}.json")
        path = os.path.join(_ARTIFACT_DIR, name)
        with open(path, "w") as f:
            json.dump(art, f, indent=1)
        _log(f"{'EARLY ' if early else ''}TPU artifact written: {path} "
             f"(commit it!)")
    except Exception as e:
        _log(f"artifact write failed: {e}")


def _run():
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.ops.registry import require_tpu
    from paddle_tpu.trainer import device_peak_flops

    require_tpu()
    backend = jax.default_backend()
    on_tpu = True       # the detail probes still take the flag (D3)
    # ~0.5B params — fits one v5e chip (16GB) in bf16 with adam fp32 state
    cfg = LlamaConfig(**_HEADLINE_TPU_CFG)
    batch_size, seq_len, steps, warmup = 8, 2048, 10, 3

    # one configuration, as configured: a failure here is the result
    (tps, step_s, stall_s, loss, model, per_step,
     superstep, cost_attr) = _train_bench(
         cfg, batch_size, seq_len, steps, warmup, superstep_probe=True)
    attn_path = ("xla (PT_DISABLE_PALLAS)"
                 if os.environ.get("PT_DISABLE_PALLAS") else "pallas")

    n_chips = jax.device_count()
    tps_chip = tps / n_chips
    mfu = tps_chip * model.flops_per_token(seq_len) / device_peak_flops()
    # dual-convention MFU (round-4 verdict weak #5): the headline `mfu` is
    # amortized-async + PaLM non-causal FLOPs (cross-paper comparable);
    # `mfu_fenced_causal` is the strictest honest-utilization reading —
    # per-step host-fenced wall time + only the FLOPs the causal kernel
    # executes. Both are quoted wherever the headline appears (README).
    mfu_causal = (tps_chip * model.flops_per_token(seq_len, causal=True)
                  / device_peak_flops())
    mfu_fenced_causal = None
    if per_step:
        fenced = sorted(per_step)[len(per_step) // 2]
        tps_fenced = batch_size * seq_len / fenced / n_chips
        mfu_fenced_causal = round(
            tps_fenced * model.flops_per_token(seq_len, causal=True)
            / device_peak_flops(), 4)

    detail = {
        "backend": backend,
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
        "attention_path": attn_path,
        # what is ACTUALLY installed — PT_NO_OVERLAP only stops bench
        # from adding flags, it cannot strip preexisting ones
        "overlap_flags": ("on" if "async_collective" in
                          os.environ.get("LIBTPU_INIT_ARGS", "")
                          else ("off" if os.environ.get("PT_NO_OVERLAP")
                                else "default")),
        "n_chips": n_chips,
        "params": model.num_params(),
        "batch_size": batch_size,
        "seq_len": seq_len,
        "steps": steps,
        "step_time_s": round(step_s, 4),
        "fenced_step_times_s": per_step,
        "input_stall_s_per_step": round(stall_s, 4),
        "mfu": round(mfu, 4),
        "mfu_causal": round(mfu_causal, 4),
        "mfu_fenced_causal": mfu_fenced_causal,
        "final_loss": loss,
    }
    detail.update(superstep)
    # cost-observatory rows (ISSUE 9) — ratio metrics per the bench-
    # variance policy: `mfu_analytical` is HLO-attributed flops of the
    # HEADLINE step executable / (measured step time x device peak) —
    # same analyzer as the live pt_model_flops_utilization gauge and
    # graph_lint's flop floor (vs `mfu`, the PaLM closed form);
    # `step_time_predicted_over_measured` is roofline-predicted /
    # measured (cost-model drift); `comm_time_predicted_s` prices the
    # step's collective census bytes against the axis link bandwidth
    # (0.0 single-chip — a sharded pod shows its real comm price here)
    if cost_attr:
        try:
            from paddle_tpu.observability.costs import device_spec
            spec = device_spec()
            detail["mfu_analytical"] = round(
                cost_attr["flops"] / (step_s * spec.peak_flops), 4)
            detail["step_time_predicted_over_measured"] = round(
                cost_attr["predicted_s"] / step_s, 4)
            detail["comm_time_predicted_s"] = round(
                cost_attr["comm_bytes"] / spec.link_bw, 6)
            detail["cost_unmodeled_ops"] = cost_attr["unmodeled_ops"]
        except Exception as e:
            detail["cost_rows_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    # which loss head actually trained: fused (blockwise vocab-CE, no
    # [b, s, V] logits) is the default; PT_NAIVE_LOSS_HEAD or
    # cfg.loss_impl flip it back
    from paddle_tpu.models.llama import fused_loss_enabled
    detail["loss_head_path"] = ("fused" if fused_loss_enabled(cfg)
                                else "naive")
    # compile/AOT cache counters (core/compile_cache.py): hit/miss across
    # this whole process — miss-only means cold; persistent_dir is where
    # jax's persistent cache lives for this run
    from paddle_tpu.core import compile_cache
    detail["compile_cache"] = compile_cache.stats()

    # ONE payload dict: the early artifact and the final record must never
    # disagree on the headline numbers (detail is shared by reference; the
    # early write snapshots it pre-probes)
    payload = {
        "metric": "llama_pretrain_tokens_per_sec_per_chip",
        "value": round(tps_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "detail": detail,
    }
    # EARLY artifact: the headline number is on disk before the long
    # detail probes run
    _write_tpu_artifact({**payload, "detail": dict(detail)}, early=True)

    detail.update(_decode_bench(cfg, on_tpu))
    detail.update(_loss_head_probe(cfg, on_tpu, step_s))
    detail.update(_obs_probe(on_tpu))
    detail.update(_graph_contracts_probe(on_tpu))
    detail.update(_planner_probe(on_tpu))
    detail.update(_fsdp_probe(on_tpu))
    detail.update(_moe_ep_probe(on_tpu))
    detail.update(_elastic_probe(on_tpu))
    # noise-aware regression verdict vs the checked-in pinned baseline
    # (ISSUE 10): ratio metrics only, per the bench-variance policy —
    # the round records whether it moved past the band, mechanically
    try:
        from paddle_tpu.observability.sentry import baselines as _bl
        bpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools", "bench_baseline.json")
        if os.path.exists(bpath):
            detail["bench_diff"] = _bl.diff_records(
                _bl.load_record(bpath), payload).summary()
    except Exception as e:
        detail["bench_diff_error"] = f"{type(e).__name__}: {str(e)[:150]}"
    _write_tpu_artifact(payload)
    _emit(payload)


def main():
    """One process, one chip, one configuration. No TPU → RuntimeError →
    non-zero exit with no metric line; so does any failure in the
    headline run."""
    from paddle_tpu.core.compile_cache import configure_compilation_cache
    from paddle_tpu.distributed.overlap import enable_overlap
    # async-collective + latency-hiding flags (overlap.py), before jax
    # initializes; A/B lever: PT_NO_OVERLAP=1
    enable_overlap(True)
    configure_compilation_cache()
    _run()


if __name__ == "__main__":
    main()
