"""LLM generation loop over the KV-cache decode path (reference analogue:
PaddleNLP's generation utils driving the fused/block attention kernels;
in-repo kernels masked_multihead_attention / block_multi_head_attention).

TPU-native: prefill compiles once for the padded prompt length, the decode
step compiles once (static cache shape, dynamic position index), and the
token loop runs on host while all math stays on device. Sampling strategies:
greedy, temperature, top-k, top-p — each a pure function over logits.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    seed: int = 0


def _sample_logits(logits, cfg: GenerationConfig, key):
    """[b, vocab] → [b] next tokens."""
    if not cfg.do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(cfg.temperature, 1e-6)
    if cfg.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -cfg.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if cfg.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p; always keep the best
        cutoff_idx = jnp.sum(cum < cfg.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def _mask_logits_rowwise(logits, temperature, top_k, top_p):
    """Shared temperature/top-k/top-p masking for the per-row samplers:
    [b, vocab] logits + per-row knob arrays → masked [b, vocab] logits
    ready for ``jax.random.categorical``."""
    b, vocab = logits.shape
    x = logits / jnp.maximum(temperature, 1e-6)[:, None]

    # top-k: keep each row's k best (k=0 -> vocab = keep all)
    sorted_x = jnp.sort(x, axis=-1)[:, ::-1]             # descending
    k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, vocab), vocab)
    kth = jnp.take_along_axis(sorted_x, (k_eff - 1)[:, None], axis=-1)
    x = jnp.where(x < kth, -jnp.inf, x)
    # top-p over the top-k-FILTERED distribution (filters compose
    # sequentially, matching _sample_logits): smallest prefix with mass
    # >= p, always keeping the best token. No second O(V log V) sort:
    # the kept set is {x >= kth} and sorted_x is already descending, so
    # the masked sort is the PREFIX of sorted_x with value >= kth — a
    # value compare, NOT a position compare (ties at the kth value all
    # survive the mask, exactly as the scalar reference's re-sort sees
    # them). This runs inside the decode scan every step; the sort is
    # the sampler's dominant cost.
    sorted_m = jnp.where(sorted_x >= kth, sorted_x, -jnp.inf)
    probs = jax.nn.softmax(sorted_m, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)
    cutoff_idx = jnp.minimum(cutoff_idx, vocab - 1)
    cutoff = jnp.take_along_axis(sorted_m, cutoff_idx[:, None], axis=-1)
    # top_p >= 1.0 must be a strict no-op: fp32 cumsum saturates to 1.0
    # thousands of tokens early at real vocab sizes (measured on v5e:
    # 22604/32000 tokens wrongly masked), so `cum < 1.0` is NOT a no-op
    cutoff = jnp.where((top_p < 1.0)[:, None], cutoff, -jnp.inf)
    return jnp.where(x < cutoff, -jnp.inf, x)


def sample_logits_batched(logits, temperature, top_k, top_p, do_sample,
                          key):
    """Per-ROW sampling: [b, vocab] logits + per-row knob arrays → [b].

    The serving-engine sampler (reference analogue: the dedicated per-row
    kernel phi/kernels/gpu/top_p_sampling_kernel.cu:1, whose ``ps`` input
    is per batch row). All knobs are TRACED ARRAYS, so one compiled
    decode block serves any mix of greedy and sampled requests with any
    per-request temperature/top-k/top-p — no recompile per config:

      temperature [b] f32   (<=0 treated as 1e-6)
      top_k       [b] i32   (0 = disabled)
      top_p       [b] f32   (1.0 = disabled)
      do_sample   [b] bool  (False = argmax row)

    Rows draw independent samples from one key via
    ``jax.random.categorical`` over the jointly masked logits.
    """
    greedy = jnp.argmax(logits, axis=-1)
    x = _mask_logits_rowwise(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, x, axis=-1)
    return jnp.where(do_sample, sampled, greedy)


def sample_logits_per_slot(logits, temperature, top_k, top_p, do_sample,
                           keys):
    """``sample_logits_batched`` with per-ROW keys ([b] stacked PRNG
    keys): each row draws from its OWN key instead of a shared per-step
    key. The async serving engine derives row keys as
    ``fold_in(fold_in(base, request_id), token_index)``, which makes a
    request's sampled stream a pure function of (seed, request, token
    index) — independent of batching, speculative-dispatch depth, and
    preemption/replay interleaving, so a pipelined engine stays
    token-identical to its synchronous (depth-1) schedule."""
    greedy = jnp.argmax(logits, axis=-1)
    x = _mask_logits_rowwise(logits, temperature, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(keys, x)
    return jnp.where(do_sample, sampled, greedy)


def fold_sampling_keys(base_key, rseed, token_index):
    """Per-row replay-exact sampling keys: ``fold_in(fold_in(base, rid),
    token_index)`` for each row. This derivation IS the serving engine's
    determinism contract — the non-speculative decode scan and the
    speculative verify tick must fold IDENTICALLY so a draft is accepted
    iff it equals the token the plain scan would have emitted (spec-on ≡
    spec-off), and so sampled streams are independent of batching,
    pipelining depth, and preemption/replay. One definition, two call
    sites (``serving._build_decode`` / ``serving._build_spec_decode``)."""
    return jax.vmap(
        lambda r, n: jax.random.fold_in(jax.random.fold_in(base_key, r), n)
    )(rseed, token_index)


def decode_stop_update(tok, active, budget, eos_id):
    """On-device stop detection for one decode step (the sampling body's
    ``done`` bookkeeping). ``tok`` [b] is the token just emitted for rows
    where ``active``; ``budget`` [b] counts remaining allowed tokens;
    ``eos_id`` [b] is the per-row stop id (-1 = disabled). Returns
    ``(new_active, new_budget)`` — a row deactivates AFTER emitting its
    eos/budget-exhausting token (that token is kept, matching the host
    scheduler's append-then-check semantics), so the host never needs a
    block's tokens to decide whether the next block may dispatch."""
    budget = budget - active.astype(budget.dtype)
    stop = active & ((budget <= 0) | ((eos_id >= 0) & (tok == eos_id)))
    return active & ~stop, budget


def generate(model, input_ids, generation_config: GenerationConfig = None,
             **kwargs) -> jnp.ndarray:
    """Autoregressive generation for models exposing
    ``model.prefill(ids, max_len)`` / ``model.decode_step(tok, pos, caches)``
    (LlamaModel contract) with a ``logits(hidden)`` head on the wrapper.

    Returns [b, prompt + max_new_tokens] token ids (prompt included,
    reference generate() convention).
    """
    cfg = generation_config or GenerationConfig(**kwargs)
    input_ids = jnp.asarray(input_ids)
    b, prompt_len = input_ids.shape
    max_len = prompt_len + cfg.max_new_tokens

    core = getattr(model, "model", model)   # LlamaForCausalLM → LlamaModel
    head = model.logits if hasattr(model, "logits") else (lambda h: h)

    hidden, caches = core.prefill(input_ids, max_len)
    logits = head(hidden[:, -1, :])
    key = jax.random.PRNGKey(cfg.seed)

    decode = getattr(model, "_compiled_decode", None)
    if decode is None:
        def _step(tok, pos, caches):
            h, caches = core.decode_step(tok, pos, caches)
            return head(h[:, 0, :]), caches
        decode = _step

    tokens = [input_ids]
    finished = jnp.zeros((b,), bool)
    for i in range(cfg.max_new_tokens):
        key, sub = jax.random.split(key)
        next_tok = _sample_logits(logits.astype(jnp.float32), cfg, sub)
        if cfg.eos_token_id is not None:
            next_tok = jnp.where(finished, cfg.pad_token_id, next_tok)
            finished = finished | (next_tok == cfg.eos_token_id)
        tokens.append(next_tok[:, None])
        if cfg.eos_token_id is not None and bool(finished.all()):
            pad = jnp.full((b, cfg.max_new_tokens - i - 1), cfg.pad_token_id,
                           input_ids.dtype)
            if pad.shape[1]:
                tokens.append(pad)
            break
        if i < cfg.max_new_tokens - 1:
            pos = jnp.full((b,), prompt_len + i, jnp.int32)
            logits, caches = decode(next_tok, pos, caches)
    return jnp.concatenate(tokens, axis=1)


def _compiled_generate(model, cfg: GenerationConfig, b: int, prompt_len: int,
                       kind: str, page_size: int):
    """One jitted (prefill → scan-decode → tokens) program, cached ON THE
    MODEL per (config, shape, cache kind): repeat calls with the same
    shapes reuse the executable instead of re-tracing (the Python-loop
    ``generate`` gets this via _compiled_decode; the scan drivers need it
    too or every call pays full compile).

    ``kind``: "dense" (contiguous [b, max_len, kv, hd] caches) or "paged"
    (head-major page pools + block table — the vLLM-style serving path,
    reference: block_multi_head_attention_kernel.cu). All cache state is
    allocated INSIDE the traced function so nothing is baked into the
    executable as a constant.
    """
    key_ = (kind, page_size, b, prompt_len, cfg.max_new_tokens,
            cfg.do_sample, cfg.temperature, cfg.top_k, cfg.top_p,
            cfg.eos_token_id, cfg.pad_token_id)
    cache = model.__dict__.setdefault("_generate_cache", {})
    if key_ in cache:
        cache[key_] = cache.pop(key_)        # LRU refresh (dict is ordered)
        return cache[key_]

    max_len = prompt_len + cfg.max_new_tokens
    core = getattr(model, "model", model)
    head = model.logits if hasattr(model, "logits") else (lambda h: h)
    eos = cfg.eos_token_id

    def run(params, input_ids, key):
        # run under the layer's functional bridge so params are traced inputs
        with model._bind(params) if hasattr(model, "_bind") else \
                contextlib.nullcontext():
            if kind == "paged":
                # the ``ServingCore`` programs, every row's prompt at once
                pools0, tables = core.alloc_paged_caches(b, max_len,
                                                         page_size)
                state = core.alloc_slot_state(b)
                if jax.tree.leaves(state):
                    raise ValueError(
                        f"{type(core).__name__} keeps per-slot state, which "
                        f"a prefill fills one sequence at a time: serve it "
                        f"through ContinuousBatchingEngine")
                hidden, caches, _ = core.prefill_paged(
                    input_ids, pools0, tables, state, None, None)
                decode = lambda tok, pos, c: core.decode_step_paged(
                    tok, pos, c, tables, state)[:2]
            else:
                hidden, caches = core.prefill(input_ids, max_len)
                decode = core.decode_step
            logits0 = head(hidden[:, -1, :])

            def step(carry, i):
                logits, caches, key, finished = carry
                key, sub = jax.random.split(key)
                tok = _sample_logits(logits.astype(jnp.float32), cfg, sub)
                if eos is not None:
                    tok = jnp.where(finished, cfg.pad_token_id, tok)
                    finished = finished | (tok == eos)
                pos = jnp.full((b,), prompt_len + i, jnp.int32)
                h, caches = decode(tok, pos, caches)
                new_logits = head(h[:, 0, :])
                return (new_logits, caches, key, finished), tok

            finished0 = jnp.zeros((b,), bool)
            (_, _, _, _), toks = jax.lax.scan(
                step, (logits0, caches, key, finished0),
                jnp.arange(cfg.max_new_tokens))
        return jnp.concatenate([input_ids, toks.T], axis=1)

    compiled = jax.jit(run)
    cache[key_] = compiled
    # bounded LRU: serving with varied (batch, prompt_len) shapes must not
    # retain every compiled executable for the model's lifetime
    while len(cache) > 8:
        cache.pop(next(iter(cache)))
    return compiled


def generate_scan(model, input_ids, generation_config: GenerationConfig = None,
                  **kwargs) -> jnp.ndarray:
    """Fully-compiled generation: the whole decode loop is ONE lax.scan
    inside jit — no host↔device roundtrip per token (the Python-loop
    ``generate`` dispatches one device call per step). Finished sequences
    keep emitting pad; output matches ``generate`` for greedy decoding.

    TPU notes: static cache shapes (prompt padded into max_len at prefill),
    dynamic position via the scan carry — everything XLA needs to keep the
    decode step as a single resident program.
    """
    cfg = generation_config or GenerationConfig(**kwargs)
    input_ids = jnp.asarray(input_ids)
    b, prompt_len = input_ids.shape
    params = model.raw_parameters() if hasattr(model, "raw_parameters") else {}
    compiled = _compiled_generate(model, cfg, b, prompt_len, "dense", 0)
    return compiled(params, input_ids, jax.random.PRNGKey(cfg.seed))


def generate_paged(model, input_ids,
                   generation_config: GenerationConfig = None,
                   page_size: int = 128, **kwargs) -> jnp.ndarray:
    """Fully-compiled generation over PAGED KV caches (vLLM-style serving
    path; reference capability: block_multi_head_attention_kernel.cu).

    Instead of one dense [b, max_len, kv, hd] cache per layer, K/V live in
    head-major page pools indexed by a block table; each decode step
    writes one page slot and attends through the Pallas paged kernel on
    TPU (XLA gather elsewhere). Greedy output matches generate_scan.
    """
    cfg = generation_config or GenerationConfig(**kwargs)
    input_ids = jnp.asarray(input_ids)
    b, prompt_len = input_ids.shape
    params = model.raw_parameters() if hasattr(model, "raw_parameters") else {}
    compiled = _compiled_generate(model, cfg, b, prompt_len, "paged",
                                  page_size)
    return compiled(params, input_ids, jax.random.PRNGKey(cfg.seed))
