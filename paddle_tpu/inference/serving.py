"""Async continuous-batching serving engine over the paged-KV decode path.

Reference capability: the block/paged KV-cache serving stack
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu and the
fleet dist-inference helpers). The reference exposes the kernel; serving
systems built on it (vLLM-style) add a page allocator + request scheduler.
This module is that scheduler, TPU-shaped:

- ONE compiled decode block over ``max_batch`` fixed slots (static shapes;
  no recompilation as requests come and go). Inactive slots write their
  K/V into a reserved garbage page and their sampled token is ignored.
- A host-side free-list page allocator over a global pool. Prompt pages
  are claimed at admission; decode pages are claimed LAZILY when a
  sequence's position crosses a page boundary, so short completions never
  reserve worst-case memory (the point of paged attention).
- Recompute-style preemption: if the pool is exhausted when a running
  sequence needs its next page, the most recently admitted active slot is
  evicted back to the queue (pages freed, generated tokens kept for
  replay) — vLLM's "recompute" policy, which on TPU is just a re-prefill.
- Prefill runs per-slot with the prompt padded up to a page multiple
  (bucketed → bounded executable count); the first-token logits are taken
  at the true last-prompt index.

ASYNC hot loop (vLLM SOSP'23 / NanoFlow-style host-overlap, TPU-shaped):

- Stop detection runs ON DEVICE: the decode scan carries per-slot eos ids
  and remaining-token budgets, deactivates a slot the step AFTER it emits
  its stop token, masks later tokens to pad and routes their K/V to the
  garbage page. The host never needs block N's tokens to decide whether
  block N+1 may dispatch.
- Dispatches are PIPELINED: block N+1 is issued while block N is still in
  flight (bounded window, ``async_depth``, default 2). Block N's [K, B]
  tokens + done flags drain via an async device→host copy and are
  reconciled at block boundaries — retirements, admissions and page
  bookkeeping all happen one block behind the device, hidden under its
  compute. A slot retired by block N's results had its speculative
  block-N+1 writes routed to the garbage page by the same on-device
  active mask, so rollback is free and outputs are bit-identical to the
  synchronous (``async_depth=1``) schedule.
- Scheduler state is DEVICE-RESIDENT: pos, active mask, budgets, sampling
  knobs and last logits persist as device arrays threaded from block to
  block; admissions/evictions touch them through small jitted update fns.
  The per-tick host work of the old engine (seven ``jnp.asarray`` uploads
  + a host ``jax.random.split``) is gone; sampling keys fold on-device
  from (seed, request id, token index), making sampled streams
  schedule-independent (and exact across preemption/replay).

TOKEN-LEVEL SPECULATION (``spec_k > 0``, Leviathan'23 / prompt-lookup
Saxena'23, TPU-shaped):

- Each tick drafts ``spec_k`` tokens from the slot's device-resident
  token history (``DraftProvider``; n-gram prompt-lookup by default —
  zero model cost), verifies all of them in ONE (spec_k+1)-wide forward
  against the paged KV cache (``decode_verify_paged``), and commits the
  agreeing prefix: 1..spec_k+1 tokens per weight pass.
- Acceptance reuses the replay-exact (seed, rid, token_index) keys, so
  a draft is accepted iff it EQUALS the token the non-speculative scan
  would have emitted — spec-on streams are token-identical to spec-off,
  greedy and sampled alike (tests/test_serving_spec.py).
- Accept/reject folds into the same ``decode_stop_update`` carry that
  retires slots: rejected suffixes leave the tick as pad with
  ``kept=False`` and their K/V is overwritten by the next verify chunk
  (positions advance only by the committed prefix) or routed to the
  garbage page — no rollback, and the depth-2 in-flight window is
  preserved because a speculatively dispatched block self-masks tokens
  the previous block rejected, exactly as it self-masks retired slots.
- Page claims become variable-stride: the host projects the MAX stride
  per in-flight block and re-anchors at drained truth; tables keep every
  page ever claimed, so claim coverage is monotone and always ahead of
  what the device can commit.

RADIX PREFIX SHARING (``prefix_cache=True``, PagedAttention Kwon'23 /
RadixAttention Zheng'24, TPU-shaped):

- A radix tree over token sequences (``prefix_cache.RadixPrefixCache``)
  owns REFCOUNTED pages in the same pool the engine allocates from.
  Admission walks the tree, maps the matched pages straight into the new
  slot's page table (one lock per slot; node splits are page-aligned)
  and prefills ONLY the unmatched suffix through the existing
  chunked-prefill path from a page-aligned offset — shared system
  prompts cost one table write instead of a full prefill.
- A FULL-prompt match takes the COW fast path: the page holding the
  last prompt token is copy-on-written into a private page (decode is
  about to diverge into it) and exactly ONE token is re-forwarded
  (``decode_verify_paged`` at L-1) to produce the first-token logits —
  TTFT collapses to one decode-step's work.
- Retiring/preempted slots DONATE their completed full pages to the
  tree before their lock releases, so conversation-style reuse and
  preemption replay both hit. Refcount-0 tree pages stay cached and are
  LRU-evicted (tail-first) only under pool pressure, inside
  ``_alloc_pages`` — the ``pool_dry_drains``/recompute-preemption
  machinery downstream is untouched, it just sees a deeper pool.
- The refcount invariant (fuzz-tested): every pool page is free, OR
  privately owned by exactly one table, OR tree-owned with
  ``node.ref == number of tables mapping it``. Decode never writes a
  shared page: the mapped prefix always ends below the first decode
  position (the COW fast path privatizes the boundary page at admit).
- ``prefix_cache=False`` (default) leaves every path above unbuilt —
  the engine is characterization-identical to the pre-prefix code.

SLO-AWARE ADMISSION (``admission=SLOAdmissionPolicy(...)``): queued
requests are admitted shortest-uncached-suffix first (prefix-aware
ordering — the SGLang insight), a long cold prefill is DEFERRED while
the ITL p99 gauge breaches its target (unless TTFT is also breaching),
and recompute-preemption prefers low-progress / low-shared-refcount
victims. ``admission=None`` (default) keeps FIFO + newest-rid victims.

The engine is exact: greedy outputs match ``generate_scan`` per request
regardless of batching/preemption/pipelining/speculation/prefix-sharing
interleaving (tests/test_serving.py, tests/test_serving_async.py,
tests/test_serving_spec.py, tests/test_serving_prefix.py).
"""

from __future__ import annotations

import contextlib
import hashlib
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import compile_cache
from ..models.serving_core import ServingCore
from ..observability.metrics import REGISTRY as _REG
from ..observability.sentry import sentry as _sentry
from ..observability.tracing import TRACER as _TRACE
from ..profiler import RecordEvent
from .admission import AdmissionPolicy, VictimInfo
from .generation import (GenerationConfig, decode_stop_update,
                         fold_sampling_keys, sample_logits_per_slot)
from .prefix_cache import RadixPrefixCache
from .speculative import DraftProvider, NgramDraftProvider


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray                  # [L] int32
    max_new_tokens: int
    # per-request sampling knobs (engine defaults when not overridden)
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    do_sample: bool = False
    eos_token_id: Optional[int] = None
    # sampling-stream identity: the value folded into the per-token keys
    # (defaults to rid). A router re-admitting a request on ANOTHER
    # engine passes the original identity so the sampled stream is
    # engine-independent (serving_fabric failover replay).
    rseed: Optional[int] = None
    generated: List[int] = field(default_factory=list)
    done: bool = False
    slot: int = -1                      # active slot, -1 = queued/finished
    # the request's timeline, perf_counter stamps (0 = not yet). Each is
    # set ONCE, at its first occurrence, so a preempted request's replay
    # keeps submit <= admit <= prefill_start <= prefill_dispatched <=
    # first token <= done; ``preemptions`` counts the replays.
    submit_t: float = 0.0
    admit_t: float = 0.0                # pages claimed, slot taken
    prefill_start_t: float = 0.0        # first prefill program about to go
    prefill_dispatched_t: float = 0.0   # last prefill program enqueued
    first_tok_t: float = 0.0            # first token drained to the host
    done_t: float = 0.0                 # completion timestamp
    preemptions: int = 0
    last_emit_t: float = 0.0            # previous tick's emit timestamp
    itl_gaps: List[float] = field(default_factory=list)  # per-TICK gaps
    prefilled: int = 0                  # KV tokens written (chunked mode)
    prefill_target: int = 0             # prompt+replay length to prefill
    # distributed tracing (ISSUE 19): {"tr": tracer, "parent": wire ctx,
    # "queue"/"res": open spans, "last": decode-epoch wall stamp}. None
    # when untraced — every tracing branch below is one attr test.
    tspans: Optional[dict] = None


@dataclass
class _InflightBlock:
    """One dispatched decode block awaiting host reconciliation. The
    device arrays are the block's OUTPUTS (fresh buffers, never donated),
    async-copied to host at dispatch; ``participants`` snapshots the
    (slot, request) pairs the host believed live at dispatch time —
    a slot that stopped on-device in an earlier in-flight block simply
    drains an all-False kept column here."""
    toks: object                        # [K, B] device int32
    kept: object                        # [K, B] device bool (prefix mask)
    pos: object                         # [B] device int32, post-block
    active: object                      # [B] device bool, post-block
    participants: List[Tuple[int, "_Request"]]
    K: int
    seq: int = 0                        # dispatch number: pairs the spans
    # spec mode only: per-slot MAX possible commits this block (the
    # stride the host projected at dispatch) — drains subtract it back
    # out of the projection when the device committed fewer
    steps: Optional[Dict[int, int]] = None
    # the stream's books (_StreamBooks.dispatched): programs the admission
    # path enqueued in front of this block, the instant the first program
    # of its interval went to a QUIET device (None: a block dispatched
    # before was unfinished), and whether one was undrained at its dispatch
    admit_calls: int = 0
    quiet_t: Optional[float] = None
    behind: bool = False


class _StreamBooks:
    """The books of the engine's ONE device stream, kept by the host from
    what it knows: the order of what it enqueued, whether a block was
    still unfinished when it looked, and the instant a drain handed a
    block's tokens over (its STAMP). An INTERVAL holds the device time of
    exactly what was enqueued between two dispatches: the block's K ticks
    and the programs the admission path put in front of them
    (prefill_paged, prefill_chunk, tail_logits, cow_page, activate_slot).

    An interval is attributed only where the device was demonstrably busy
    from its start to its end and both instants are known. It ENDS at its
    stamp where the host had to WAIT for the block in the drain (a block
    that was ready before the host looked has a late stamp; a host that
    is stalled INSIDE its wait comes back late too, and that the books
    cannot see: the stall is in the run it closes). It STARTS
    (b) at the enqueue of its first program where every block dispatched
    before was finished by then, so the device was quiet and started at
    that instant (what lies before it is the device's idle time: in
    ``stream_s`` and in no program's sum); or (a) at the previous stamp,
    where the block was dispatched behind a predecessor that was still
    unfinished at that first enqueue, so the device went from one straight
    to the other, and that predecessor's stamp was waited for. Where the
    predecessor's stamp was LATE but its own start is known, the two
    intervals are one RUN with one start, the K's and the admission
    programs of both, closed by the next stamp that is waited for: a late
    stamp cannot split a run, it does not break it.

    A closed run with no admission work is clean ticks (``stream_tick_s``
    over ``stream_ticks``); one with admission work, less its ticks at the
    RECENT clean tick's time (the tick drifts with the live context, so
    the baseline is local), is that work's device time
    (``stream_admit_s``). What the books could NOT attribute is
    ``stream_unattributed_s``: the time up to the stamp of an interval
    whose start they cannot know, and a closed admission run that no clean
    tick came before. The other sums read low by up to its share of
    ``stream_s``. All lifetime sums, monotone; a run left open when the
    engine stops is in none of them."""

    RECENT = 8          # clean ticks the baseline is the mean of

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self.stream_s = 0.0
        self.stream_tick_s = 0.0
        self.stream_ticks = 0
        self.stream_admit_s = 0.0
        self.stream_unattributed_s = 0.0
        self._recent: Deque[float] = deque(maxlen=self.RECENT)
        self._booked: Optional[float] = None  # stream_s is summed up to here
        self._exact = False         # ... and that instant is a waited stamp
        # a run whose last stamp was late: (start, K, admission programs)
        self._open: Optional[Tuple[float, int, int]] = None
        self._pending = 0        # admission programs since the last dispatch
        self._quiet_t: Optional[float] = None

    def _enqueue(self, busy: bool) -> None:
        if not self._pending and not busy:         # an interval's first
            self._quiet_t = self._clock()

    def admitted(self, busy: bool) -> None:
        """The admission path is about to enqueue one program (``busy``: a
        block dispatched before is still unfinished)."""
        self._enqueue(busy)
        self._pending += 1

    def dispatched(self, busy: bool, behind: bool) -> dict:
        """A decode block is about to be enqueued (``behind``: a block
        dispatched before is not drained yet): the fields its
        ``_InflightBlock`` keeps for ``drained``."""
        self._enqueue(busy)
        out = dict(admit_calls=self._pending, quiet_t=self._quiet_t,
                   behind=behind)
        self._pending, self._quiet_t = 0, None
        return out

    def recent_tick_s(self) -> Optional[float]:
        """The mean of the last few clean ticks (None before the first)."""
        return sum(self._recent) / len(self._recent) if self._recent else None

    def drained(self, K: int, admit_calls: int, quiet_t: Optional[float],
                behind: bool, waited: bool) -> Tuple[float, dict]:
        """A block's tokens just reached the host (``waited``: the host
        found it unfinished and blocked for it). Returns the stamp and
        what the ``serving::drain`` span says of it: ``chained`` 1 with
        the closed run's ``interval_us``, ``ticks`` and ``admit_calls``;
        2 where the stamp was late and the run stays open; 0 where the
        books gave up the time since the last instant they had booked."""
        now = self._clock()
        run, self._open = self._open, None
        start = None
        if quiet_t is not None:                                      # (b)
            start = quiet_t
        elif behind and run is not None:                # (a), the run goes on
            (start, k, n), run = run, None
            K, admit_calls = K + k, admit_calls + n
        elif behind and self._exact:                                 # (a)
            start = self._booked
        said = dict(ticks=K, admit_calls=admit_calls)
        if self._booked is None:
            self._booked = start
        booked = self._booked
        if booked is None:              # the first stamp: nothing to span
            self._booked, self._exact = now, waited
            return now, dict(said, interval_us=0, chained=0)
        if start is None or start < booked:
            self.stream_s += now - booked
            self.stream_unattributed_s += now - booked
            self._booked, self._exact = now, waited
            return now, dict(said, chained=0,
                             interval_us=round((now - booked) * 1e6))
        if run is not None:     # a run no stamp closed ended before ``start``
            self.stream_s += start - booked
            self.stream_unattributed_s += start - booked
            self._booked = booked = start
        length = now - start
        if not waited:
            self._open, self._exact = (start, K, admit_calls), False
            return now, dict(said, interval_us=round(length * 1e6), chained=2)
        self.stream_s += now - booked
        if not admit_calls:
            self.stream_tick_s += length
            self.stream_ticks += K
            self._recent.append(length / K)
        elif self._recent:
            self.stream_admit_s += max(
                length - K * self.recent_tick_s(), 0.0)
        else:
            self.stream_unattributed_s += length
        self._booked, self._exact = now, True
        return now, dict(said, interval_us=round(length * 1e6), chained=1)


# self-describing KV-page handoff payload format (serialize_pages /
# adopt_pages); bump on any layout change — adoption REJECTS unknown fmts.
# v2 (ISSUE 17) carries the pool dtype and, for int8 pools, the per-page
# fp32 K/V scales. v1 payloads (scale-less) are still adopted by NATIVE
# (bf16/f32) pools — a v1 emitter predates quantized pools, so its pages
# are float and layout-compatible; an int8 pool REJECTS v1 (no scales to
# dequant by), and the fabric's failed-handoff path falls back to a cold
# prefill.
HANDOFF_FMT = "pt-kv-pages-v2"
HANDOFF_FMT_V1 = "pt-kv-pages-v1"

# the most prefill programs (one a padded prompt width) an engine builds
# over its ``max_len``: each is a compilation, a cache entry and seconds of
# set-up. Every table of 24 pages or fewer keeps page-wide widths.
MAX_PREFILL_PROGRAMS = 24


def _entry_page_copy(entry, src, dst):
    """Copy physical page ``src`` → ``dst`` within one per-layer pool
    entry, generically over layout: 4-D pool arrays carry pages on axis
    1, 1-D per-page scale arrays (int8 pools) on axis 0 — so COW, the
    tail re-forward and page adoption move a page's scale with its
    bytes for free."""
    return tuple(a.at[:, dst].set(a[:, src]) if a.ndim == 4
                 else a.at[dst].set(a[src]) for a in entry)


def _named_jit(fn, name: str, **jit_kw):
    """``jax.jit(fn)`` compiled as the program ``jit_<name>``: the name the
    device trace's ``XLA Modules`` line prints and, for a program built per
    bucket, the only thing that tells two buckets apart there. Every name
    is one of ``profiler.SERVING_PROGRAMS`` (plus ``_<bucket>``)."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kw)


# the fields of one retired request's record, in the order it is stored
_TIMELINE = ("rid", "submit_t", "admit_t", "prefill_start_t",
             "prefill_dispatched_t", "first_tok_t", "done_t", "tokens",
             "preemptions")


class _PoolDry(Exception):
    """Page pool exhausted while speculative blocks are still in flight:
    drain them first (retirements may free pages) before preempting."""


class ContinuousBatchingEngine:
    """vLLM-style continuous batching over a model whose core (the model
    itself, or its ``.model``: ``LlamaForCausalLM``) is a
    ``models.serving_core.ServingCore``: the cache and the programs declared
    there are all the engine uses of it.

    ``async_depth``: bounded in-flight dispatch window. 1 = synchronous
    (dispatch → drain → bookkeep, the pre-async engine's schedule, kept
    bit-identical); 2 (default) overlaps host scheduling/bookkeeping of
    block N with the device computing block N+1.

    ``spec_k``: draft tokens per speculative tick (0 = off). When on,
    the tick is one (spec_k+1)-wide verify forward and ``decode_block``
    is NOT consulted — the spec tick already amortizes the host round
    trip over its committed run the way a K-token block does."""

    def __init__(self, model, max_batch: int = 8, page_size: int = 128,
                 max_len: int = 2048, num_pages: Optional[int] = None,
                 generation_config: Optional[GenerationConfig] = None,
                 decode_block: int = 1, chunked_prefill: bool = False,
                 prefill_chunk: Optional[int] = None, async_depth: int = 2,
                 attn_crossover: Optional[int] = None, spec_k: int = 0,
                 draft_provider: Optional[DraftProvider] = None,
                 prefix_cache: bool = False,
                 admission: Optional[AdmissionPolicy] = None,
                 name: Optional[str] = None):
        # replica identity (ISSUE 12 satellite): N engines in one process
        # (the in-proc serving fabric) must not merge their registry
        # series — every gauge/counter this engine publishes carries an
        # engine=<name> label when a name is given. Unnamed engines keep
        # their historical label-free series.
        self.name = name or ""
        self._mlabels: Dict[str, str] = ({"engine": self.name}
                                         if self.name else {})
        # tracer override hook: tests inject a private Tracer so ONE
        # process can play both sides of the TCP hop without the
        # replica's spans landing in the router's singleton
        self._tracer = None
        self.core = getattr(model, "model", model)
        if not isinstance(self.core, ServingCore):
            raise TypeError(
                f"{type(self.core).__name__} is no ServingCore "
                f"(models/serving_core.py declares what the engine serves)")
        # the model around the core, resolved ONCE: its leaves, the context
        # that binds a program's traced leaves, and its head
        self._params = (model.raw_parameters()
                        if hasattr(model, "raw_parameters") else {})
        self._bind = (model._bind if hasattr(model, "_bind")
                      else (lambda params: _NULL))
        self._head = (model.logits if hasattr(model, "logits")
                      else (lambda hidden: hidden))
        # per-slot state (``ServingCore.alloc_slot_state``; empty where the
        # state is all in the pages) lives beside the pools, is donated
        # with them, written by the slot's prefill and rewritten by every
        # tick; a freed, reused or preempted slot needs no copy, because
        # its next prefill overwrites it. What would need a SNAPSHOT of it
        # at a position other than a sequence's end is refused here, by name
        self.slot_state = self.core.alloc_slot_state(max_batch)
        self.slot_state_bytes = sum(
            a.size * a.dtype.itemsize for a in jax.tree.leaves(self.slot_state))
        if self.slot_state_bytes:
            for mode, on in (("chunked_prefill", chunked_prefill),
                             ("prefix_cache", prefix_cache),
                             ("spec_k", spec_k)):
                if on:
                    raise ValueError(
                        f"{mode}={on!r} needs a snapshot of the per-slot "
                        f"state {type(self.core).__name__} keeps beside "
                        f"its pages (alloc_slot_state), which the engine "
                        f"does not take yet")
        # the modes built on an optional program (the prefill-extend, the
        # multi-token verify: a latent-cache model has neither yet) are
        # refused here, by name, not inside a trace
        has = self.core.optional_programs
        if spec_k and "decode_verify_paged" not in has:
            raise ValueError(
                f"spec_k={spec_k} needs a model whose core implements "
                f"decode_verify_paged (multi-token paged verify); "
                f"{type(self.core).__name__} does not")
        for mode, on, needs in (
                ("chunked_prefill", chunked_prefill, ("prefill_chunk_paged",)),
                ("prefix_cache", prefix_cache, ("prefill_chunk_paged",
                                                "decode_verify_paged"))):
            lacks = [m for m in needs if m not in has]
            if on and lacks:
                raise ValueError(
                    f"{mode}=True needs a model whose core implements "
                    f"{' and '.join(lacks)}; "
                    f"{type(self.core).__name__} does not")
        self.cfg = generation_config or GenerationConfig()
        self.max_batch = max_batch
        self.page_size = page_size
        self.max_len = max_len
        self.pages_per_seq = -(-max_len // page_size)
        # a prompt is padded to whole steps of so many tokens, one prefill
        # program a width: a page while the table spans no more pages than
        # MAX_PREFILL_PROGRAMS, else the fewest pages that keep the
        # programs within it
        self._bucket_step = page_size * -(-self.pages_per_seq
                                          // MAX_PREFILL_PROGRAMS)
        # pool: page 0 is the reserved garbage page for inactive slots
        total = (num_pages if num_pages is not None
                 else max_batch * self.pages_per_seq) + 1
        pools, _ = self.core.alloc_paged_caches(
            1, total * page_size, page_size)
        self.pools = pools
        # a model with NO paged layer (every mixer keeps a per-slot state:
        # ``pools`` is empty) is served without a page: no page is counted,
        # claimed or freed (the allocator below holds none and the tables
        # stay at 0), a request is admitted by a free slot alone, and
        # ``page_size`` is only the step of the prefill programs' widths
        self.paged_layers = len(pools)
        if not pools:
            total = 1
        # int8 KV pages (ISSUE 17): a quantized pool's per-layer entry is
        # the 4-tuple (kp, vp, kscale, vscale); everything below that
        # moves pages (COW, handoff, adoption) is layout-generic, and the
        # decode/prefill write paths quantize inside the model
        self.kv_quant = bool(pools) and len(pools[0]) == 4
        self.kv_quant_ticks = 0             # decode ticks on an int8 pool
        # "gqa": K and V pages per KV head; "mla": one latent row a token.
        # Nothing below reads a pool entry's arrays as K and V except the
        # handoff (serialize_pages / adopt_pages), which refuses the rest
        self.attention_kind = self.core.attention_kind
        # bytes of cache a token takes over all layers, as ALLOCATED (a
        # gauge): the 4-D arrays of every entry, a token's share of a page,
        # a latent row's padding to whole lane tiles included
        self.kv_bytes_per_token = sum(
            a.shape[0] * a.shape[3] * a.dtype.itemsize
            for entry in pools for a in entry if a.ndim == 4)
        # counters a model's decode tick adds up on the device
        # (``core.tick_counters``: a routed model's expert load); they ride
        # to the host inside the block's token array
        self._tick_counters = tuple(self.core.tick_counters)
        self.tick_counts = dict.fromkeys(self._tick_counters, 0)
        self._total_pages = total - 1
        self._free: List[int] = list(range(total - 1, 0, -1))  # stack; 0 kept
        self.tables = np.zeros((max_batch, self.pages_per_seq), np.int32)
        self._tables_dev = None
        self._tables_dirty = True
        # reconciled positions (exact up to the last drained block) and
        # the device-side PROJECTION including in-flight blocks — the
        # allocator claims pages against the projection, so speculative
        # writes always land in owned pages. For a live (not-stopped)
        # slot projection == device pos; an early eos only ever makes the
        # projection an over-claim, freed wholesale at retirement.
        self.pos = np.zeros((max_batch,), np.int32)
        self._proj_pos = np.zeros((max_batch,), np.int64)
        self._proj_gen = np.zeros((max_batch,), np.int64)
        # host mirrors of the per-slot sampling knobs (device copies are
        # updated by the jitted activation fn; the mirror only drives the
        # any_sample executable choice)
        self._dosample = np.zeros((max_batch,), bool)
        self._slots: List[Optional[_Request]] = [None] * max_batch
        self._queue: Deque[_Request] = deque()
        self._requests: Dict[int, _Request] = {}
        self._rid = itertools.count()
        self._base_key = jax.random.PRNGKey(self.cfg.seed)
        self._prefill_cache: Dict[int, object] = {}
        # decode_block = tokens generated per compiled scheduler tick. One
        # tick costs ONE dispatch + ONE host readback regardless of K, so
        # throughput scales ~K until compute dominates. The scan
        # deactivates a slot at its own EOS/max_new ON DEVICE, so tokens
        # past the stop are pad + garbage-page KV and outputs are EXACT
        # for any K.
        self.decode_block = max(1, int(decode_block))
        self._decode_fns: Dict[tuple, object] = {}  # (K, sample, impl) -> fn
        # one row per program this engine built (name, t_s, seconds,
        # cache): which program compiled, when, for how long — the
        # split of set-up time. ``_built`` holds what already has its row.
        self.build_log: List[dict] = []
        self._built: set = set()
        self._block_seq = 0                 # decode blocks dispatched
        self.async_depth = max(1, int(async_depth))
        # token-level speculative decoding (ISSUE 6): each tick drafts
        # spec_k tokens (DraftProvider, n-gram prompt-lookup by default),
        # verifies all of them in ONE (spec_k+1)-wide forward against the
        # paged KV cache, and commits the matching prefix — 1..spec_k+1
        # tokens per tick for one weight pass. spec_k=0 is EXACTLY the
        # non-speculative engine (every spec branch below is gated).
        self.spec_k = max(0, int(spec_k))
        self._draft: Optional[DraftProvider] = None
        self._hist = None                   # [B, max_len] device history
        self._hist_set_fn = None
        self.spec_tokens_proposed = 0       # drafts scored by a verify pass
        self.spec_tokens_accepted = 0       # drafts committed (beyond the
        #                                     tick's one guaranteed token)
        self._spec_drains = 0               # committing spec drains
        if self.spec_k:
            self._draft = draft_provider or NgramDraftProvider()
            self._hist = jnp.zeros((max_batch, max_len), jnp.int32)
        # context-aware dense/paged dispatch: each dispatched block picks
        # the attention path from the batch's MAX projected context vs the
        # measured crossover (TuneDB-backed,
        # autotune.paged_decode_crossover); the choice is baked per
        # executable, so at most 2 executables per (K, any_sample). On
        # v5e the Pallas kernel is ahead at every context measured
        # (256-8192), so the default crossover is 0 and an engine resolves
        # ONE decode executable: a crossover inside (0, max_len) gives a
        # second one, first met on a quiet tick and compiled mid-traffic.
        if attn_crossover is None:
            from ..ops.pallas.autotune import paged_decode_crossover
            attn_crossover = paged_decode_crossover()
        self.attn_crossover = int(attn_crossover)
        self.attn_path_ticks = {"dense": 0, "paged": 0}
        self._inflight: Deque[_InflightBlock] = deque()
        # (rid, token) pairs a cancel() drained outside step()
        self._held_emitted: List[tuple] = []
        # radix prefix-shared KV (ISSUE 7): tree nodes own refcounted
        # pages in THIS pool; one PrefixLock per occupied slot records
        # exactly which nodes its table maps. prefix_cache=False builds
        # none of it — every sharing branch below gates on _prefix.
        self._prefix = (RadixPrefixCache(page_size) if prefix_cache
                        else None)
        self._tree_locks: List[Optional[object]] = [None] * max_batch
        self._admission = admission
        self.prefix_hit_tokens = 0          # prompt tokens NOT recomputed
        self.prefix_cow_copies = 0          # shared pages copy-on-written
        self._prefix_prompt_tokens = 0      # denominator for the hit rate
        self._price_cache: Dict[int, tuple] = {}   # rid -> (key, price)
        self._cow_fn = None                 # jitted page copy (COW)
        self._tail_fn = None                # 1-token re-forward for logits
        # KV-page handoff (ISSUE 12): jitted gather/scatter for
        # serialize_pages/adopt_pages + lifetime transfer counters
        self._gather_fn = None
        self._scatter_fn = None
        self.pages_exported = 0
        self.pages_adopted = 0
        # chunked prefill (Sarathi/vLLM prefill-extend): admission claims
        # pages but prefill proceeds one chunk per scheduler tick,
        # interleaved with decode of running slots — bounds the per-tick
        # stall a long prompt inflicts on running requests' ITL. The
        # chunk is page-aligned so every chunk writes whole pages.
        self.chunked_prefill = bool(chunked_prefill)
        self.prefill_chunk = int(prefill_chunk or page_size)
        if self.prefill_chunk % page_size:
            raise ValueError(f"prefill_chunk ({self.prefill_chunk}) must "
                             f"be a multiple of page_size ({page_size})")
        self._chunk_fns: Dict[int, object] = {}  # ids width -> fn
        # device-resident scheduler state, created at first activation:
        #   state = (logits [B,V], pos [B], active [B], budget [B], gen [B])
        #   knobs = dict(rseed, eos, temp, topk, topp, dosample)  [B] each
        self._state = None
        self._knobs = None
        self._act_fn = None
        self._deact_fn = None
        self.preemptions = 0
        # times a dry pool was answered by draining the in-flight window
        # (instead of immediately evicting) — retirements it reveals often
        # free pages without costing anyone a replay
        self.pool_dry_drains = 0
        # bounded window (run() releases _Request objects for the same
        # reason — a long-lived engine must not grow per-request state)
        # — ONE record per retired request: its timeline stamps and
        # token count; latency_stats() is computed from these
        self._timelines: Deque[tuple] = deque(maxlen=10_000)
        # per-tick inter-token gaps of retired requests (incl. stalls a
        # preemption or a long peer prefill inflicted on them)
        self._itl_gaps = deque(maxlen=100_000)
        # cost observatory (ISSUE 9): attached when a decode executable is
        # built with the metrics plane on; the books' recent clean tick is
        # the measured seconds its breakdown gauges divide
        self._cost_watch = None
        # always-on lifetime counters, all returned by stats(). The
        # admission path's work (_prefill_event): the prefill programs'
        # ids' widths summed, and what of those was padding
        self.prefill_width_tokens = 0
        self.prefill_pad_tokens = 0
        # a request's stage waits, summed when its first token reaches
        # the host (_reconcile_host) from the stamps request_timelines()
        # hands out
        self.first_tokens = 0
        self.prefill_dispatch_s = 0.0
        self.first_token_wait_s = 0.0
        # which of the engine's programs had the device (_StreamBooks)
        self._books = _StreamBooks()
        # metrics-plane lifetime counters (plain attrs: zero cost until
        # publish_metrics mirrors them into the registry as deltas)
        self._tokens_emitted = 0
        self._requests_retired = 0
        self._published: Dict[str, float] = {}
        # gauge handles resolved ONCE (registry.reset() keeps metric
        # objects valid): the per-tick path must not pay a registry
        # name-lookup per gauge per tick
        self._g_queue = _REG.gauge("pt_serving_queue_depth",
                                   "requests waiting for a slot")
        self._g_inflight = _REG.gauge(
            "pt_serving_inflight_blocks",
            "decode blocks dispatched but not yet drained")
        self._g_active = _REG.gauge("pt_serving_active_slots",
                                    "slots holding a request")
        self._g_free = _REG.gauge("pt_serving_free_pages",
                                  "KV pool pages unclaimed")
        self._g_occupancy = _REG.gauge(
            "pt_serving_page_pool_occupancy",
            "fraction of the KV page pool claimed")
        self._g_prefix_pages = _REG.gauge(
            "pt_serving_prefix_shared_pages",
            "pool pages owned by the radix prefix cache")
        self._g_prefix_hit = _REG.gauge(
            "pt_serving_prefix_hit_rate",
            "prefix-cache hit tokens / admitted prompt tokens")

    # -- public API ---------------------------------------------------------

    def submit(self, input_ids, max_new_tokens: Optional[int] = None,
               generation_config: Optional[GenerationConfig] = None,
               rseed: Optional[int] = None,
               replay_prefix=None, trace=None) -> int:
        """Queue one request; returns its id.

        ``rseed`` overrides the sampling-stream identity folded into the
        per-token keys (default: this engine's rid). A router spreading
        one logical request stream across replicas — or re-admitting it
        on a survivor after a replica death — passes the ORIGINAL
        identity so sampled tokens are engine-independent.

        ``replay_prefix`` seeds the request with tokens ALREADY emitted
        by a previous incarnation (a failed replica): the engine treats
        it exactly like its own recompute-preemption replay — the prefix
        is re-prefilled (or prefix-cache mapped), generation resumes at
        token index ``len(replay_prefix)`` with the remaining budget,
        and the replay-exact keys make the continuation token-identical
        to the uninterrupted stream.

        ``generation_config`` overrides the engine's sampling knobs
        (do_sample/temperature/top_k/top_p) and eos_token_id for THIS
        request only; the token budget comes from the ``max_new_tokens``
        PARAMETER (falling back to the engine default) — gc's own
        max_new_tokens is deliberately ignored, since a caller passing a
        config just to enable sampling would otherwise silently get the
        dataclass default budget of 32. Knobs are per-slot arrays inside
        the one compiled decode block (sample_logits_per_slot), so any
        mix of greedy and sampled requests batches together with no
        recompilation — the TPU analogue of the reference's per-row
        top_p_sampling_kernel.cu."""
        ids = np.asarray(input_ids, np.int32).reshape(-1)
        gc = generation_config or self.cfg
        new = (max_new_tokens if max_new_tokens is not None
               else self.cfg.max_new_tokens)
        if len(ids) == 0:
            raise ValueError("empty prompt")
        if new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {new}")
        if len(ids) + new > self.max_len:
            raise ValueError(f"prompt {len(ids)} + max_new {new} exceeds "
                             f"engine max_len {self.max_len}")
        replay = ([] if replay_prefix is None
                  else [int(t) for t in np.asarray(replay_prefix,
                                                   np.int32).reshape(-1)])
        if len(replay) >= new:
            raise ValueError(f"replay_prefix ({len(replay)} tokens) "
                             f"exhausts max_new_tokens ({new})")
        # the replay prefix re-prefills WITH the prompt, so it counts
        # against the pool here — otherwise a router failover re-submit
        # passes validation and _admit raises mid-step, which would
        # crash the whole fabric instead of failing one request
        if self.paged_layers and -(-(len(ids) + len(replay))
                                   // self.page_size) > self._total_pages:
            raise ValueError(f"prompt needs more pages than the pool "
                             f"holds ({self._total_pages}); raise "
                             f"num_pages")
        req = _Request(next(self._rid), ids, new,
                       temperature=float(gc.temperature),
                       top_k=int(gc.top_k), top_p=float(gc.top_p),
                       do_sample=bool(gc.do_sample),
                       eos_token_id=gc.eos_token_id,
                       rseed=None if rseed is None else int(rseed))
        req.generated = replay
        req.submit_t = time.perf_counter()
        if trace is not None:
            # ``trace`` is the wire TraceContext dict the fabric carried
            # over the transport; spans minted here stitch under it
            tr = self._tracer or _TRACE
            if tr.enabled:
                sp = tr.start("replica::queue", parent=trace,
                              tags={"rid": req.rid,
                                    "engine": self.name})
                if sp is not None:
                    req.tspans = {"tr": tr, "parent": trace,
                                  "queue": sp}
        self._requests[req.rid] = req
        self._queue.append(req)
        return req.rid

    def has_work(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    def step(self) -> List[tuple]:
        """One scheduler tick: reconcile drained blocks, admit what fits,
        advance at most one prefill chunk (chunked mode), dispatch the
        next decode block. Returns [(rid, token), ...] whose results
        ARRIVED this tick — with ``async_depth > 1`` a token is emitted
        the tick its block drains, one block behind its dispatch."""
        emitted, self._held_emitted = self._held_emitted, []
        with RecordEvent("serving::admit", queued=len(self._queue),
                         free_pages=len(self._free)):
            self._admit()
        if self.chunked_prefill:
            self._prefill_tick()
        dispatched = self._dispatch_block(emitted)
        if not dispatched and self._inflight:
            # nothing new to dispatch: force progress on the oldest block
            emitted.extend(self._reconcile_one())
        # bounded window: block on the oldest until at most depth-1 remain
        while len(self._inflight) > self.async_depth - 1:
            emitted.extend(self._reconcile_one())
        # opportunistic: drain blocks whose results already landed
        while self._inflight and self._block_ready(self._inflight[0]):
            emitted.extend(self._reconcile_one())
        if _REG.enabled:
            self._tick_gauges()
            # SLO sentry (ISSUE 10): drain boundary — the gauges above
            # are fresh. A default-constructed sentry evaluates EVERY
            # tick (a full registry snapshot); production installs on a
            # busy engine should set min_interval_s (README shows 1.0).
            # Uninstalled is a load + branch.
            _sentry.maybe_tick()
        return emitted

    def run(self) -> Dict[int, np.ndarray]:
        """Drive until all submitted requests complete; returns
        {rid: np.ndarray of generated tokens} for the requests finished by
        this call and RELEASES them (a long-lived engine must not retain
        every request it ever served)."""
        while self.has_work():
            self.step()
        # leftover speculative blocks are fully masked on device (every
        # participant already stopped); reconcile them so allocator and
        # position mirrors stay exact for the next run
        while self._inflight:
            self._reconcile_one()
        out = {rid: np.asarray(r.generated, np.int32)
               for rid, r in self._requests.items() if r.done}
        for rid in out:
            del self._requests[rid]
        if _REG.enabled:
            self.publish_metrics()
            # run() completion republished the percentile gauges — the
            # drain boundary an ITL/TTFT ceiling rule should see
            _sentry.maybe_tick()
        return out

    def stats(self) -> Dict[str, float]:
        """Gauges (``free_pages``, ``active``, ``queued``, ``inflight``,
        ``prefix_shared_pages``, the two ``*_bytes``, ``paged_layers``: 0
        says the model keeps no page, so ``free_pages`` 0 is no dry pool)
        and monotone lifetime counters: attribute reads only, so a caller
        may ask every step."""
        books = self._books
        out = {"free_pages": len(self._free),
               "paged_layers": self.paged_layers,
               "active": sum(s is not None for s in self._slots),
               "queued": len(self._queue),
               "preemptions": self.preemptions,
               "inflight": len(self._inflight),
               "attn_dense_ticks": self.attn_path_ticks["dense"],
               "attn_paged_ticks": self.attn_path_ticks["paged"],
               "kv_bytes_per_token": self.kv_bytes_per_token,
               "slot_state_bytes": self.slot_state_bytes,
               "prefill_width_tokens": self.prefill_width_tokens,
               "prefill_pad_tokens": self.prefill_pad_tokens,
               "first_tokens": self.first_tokens,
               "prefill_dispatch_s": self.prefill_dispatch_s,
               "first_token_wait_s": self.first_token_wait_s,
               "stream_s": books.stream_s,
               "stream_tick_s": books.stream_tick_s,
               "stream_ticks": books.stream_ticks,
               "stream_admit_s": books.stream_admit_s,
               "stream_unattributed_s": books.stream_unattributed_s,
               **self.tick_counts}
        if self.spec_k:
            out["spec_tokens_proposed"] = self.spec_tokens_proposed
            out["spec_tokens_accepted"] = self.spec_tokens_accepted
        if self._prefix is not None:
            out["prefix_hit_tokens"] = self.prefix_hit_tokens
            out["prefix_cow_copies"] = self.prefix_cow_copies
            out["prefix_shared_pages"] = self._prefix.num_pages
        return out

    def prefix_stats(self) -> Dict[str, float]:
        """Prefix-cache effectiveness over the engine's lifetime: hit
        tokens (prompt tokens served from shared pages instead of being
        re-prefilled), hit rate against all admitted prompt tokens,
        copy-on-write count and current tree size. Empty when
        ``prefix_cache=False``."""
        if self._prefix is None:
            return {}
        out = {"prefix_hit_tokens": float(self.prefix_hit_tokens),
               "prefix_prompt_tokens": float(self._prefix_prompt_tokens),
               "prefix_cow_copies": float(self.prefix_cow_copies),
               "prefix_shared_pages": float(self._prefix.num_pages),
               "prefix_nodes": float(self._prefix.num_nodes())}
        if self._prefix_prompt_tokens:
            out["prefix_hit_rate"] = (self.prefix_hit_tokens
                                      / self._prefix_prompt_tokens)
        return out

    def spec_stats(self) -> Dict[str, float]:
        """Speculation effectiveness over the engine's lifetime:
        acceptance rate (accepted ÷ proposed drafts) and mean committed
        tokens per committing drain (1.0 = no speculation win,
        spec_k+1 = every draft accepted). Empty when ``spec_k == 0``."""
        if not self.spec_k:
            return {}
        out = {"spec_k": float(self.spec_k),
               "spec_tokens_proposed": float(self.spec_tokens_proposed),
               "spec_tokens_accepted": float(self.spec_tokens_accepted)}
        if self.spec_tokens_proposed:
            out["spec_accept_rate"] = (self.spec_tokens_accepted
                                       / self.spec_tokens_proposed)
        if self._spec_drains:
            out["spec_mean_accepted_len"] = 1.0 + (
                self.spec_tokens_accepted / self._spec_drains)
        return out

    def take_finished(self) -> Dict[int, np.ndarray]:
        """Finished requests' full token streams (replay prefix
        included), RELEASING them — the incremental analogue of
        ``run()``'s collection for callers (a fabric replica) that drive
        ``step()`` themselves and must observe completions between
        ticks."""
        out = {rid: np.asarray(r.generated, np.int32)
               for rid, r in self._requests.items() if r.done}
        for rid in out:
            del self._requests[rid]
        return out

    def cancel(self, rid: int) -> bool:
        """Terminate ``rid`` NOW and free its slot/pages (the front
        door's slow-client / deadline / client-cancel path). A queued
        request is simply removed; an active one drains the in-flight
        blocks first (the preemption discipline — freed pages must not
        be re-claimed while a dispatched block still writes them), then
        the slot releases through the one ``_free_slot`` path with
        ``cache=True``: a cancelled conversation's completed pages are
        still future prefix hits. Returns True when the request existed
        and had not already finished (a finished request stays for
        ``take_finished`` — cancel does not eat a delivered result)."""
        req = self._requests.get(rid)
        if req is None or req.done:
            return False
        try:
            self._queue.remove(req)
        except ValueError:
            pass
        slot = next((i for i, s in enumerate(self._slots) if s is req),
                    -1)
        if slot >= 0:
            # tokens other slots commit in this drain are held for the
            # next step() to emit: a consumer that streams by emission
            # (the fabric) must see every token of a stream that goes on
            # exactly once; one that finished here ships whole with its
            # finish
            self._held_emitted.extend(
                (r, t) for r, t in self._drain_all()
                if r in self._requests and not self._requests[r].done)
            if not req.done and self._slots[slot] is req:
                self._deactivate(slot)
                self._free_slot(slot, cache=True)
        if req.done:
            return False
        self._held_emitted = [e for e in self._held_emitted if e[0] != rid]
        if req.tspans is not None:
            for k in ("queue", "res"):
                sp = req.tspans.pop(k, None)
                if sp is not None:
                    sp.tag(outcome="cancelled").end()
        self._requests.pop(rid, None)
        self._price_cache.pop(rid, None)
        return True

    # -- KV-page handoff (serving-fabric disaggregation, ISSUE 12) -----------

    def _refuse_latent_handoff(self) -> None:
        if self.slot_state_bytes:
            raise ValueError(
                f"KV-page handoff ({HANDOFF_FMT}) carries pages alone; "
                f"this engine's model keeps per-slot state beside them "
                f"(alloc_slot_state), which the format cannot say")
        if self.attention_kind != "gqa":
            raise ValueError(
                f"KV-page handoff ({HANDOFF_FMT}) carries K and V pages "
                f"per KV head; this engine's pool holds "
                f"{self.attention_kind!r} pages, which the format cannot "
                f"say")

    @staticmethod
    def _handoff_bucket(n: int) -> int:
        """Next power of two ≥ n: the gather/scatter executable count
        stays O(log max pages) instead of one per distinct page
        count."""
        b = 1
        while b < n:
            b *= 2
        return b

    def serialize_pages(self, tokens) -> Optional[Dict[str, object]]:
        """Export the KV pages the radix tree holds for the longest
        page-aligned prefix of ``tokens``: page contents (every layer's
        K and V, gathered in one jitted dispatch), the covered token
        run, and a sha256 over both — the prefill→decode handoff unit.
        Returns None when the tree covers no full page of ``tokens``.

        The payload is self-describing (``shape``/``dtype``/``sha256``)
        so :meth:`adopt_pages` can validate it END-TO-END before
        touching its own pool; the wire codec (base64 over TCP) lives in
        ``serving_fabric.transport``, this dict is the in-process
        form."""
        self._refuse_latent_handoff()
        if self._prefix is None:
            raise RuntimeError("serialize_pages needs prefix_cache=True "
                               "(the radix tree owns the exportable "
                               "pages)")
        toks = np.asarray(tokens, np.int32).reshape(-1)
        ids = self._prefix.match_page_ids(toks)
        if not ids:
            return None
        toks = toks[:len(ids) * self.page_size]
        if self._gather_fn is None:
            def gather_pages(pools, pids):
                kv = jnp.stack(
                    [jnp.stack([e[0][:, pids], e[1][:, pids]], axis=0)
                     for e in pools], axis=0)
                if self.kv_quant:        # [L, 2, n] per-page scales
                    sc = jnp.stack(
                        [jnp.stack([e[2][pids], e[3][pids]], axis=0)
                         for e in pools], axis=0)
                    return kv, sc
                return kv, None
            self._gather_fn = jax.jit(gather_pages)
        # page count padded to a power-of-two bucket (extra rows read
        # the garbage page, sliced off below): the jit retraces per
        # page-count SHAPE, and unbucketed counts would pay a fresh
        # compile per distinct prompt length on the serving path
        b = self._handoff_bucket(len(ids))
        with self._building("gather_pages", pages=b):
            kv, scales = self._gather_fn(
                self.pools,
                jnp.asarray(ids + [0] * (b - len(ids)), jnp.int32))
        kv = np.ascontiguousarray(np.asarray(kv)[:, :, :, :len(ids)])
        self.pages_exported += len(ids)
        payload = {"fmt": HANDOFF_FMT, "page_size": self.page_size,
                   "tokens": toks, "kv": kv, "dtype": str(kv.dtype),
                   "shape": list(kv.shape)}
        blob = toks.tobytes() + kv.tobytes()
        if scales is not None:
            sc = np.ascontiguousarray(
                np.asarray(scales, np.float32)[:, :, :len(ids)])
            payload["scales"] = sc
            payload["scales_shape"] = list(sc.shape)
            blob += sc.tobytes()
        payload["sha256"] = hashlib.sha256(blob).hexdigest()
        return payload

    def adopt_pages(self, payload) -> List[int]:
        """Adopt a :meth:`serialize_pages` payload into THIS engine's
        pool + radix tree: pages land in freshly allocated pool slots
        (under pressure the allocator's existing tree eviction makes
        room) and the token run is inserted at refcount 0 — cached, so
        the NEXT admission of a matching prompt prefix-hits, which is
        how a prefill→decode transfer seeds future sharing. Returns the
        page ids that became tree-owned ([] when the tree already
        covered the whole run).

        Validation is strictly first: a corrupt, truncated or
        mis-shaped payload raises ValueError before anything mutates."""
        self._refuse_latent_handoff()
        if self._prefix is None:
            raise RuntimeError("adopt_pages needs prefix_cache=True")
        fmt = payload.get("fmt") if isinstance(payload, dict) else None
        if fmt not in (HANDOFF_FMT, HANDOFF_FMT_V1):
            raise ValueError("handoff payload: unknown format")
        if fmt == HANDOFF_FMT_V1 and self.kv_quant:
            # a v1 emitter has float pages and no scales — nothing to
            # dequant by; the fabric treats this like any failed handoff
            # and falls back to a cold prefill
            raise ValueError("handoff payload: v1 (scale-less) payload "
                             "cannot seed an int8 KV pool")
        if int(payload.get("page_size", -1)) != self.page_size:
            raise ValueError(
                f"handoff payload: page_size {payload.get('page_size')} "
                f"!= engine page_size {self.page_size}")
        toks = np.asarray(payload.get("tokens"), np.int32).reshape(-1)
        kv = payload.get("kv")
        ps = self.page_size
        if len(toks) == 0 or len(toks) % ps:
            raise ValueError("handoff payload: token run is not a "
                             "whole-page multiple")
        n = len(toks) // ps
        kp0 = self.pools[0][0]
        want = (len(self.pools), 2, kp0.shape[0], n, ps, kp0.shape[3])
        if not isinstance(kv, np.ndarray) or kv.shape != want \
                or list(kv.shape) != list(payload.get("shape", [])):
            raise ValueError(
                f"handoff payload: kv shape "
                f"{getattr(kv, 'shape', None)} != expected {want}")
        if str(kv.dtype) != payload.get("dtype") \
                or str(kv.dtype) != str(kp0.dtype):
            raise ValueError(
                f"handoff payload: dtype {payload.get('dtype')} != pool "
                f"dtype {kp0.dtype}")
        scales = payload.get("scales")
        blob = toks.tobytes() + kv.tobytes()
        if self.kv_quant:
            sc_want = (len(self.pools), 2, n)
            if not isinstance(scales, np.ndarray) \
                    or scales.shape != sc_want \
                    or str(scales.dtype) != "float32" \
                    or list(scales.shape) != list(
                        payload.get("scales_shape", [])):
                raise ValueError(
                    f"handoff payload: scales shape "
                    f"{getattr(scales, 'shape', None)} != expected "
                    f"{sc_want} (int8 pool needs per-page fp32 scales)")
            blob += scales.tobytes()
        elif scales is not None:
            raise ValueError("handoff payload: scales present but this "
                             "engine's KV pool is not quantized")
        digest = hashlib.sha256(blob).hexdigest()
        if digest != payload.get("sha256"):
            raise ValueError("handoff payload: checksum mismatch "
                             "(corrupt or truncated transfer)")
        # -- validated; now (and only now) touch the pool. Only the
        # UNCOVERED whole-page suffix is staged: pages the tree already
        # serves would be scattered and immediately freed — and worse,
        # allocating them under pressure could evict the very cached
        # prefixes the transfer exists to seed.
        k = min(self._prefix.match(toks, touch=False) // ps, n)
        if k >= n:
            return []                   # tree already covers the run
        pages = self._alloc_pages(n - k, protect=toks)
        if pages is None:
            raise RuntimeError(
                f"adopt_pages: pool cannot hold {n - k} more pages "
                f"even after tree eviction; raise num_pages")
        if self._scatter_fn is None:
            def scatter_pages(pools, pids, data, sc):
                out = []
                for i, e in enumerate(pools):
                    ne = (e[0].at[:, pids].set(data[i, 0]),
                          e[1].at[:, pids].set(data[i, 1]))
                    if sc is not None:
                        ne += (e[2].at[pids].set(sc[i, 0]),
                               e[3].at[pids].set(sc[i, 1]))
                    out.append(ne)
                return out
            self._scatter_fn = jax.jit(scatter_pages, donate_argnums=(0,))
        # same power-of-two bucketing as the gather: padded rows write
        # the garbage page (reserved junk — the designated sink)
        b = self._handoff_bucket(n - k)
        kv_pad = np.zeros(kv.shape[:3] + (b,) + kv.shape[4:], kv.dtype)
        kv_pad[:, :, :, :n - k] = kv[:, :, :, k:]
        sc_pad = None
        if self.kv_quant:
            sc_pad = np.zeros(scales.shape[:2] + (b,), np.float32)
            sc_pad[:, :, :n - k] = scales[:, :, k:]
            sc_pad = jnp.asarray(sc_pad)
        with self._building("scatter_pages", pages=b):
            self.pools = self._scatter_fn(
                self.pools,
                jnp.asarray(list(pages) + [0] * (b - (n - k)), jnp.int32),
                jnp.asarray(kv_pad), sc_pad)
        # insert walks the FULL run; the covered prefix needs page-id
        # placeholders that are never read (insert only consumes ids
        # from the first uncovered boundary on — and a coverage that
        # ends mid-page donates nothing at all, freeing the stage)
        donated = self._prefix.insert(toks, [0] * k + pages, lock=None)
        assert all(p in set(pages) for p in donated), \
            "placeholder page id donated to the tree"
        taken = set(donated)
        self._free.extend(p for p in pages if p not in taken)
        self.pages_adopted += len(donated)
        return donated

    # -- program builds ------------------------------------------------------

    def _building(self, program: str, **shape):
        """Context for a CALL of ``program``: the first one per shape
        variant is its build (trace + lower + compile or cache read), so it
        runs as a ``compile::<program>`` span and leaves a ``build_log``
        row; every later one is a set lookup. A prefill program of a routed
        model also says how its experts run at that many rows
        (``core.expert_path``: ``expert_path``, and ``expert_step_rows``
        where the path is the loop over an expert's rows); a prefill
        program and the tick of a model with state-space layers say which
        form their recurrence took (``core.state_path``: ``state_path``,
        "kernel" or "xla")."""
        key = (program, *shape.values())
        if key in self._built:
            return _NULL
        self._built.add(key)
        rows = shape.get("bucket", shape.get("width"))   # a prefill's
        path, step = (rows and self.core.expert_path(rows)) or (None, None)
        said = {"expert_path": path, "expert_step_rows": step}
        if program in ("prefill_paged", "run"):
            said["state_path"] = self.core.state_path(rows, self.max_batch)
            said["pages"] = None if self.paged_layers else "none"
        return compile_cache.building(
            program, self.build_log, **shape,
            **{k: v for k, v in said.items() if v})

    # -- metrics plane -------------------------------------------------------

    def _tick_gauges(self) -> None:
        """Per-tick point-in-time view (cheap: five cached-handle gauge
        sets, and only ever reached when the registry is enabled)."""
        lb = self._mlabels
        self._g_queue.set(len(self._queue), **lb)
        self._g_inflight.set(len(self._inflight), **lb)
        self._g_active.set(sum(s is not None for s in self._slots), **lb)
        self._g_free.set(len(self._free), **lb)
        self._g_occupancy.set(
            1.0 - len(self._free) / self._total_pages
            if self._total_pages else 0.0, **lb)
        if self._prefix is not None:
            self._g_prefix_pages.set(self._prefix.num_pages, **lb)

    def _decode_args(self, spec_mode: bool) -> tuple:
        """The decode tick's argument tuple — ONE definition shared by
        the dispatch call and the cost observatory's eager lower, so a
        signature change can't leave the two silently diverged."""
        args = (self._params, self.pools, self._tables_dev,
                self._base_key, self._state, self._knobs)
        return args + ((self._hist,) if spec_mode else (self.slot_state,))

    def _maybe_compile_with_costs(self, jfn, spec_mode: bool):
        """Resolve a freshly built decode tick for dispatch. With the
        metrics plane OFF this returns the jitted fn untouched (it
        compiles lazily at first call, exactly the old behavior). With
        the plane ON it pays the same one trace+compile EAGERLY —
        ``lower().compile()`` on the concrete args of this dispatch — so
        the cost observatory can attribute flops/bytes from the
        optimized HLO of the executable that will actually run. A
        program the compiler refuses fails here, as it would at first
        call."""
        if not _REG.enabled:
            return jfn
        compiled = jfn.lower(*self._decode_args(spec_mode)).compile()
        try:
            from ..observability.costs import CostWatch
            if self._cost_watch is None:
                self._cost_watch = CostWatch("serving")
            self._cost_watch.observe_executable(compiled)
        except Exception:
            pass
        return compiled

    def _publish_cost_metrics(self) -> None:
        """Breakdown/MFU gauges for the serving tick: the stream books'
        recent clean tick (the device's seconds a tick with no admission
        work in front of it and no idle gap in it), attributed against
        the analyzed block executable of ``decode_block`` (or
        ``spec_k + 1``) ticks."""
        watch = self._cost_watch
        tick = self._books.recent_tick_s()
        if watch is None or not watch.attached or tick is None:
            return
        watch.publish(tick, steps_per_exec=(self.spec_k + 1 if self.spec_k
                                            else self.decode_block))

    def publish_metrics(self) -> Dict[str, float]:
        """Mirror the engine's telemetry into the process metrics registry
        — the counters/percentiles ``stats()``/``latency_stats()`` used to
        be the only window onto. Lifetime counters publish as DELTAS since
        the previous publish, so registry counters stay monotonic across
        repeated calls; called automatically at ``run()`` completion and
        safe to call any time. Returns ``latency_stats()`` for
        convenience."""
        lat = self.latency_stats()
        if not _REG.enabled:
            return lat
        lb = self._mlabels
        for name, val, help in (
                ("pt_serving_preemptions_total", self.preemptions,
                 "recompute-policy slot evictions"),
                ("pt_serving_pool_dry_drains_total", self.pool_dry_drains,
                 "dry pools answered by draining the in-flight window"),
                ("pt_serving_tokens_total", self._tokens_emitted,
                 "tokens emitted to clients"),
                ("pt_serving_requests_total", self._requests_retired,
                 "requests retired"),
                ("pt_spec_tokens_proposed_total",
                 self.spec_tokens_proposed,
                 "draft tokens scored by speculative verify passes"),
                ("pt_spec_tokens_accepted_total",
                 self.spec_tokens_accepted,
                 "draft tokens committed by speculative verify passes"),
                ("pt_serving_prefix_hit_tokens_total",
                 self.prefix_hit_tokens,
                 "prompt tokens served from shared prefix pages"),
                ("pt_serving_cow_copies_total", self.prefix_cow_copies,
                 "shared pages copy-on-written at divergence"),
                ("pt_serving_kv_quant_ticks_total", self.kv_quant_ticks,
                 "decode/verify ticks served from an int8 KV pool")):
            prev = self._published.get(name, 0)
            if val > prev:
                _REG.counter(name, help).inc(val - prev, **lb)
            self._published[name] = val
        sp = self.spec_stats()
        if "spec_accept_rate" in sp:
            _REG.gauge("pt_spec_accept_rate",
                       "accepted / proposed speculative drafts").set(
                sp["spec_accept_rate"], **lb)
        if "spec_mean_accepted_len" in sp:
            _REG.gauge("pt_spec_mean_accepted_len",
                       "mean committed tokens per speculative drain").set(
                sp["spec_mean_accepted_len"], **lb)
        if self._prefix is not None and self._prefix_prompt_tokens:
            self._g_prefix_hit.set(self.prefix_hit_tokens
                                   / self._prefix_prompt_tokens, **lb)
        _REG.gauge("pt_serving_kv_quant_enabled",
                   "1 when the KV page pool is int8 with per-page "
                   "scales").set(float(self.kv_quant), **lb)
        if self.kv_quant:
            _REG.gauge("pt_serving_kv_quant_pool_bytes",
                       "HBM bytes held by the int8 KV pool incl. scale "
                       "arrays", "By").set(float(sum(
                           a.size * a.dtype.itemsize
                           for e in self.pools for a in e)), **lb)
        for key, metric in (("ttft", "pt_serving_ttft_seconds"),
                            ("latency", "pt_serving_latency_seconds"),
                            ("itl", "pt_serving_itl_seconds")):
            for q in ("p50", "p99"):
                v = lat.get(f"{key}_{q}_s")
                g = _REG.gauge(metric, f"{key} percentile over the "
                                       f"retired-request window", "s")
                if v is not None:
                    g.set(v, q=q, **lb)
                else:
                    # empty/reset window: CLEAR rather than leave the
                    # previous publish reading as current — an absent
                    # percentile is honest (and what the sentry's
                    # Staleness rule exists to notice), a stale one lies
                    g.clear(q=q, **lb)
        _REG.gauge("pt_serving_window_requests",
                   "retired requests in the latency window").set(
            lat.get("requests", 0), **lb)
        self._publish_cost_metrics()
        self._tick_gauges()
        return lat

    # -- page allocator -----------------------------------------------------

    def _alloc_pages(self, n: int,
                     protect=None) -> Optional[List[int]]:
        """Pop ``n`` pages; under pressure, refcount-0 prefix-tree pages
        are LRU-evicted back into the free list first (``protect`` pins
        the match path of the request being admitted so admission can't
        evict the prefix it is about to map). Only once the tree has
        nothing evictable does the caller see None — the dry-pool
        drain/preemption machinery downstream is unchanged."""
        if len(self._free) < n and self._prefix is not None:
            self._free.extend(
                self._prefix.evict(n - len(self._free), protect))
        if len(self._free) < n:
            return None
        return [self._free.pop() for _ in range(n)]

    def _free_slot(self, slot: int, cache: bool = False):
        req = self._slots[slot]
        # free every held page (page 0 == unset): counting from pos would
        # leak a boundary page granted earlier in the same scheduling pass
        if self._prefix is not None:
            if cache and req is not None and self._decode_ready(req):
                # donate completed full pages before the lock releases:
                # retirement caches the whole conversation, preemption
                # caches the replay's own prefix (the re-prefill hits)
                self._insert_prefix(slot, req)
            lock = self._tree_locks[slot]
            if lock is not None:
                # released exactly ONCE, whether the slot retired,
                # was preempted mid-decode, or was evicted mid-prefill
                # before ever activating — a mid-prefill slot's table
                # holds admission-claimed private pages PLUS the mapped
                # shared prefix, and only the former go back to the
                # free list (the tree still owns the latter)
                self._prefix.release(lock)
                self._tree_locks[slot] = None
            self._free.extend(int(p) for p in self.tables[slot]
                              if p != 0 and not self._prefix.owns(int(p)))
        else:
            self._free.extend(int(p) for p in self.tables[slot] if p != 0)
        self.tables[slot] = 0
        self._tables_dirty = True
        self.pos[slot] = 0
        self._proj_pos[slot] = 0
        self._proj_gen[slot] = 0
        self._slots[slot] = None
        if req is not None:
            req.slot = -1
            req.prefilled = 0     # freed pages took the written KV along
            if req.tspans is not None:
                rsp = req.tspans.pop("res", None)
                if rsp is not None:
                    rsp.tag(reason="done" if req.done else "preempt",
                            n=len(req.generated)).end()

    # -- device-resident scheduler state ------------------------------------

    def _init_state(self, logits_row):
        B = self.max_batch
        vocab = logits_row.shape[-1]
        self._state = (jnp.zeros((B, vocab), logits_row.dtype),
                       jnp.zeros((B,), jnp.int32),
                       jnp.zeros((B,), bool),
                       jnp.zeros((B,), jnp.int32),
                       jnp.zeros((B,), jnp.int32))
        self._knobs = {"rseed": jnp.zeros((B,), jnp.uint32),
                       "eos": jnp.full((B,), -1, jnp.int32),
                       "temp": jnp.ones((B,), jnp.float32),
                       "topk": jnp.zeros((B,), jnp.int32),
                       "topp": jnp.ones((B,), jnp.float32),
                       "dosample": jnp.zeros((B,), bool)}

    def _build_act_fn(self):
        def activate_slot(state, knobs, slot, logits_row, pos0, budget0,
                          gen0, rseed0, eos0, temp0, topk0, topp0, dos0):
            logits, pos, active, budget, gen = state
            state = (logits.at[slot].set(logits_row.astype(logits.dtype)),
                     pos.at[slot].set(pos0),
                     active.at[slot].set(True),
                     budget.at[slot].set(budget0),
                     gen.at[slot].set(gen0))
            knobs = {"rseed": knobs["rseed"].at[slot].set(rseed0),
                     "eos": knobs["eos"].at[slot].set(eos0),
                     "temp": knobs["temp"].at[slot].set(temp0),
                     "topk": knobs["topk"].at[slot].set(topk0),
                     "topp": knobs["topp"].at[slot].set(topp0),
                     "dosample": knobs["dosample"].at[slot].set(dos0)}
            return state, knobs

        # no donation: in-flight blocks hold references to prior state
        # arrays for their async host drains
        return jax.jit(activate_slot)

    def _activate(self, slot: int, req: _Request, logits_row):
        """Flip a slot live on device after its prefill finished: one
        small jitted dispatch setting the slot's row in every scheduler
        array (pos/active/budget/gen/knobs) + its first-token logits."""
        with RecordEvent("serving::activate", rid=req.rid, slot=slot):
            self._activate_slot(slot, req, logits_row)

    def _activate_slot(self, slot: int, req: _Request, logits_row):
        if self._state is None:
            self._init_state(logits_row)
        if self._act_fn is None:
            self._act_fn = self._build_act_fn()
        L = req.prefill_target
        eos = req.eos_token_id if req.eos_token_id is not None \
            else self.cfg.eos_token_id
        with self._building("activate_slot"):
            self._state, self._knobs = self._act_fn(
                self._state, self._knobs, np.int32(slot), logits_row,
                np.int32(L),
                np.int32(req.max_new_tokens - len(req.generated)),
                np.int32(len(req.generated)),
                np.uint32((req.rid if req.rseed is None else req.rseed)
                          & 0x7FFFFFFF),
                np.int32(-1 if eos is None else eos),
                np.float32(req.temperature), np.int32(req.top_k),
                np.float32(req.top_p), np.bool_(req.do_sample))
        self.pos[slot] = L
        self._proj_pos[slot] = L
        self._proj_gen[slot] = len(req.generated)
        if req.tspans is not None:
            # decode-epoch anchor: the first replica::decode span for
            # this residency starts where activation finished
            req.tspans["last"] = time.time()
        self._dosample[slot] = req.do_sample
        if self.spec_k:
            # device-resident token history for the draft proposer:
            # prompt + replayed generations now, committed tokens appended
            # on device by each spec tick (the host is async_depth behind,
            # so drafting must read the carry, not host state)
            if self._hist_set_fn is None:
                def hist_set(h, slot, row):
                    return h.at[slot].set(row)
                self._hist_set_fn = jax.jit(hist_set, donate_argnums=(0,))
            row = np.zeros((self.max_len,), np.int32)
            row[:len(req.prompt)] = req.prompt
            if req.generated:
                row[len(req.prompt):L] = req.generated
            with self._building("hist_set"):
                self._hist = self._hist_set_fn(self._hist, np.int32(slot),
                                               row)

    def _deactivate(self, slot: int):
        if self._state is None:
            return
        if self._deact_fn is None:
            def deactivate(active, slot):
                return active.at[slot].set(False)
            self._deact_fn = jax.jit(deactivate)
        logits, pos, active, budget, gen = self._state
        with self._building("deactivate"):
            active = self._deact_fn(active, np.int32(slot))
        self._state = (logits, pos, active, budget, gen)

    # -- admission / prefill ------------------------------------------------

    def _bucket(self, L: int) -> int:
        """The width a prompt of ``L`` tokens is padded to: whole steps of
        ``_bucket_step`` tokens, within the table's span."""
        step = self._bucket_step
        return min(-(-L // step) * step, self.pages_per_seq * self.page_size)

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_cache.get(bucket)
        if fn is not None:
            return fn
        core, bind, head = self.core, self._bind, self._head

        def prefill_paged(params, ids, pools, tables1, last_idx, slot_state,
                          slot):
            with bind(params):
                hidden, pools, slot_state = core.prefill_paged(
                    ids, pools, tables1, slot_state, slot, last_idx)
                logits = head(hidden[0, last_idx, :])
            return logits, pools, slot_state

        fn = _named_jit(prefill_paged, f"prefill_paged_{bucket}",
                        donate_argnums=(2, 5))
        self._prefill_cache[bucket] = fn
        return fn

    @staticmethod
    def _req_tokens(req: _Request) -> np.ndarray:
        """The request's replay token sequence: prompt + anything
        generated before a preemption — the ONE definition the prefix
        match, donation, admission-pricing and chunk-prefill paths all
        key on."""
        return np.concatenate([req.prompt,
                               np.asarray(req.generated, np.int32)])

    def _uncached_tokens(self, req: _Request) -> int:
        """Predicted prefill cost of admitting ``req`` now: the tokens
        its admission would actually recompute (1 for a full-prompt hit
        — just the logits re-forward). The admission policy prices
        admits with this. Prices are cached per (rid, replay length)
        against the tree's mutation epoch, so a deep deferred queue
        costs one tree walk per request per tree CHANGE, not per tick."""
        L = len(req.prompt) + len(req.generated)
        if self._prefix is None:
            return L
        key = (self._prefix.epoch, L)
        hit = self._price_cache.get(req.rid)
        if hit is not None and hit[0] == key:
            return hit[1]
        # touch=False: a pricing read must not bump the match path's LRU
        # rank — a request deferred every tick would otherwise keep its
        # prefix artificially hot and starve eviction of real traffic
        m = self._prefix.match(self._req_tokens(req), touch=False)
        if m >= L:
            price = 1
        else:
            price = L - (min(m, L - 1) // self.page_size) * self.page_size
        if len(self._price_cache) > 4 * self.max_batch + 1024:
            self._price_cache.clear()          # bound stale-rid growth
        self._price_cache[req.rid] = (key, price)
        return price

    def _insert_prefix(self, slot: int, req: _Request) -> None:
        """Donate the slot's completed full pages (prompt + committed
        generations) to the radix tree. Ranges the tree already covers
        stay the slot's private duplicates; new nodes join the slot's
        lock at ref 1 so the uniform release path owns them."""
        toks = self._req_tokens(req)
        n_ins = len(toks) // self.page_size
        if n_ins == 0:
            return
        lock = self._tree_locks[slot]
        if lock is None:
            lock = self._tree_locks[slot] = self._prefix.new_lock()
        self._prefix.insert(toks[:n_ins * self.page_size],
                            [int(p) for p in self.tables[slot, :n_ins]],
                            lock)

    def _cow_page(self, src: int, dst: int) -> None:
        """Copy page ``src`` → ``dst`` across every layer's K/V pool (one
        jitted dispatch, page ids traced): the COW primitive for decode
        diverging into a shared page."""
        if self._cow_fn is None:
            def cow_page(pools, src, dst):
                return [_entry_page_copy(e, src, dst) for e in pools]
            self._cow_fn = jax.jit(cow_page, donate_argnums=(0,))
        self._books.admitted(self._stream_busy())
        with self._building("cow_page"):
            self.pools = self._cow_fn(self.pools, jnp.int32(src),
                                      jnp.int32(dst))
        self.prefix_cow_copies += 1

    def _tail_logits_fn(self):
        """The full-prompt-hit fast path's entire compute, ONE dispatch:
        copy-on-write the shared boundary page (``src`` → ``dst``, every
        layer), then re-forward the single last prompt token — its K/V
        write lands in the private copy and the returned logits row is
        what a full prefill would have produced."""
        if self._tail_fn is None:
            core, bind, head = self.core, self._bind, self._head

            def tail_logits(params, tok, pos, pools, tables1, src, dst):
                pools = [_entry_page_copy(e, src, dst) for e in pools]
                with bind(params):
                    h, pools = core.decode_verify_paged(tok, pos, pools,
                                                        tables1)
                    logits = head(h[0, 0, :])
                return logits, pools

            self._tail_fn = jax.jit(tail_logits, donate_argnums=(3,))
        return self._tail_fn

    def _admit(self):
        lat, prices, q_snap = None, {}, None
        while self._queue:
            slot = next((i for i, s in enumerate(self._slots) if s is None),
                        None)
            if slot is None:
                return
            if self._admission is not None:
                if lat is None:
                    lat = self.latency_stats()

                # price each queued request at most once per _admit call
                # (select() re-runs per admitted slot; without the memo a
                # deep queue costs admits x queue tree walks per tick).
                # Prices can go stale within the call — an earlier
                # admit's insertion may raise a later request's hit —
                # which only costs ordering accuracy, never correctness.
                def _price(r):
                    v = prices.get(r.rid)
                    if v is None:
                        v = prices[r.rid] = self._uncached_tokens(r)
                    return v
                q_snap = list(self._queue)
                qi = self._admission.select(q_snap, _price, lat)
                if qi is None:
                    return                   # SLO defer: none this tick
                req = q_snap[qi]
            else:
                qi, req = 0, self._queue[0]
            L = len(req.prompt) + len(req.generated)
            need = (-(-self._bucket(L) // self.page_size)
                    if self.paged_layers else 0)
            toks = self._req_tokens(req)
            # prefix sharing: map every FULLY matched page; a full-prompt
            # match keeps the boundary page shared too and COWs it (the
            # last token is re-forwarded for its logits), otherwise the
            # page holding the first unmatched token is recomputed by the
            # suffix prefill. n_lock*page_size is always < L, so decode
            # positions land strictly beyond the shared region.
            n_lock, fast, m = 0, False, 0
            if self._prefix is not None:
                m = self._prefix.match(toks)
                fast = m >= L
                n_lock = (L - 1) // self.page_size if m >= L \
                    else m // self.page_size
            pages = self._alloc_pages(need - n_lock,
                                      protect=toks if m else None)
            if pages is None:
                if any(s is not None for s in self._slots):
                    return                   # wait for pages to free up
                if m:
                    # nothing running, and the free pool + evictable
                    # tree can't cover the private remainder because
                    # the protected match path holds the pages: admit
                    # COLD instead (evict everything, full prefill)
                    n_lock, fast = 0, False
                    pages = self._alloc_pages(need)
                if pages is None:
                    # nothing running that could ever free pages: a
                    # replay grew past the pool (the submit-time check
                    # covers only the original prompt)
                    raise RuntimeError(
                        f"request {req.rid} needs {need} pages but the "
                        f"pool holds {self._total_pages}; raise num_pages")
            if self._admission is not None:
                # pages really claimed: NOW the passed-over requests are
                # charged a starvation skip (a pool-blocked tick above
                # returned without charging anyone)
                self._admission.note_admitted(q_snap, qi)
                del self._queue[qi]
            else:
                self._queue.popleft()
            if self._prefix is not None:
                lock = (self._prefix.lock_prefix(toks, n_lock) if n_lock
                        else self._prefix.new_lock())
                self._tree_locks[slot] = lock
                self.tables[slot, :n_lock] = lock.pages()
                self._prefix_prompt_tokens += L
                self.prefix_hit_tokens += (L - 1) if fast \
                    else n_lock * self.page_size
            self.tables[slot, n_lock:n_lock + len(pages)] = pages
            self._tables_dirty = True
            self._slots[slot] = req
            req.slot = slot
            if not req.admit_t:
                req.admit_t = time.perf_counter()
            if req.tspans is not None:
                ts = req.tspans
                q = ts.pop("queue", None)
                if q is not None:     # absent on preemption re-admits
                    q.tag(outcome="admitted", slot=slot).end()
                rsp = ts["tr"].start("replica::resident",
                                     parent=ts["parent"],
                                     tags={"slot": slot})
                if rsp is not None:
                    ts["res"] = rsp
                ts["last"] = time.time()
            self._dosample[slot] = req.do_sample
            req.prefill_target = L
            if fast:
                # COW the shared page holding token L-1 (positions >= L-1
                # in the copy are ours to overwrite; positions < L-1 in
                # it matched, so their KV is exactly what we'd compute),
                # then re-forward ONLY that token for the logits row.
                src = self._prefix.page_at(toks, n_lock)
                assert src is not None, "matched tail page vanished"
                self.prefix_cow_copies += 1
                psp = self._prefill_span(req, "cow")
                with self._prefill_event(req, slot, 1, "cow", 1), \
                        self._building("tail_logits"):
                    logits, self.pools = self._tail_logits_fn()(
                        self._params,
                        jnp.asarray(toks[L - 1:L].reshape(1, 1)),
                        jnp.full((1,), L - 1, jnp.int32), self.pools,
                        jnp.asarray(self.tables[slot:slot + 1]),
                        jnp.int32(src), jnp.int32(pages[0]))
                req.prefill_dispatched_t = (req.prefill_dispatched_t
                                            or time.perf_counter())
                if psp is not None:
                    psp.end()
                req.prefilled = L
                self._activate(slot, req, logits)
                self._insert_prefix(slot, req)
                continue
            if self.chunked_prefill:
                # pages claimed now; KV written one chunk per tick,
                # starting AFTER the shared prefix (page-aligned offset)
                req.prefilled = n_lock * self.page_size
                self.pos[slot] = 0
                self._proj_pos[slot] = 0
                self._proj_gen[slot] = 0
                continue
            bucket = self._bucket(L)
            off = n_lock * self.page_size
            req.prefilled = L
            psp = self._prefill_span(req, "suffix" if off else "full")
            with self._prefill_event(req, slot, bucket - off,
                                     "suffix" if off else "full", L - off):
                if off:
                    # suffix-only prefill from the page-aligned offset:
                    # the existing chunked-prefill extend attends over
                    # the mapped shared history plus itself. The ids
                    # width (bucket - off) is a page multiple, so the
                    # executable set this jit retraces over is bounded
                    # by pages_per_seq — the same bound the per-bucket
                    # cold-prefill cache already lives with.
                    ids = np.zeros((1, bucket - off), np.int32)
                    ids[0, :L - off] = toks[off:]
                    with self._building("prefill_chunk",
                                        width=bucket - off):
                        logits, self.pools = self._chunk_fn(bucket - off)(
                            self._params, jnp.asarray(ids), jnp.int32(off),
                            self.pools,
                            jnp.asarray(self.tables[slot:slot + 1]),
                            jnp.int32(L - 1))
                else:
                    ids = np.zeros((1, bucket), np.int32)
                    ids[0, :L] = toks
                    with self._building("prefill_paged", bucket=bucket):
                        logits, self.pools, self.slot_state = \
                            self._prefill_fn(bucket)(
                                self._params, jnp.asarray(ids), self.pools,
                                jnp.asarray(self.tables[slot:slot + 1]),
                                jnp.int32(L - 1), self.slot_state,
                                np.int32(slot))
            req.prefill_dispatched_t = (req.prefill_dispatched_t
                                        or time.perf_counter())
            if psp is not None:
                psp.end()
            self._activate(slot, req, logits)
            if self._prefix is not None:
                self._insert_prefix(slot, req)

    @staticmethod
    def _prefill_span(req: _Request, kind: str):
        """Open a replica::prefill span under ``req``'s resident span
        (None when untraced — callers guard the matching end)."""
        ts = req.tspans
        if ts is None:
            return None
        parent = ts.get("res") or ts["parent"]
        return ts["tr"].start("replica::prefill", parent=parent,
                              tags={"kind": kind})

    def _prefill_event(self, req: _Request, slot: int, bucket: int,
                       kind: str, tokens: int):
        """The ``serving::prefill`` span of one prefill program for
        ``req`` (``bucket``: the width of ids it takes; ``kind``: full,
        suffix, cow or chunk; ``attn``: the model's attention kind),
        stamping the start of the request's first. ``tokens``: what of
        the width the call really forwards. The one place every prefill
        program is announced, so the one place its work is counted."""
        if not req.prefill_start_t:
            req.prefill_start_t = time.perf_counter()
        self.prefill_width_tokens += bucket
        self.prefill_pad_tokens += bucket - tokens
        self._books.admitted(self._stream_busy())
        return RecordEvent("serving::prefill", rid=req.rid, slot=slot,
                           bucket=bucket, kind=kind,
                           attn=self.attention_kind)

    def _decode_ready(self, req) -> bool:
        return req is not None and req.prefilled >= req.prefill_target

    def _chunk_fn(self, width: int):
        """The prefill-extend program for ``width`` ids (a page multiple:
        the chunk in chunked mode, bucket minus shared prefix for a suffix
        prefill), one jit per width so each has its own name."""
        fn = self._chunk_fns.get(width)
        if fn is not None:
            return fn
        core, bind, head = self.core, self._bind, self._head

        def prefill_chunk(params, ids, offset, pools, tables1, last_idx):
            with bind(params):
                hidden, pools = core.prefill_chunk_paged(
                    ids, offset, pools, tables1)
                # logits at the prompt's true last index — meaningful on
                # the FINAL chunk only (a single-row head matmul, cheap
                # to compute unconditionally)
                logits = head(hidden[0, last_idx - offset, :])
            return logits, pools

        fn = self._chunk_fns[width] = _named_jit(
            prefill_chunk, f"prefill_chunk_{width}", donate_argnums=(3,))
        return fn

    def _prefill_tick(self):
        """Advance the oldest in-prefill slot by ONE chunk."""
        cand = [(self._slots[s].rid, s) for s in range(self.max_batch)
                if self._slots[s] is not None
                and not self._decode_ready(self._slots[s])]
        if not cand:
            return
        slot = min(cand)[1]
        req = self._slots[slot]
        C = self.prefill_chunk
        off = req.prefilled
        toks = self._req_tokens(req)
        ids = np.zeros((1, C), np.int32)
        chunk = toks[off:off + C]
        ids[0, :len(chunk)] = chunk
        last_idx = req.prefill_target - 1
        psp = self._prefill_span(req, "chunk")
        with self._prefill_event(req, slot, C, "chunk", len(chunk)), \
                self._building("prefill_chunk", width=C):
            logits, self.pools = self._chunk_fn(C)(
                self._params, jnp.asarray(ids), jnp.int32(off), self.pools,
                jnp.asarray(self.tables[slot:slot + 1]),
                jnp.int32(min(last_idx, off + C - 1)))
        if psp is not None:
            psp.tag(off=off).end()
        req.prefilled = min(off + C, self._bucket(req.prefill_target))
        if req.prefilled >= req.prefill_target:
            req.prefill_dispatched_t = (req.prefill_dispatched_t
                                        or time.perf_counter())
            self._activate(slot, req, logits)
            if self._prefix is not None:
                self._insert_prefix(slot, req)

    # -- decode -------------------------------------------------------------

    def _build_decode(self, K: int, any_sample: bool, attn_impl: str):
        """K sample+decode steps chained in one compiled lax.scan: one
        dispatch + one async [K, B] token readback per scheduler tick.
        The scan body samples with per-slot knob arrays, then runs the
        ON-DEVICE stop update: a slot that emits its eos or exhausts its
        budget deactivates for the REST of the scan (and for any
        speculatively dispatched later block — the carry's active mask is
        the block-to-block state), its tokens masked to pad and its K/V
        routed to the garbage page via the per-step table mask.
        ``any_sample=False`` compiles the argmax-only body (no full-vocab
        sorts in the scan) — the all-greedy common case keeps its old
        cost; the flag is host state, so at most two executables per K.
        ``attn_impl`` ('dense'|'paged') is baked in at TRACE time via
        force_decode_impl — the context-aware dispatch choice."""
        core, bind, head = self.core, self._bind, self._head
        from ..ops.pallas.paged_attention import force_decode_impl
        n_counts = len(self._tick_counters)

        # ``run`` is the decode tick's name in the device trace, and the
        # only program of the engine with that name: the benchmark's
        # decode_tick_roofline finds the tick as ``^jit_run\(``
        def run(params, pools, tables, base_key, state, knobs, slot_state):
            with bind(params), force_decode_impl(attn_impl):
                def body(carry, _):
                    logits, pos, active, budget, gen = carry[0]
                    pools, slots = carry[1:]
                    lf = logits.astype(jnp.float32)
                    if any_sample:
                        # key = f(seed, request, token index): sampled
                        # streams are schedule- and replay-independent
                        keys = fold_sampling_keys(base_key,
                                                  knobs["rseed"], gen)
                        tok = sample_logits_per_slot(
                            lf, knobs["temp"], knobs["topk"],
                            knobs["topp"], knobs["dosample"], keys)
                    else:
                        tok = jnp.argmax(lf, axis=-1)
                    tok = jnp.where(active, tok, 0).astype(jnp.int32)
                    # inactive rows masked to the garbage page: mid-prefill
                    # slots HOLD real pages, stopped slots' speculative
                    # writes must be unreachable — one mask serves both
                    tbl = tables * active[:, None].astype(tables.dtype)
                    # the rows' next state (empty for a core with none)
                    # and the tick's counters (None where none is declared)
                    h, pools, slots, counts = core.decode_step_paged(
                        tok, pos, pools, tbl, slots)
                    new_logits = head(h[:, 0, :])
                    new_active, budget = decode_stop_update(
                        tok, active, budget, knobs["eos"])
                    adv = active.astype(jnp.int32)
                    new_state = (new_logits, pos + adv, new_active,
                                 budget, gen + adv)
                    return (new_state, pools, slots), (tok, active, counts)

                (state, pools, slot_state), (toks, kept, counts) = \
                    jax.lax.scan(body, (state, pools, slot_state), None,
                                 length=K)
                if n_counts:
                    # the block's counters ride behind its tokens, as
                    # whole rows of the one array the host drains anyway
                    rows = -(-n_counts // toks.shape[1])
                    tail = jnp.zeros((rows * toks.shape[1],), toks.dtype)
                    tail = tail.at[:n_counts].set(
                        jnp.sum(counts, axis=0).astype(toks.dtype))
                    toks = jnp.concatenate(
                        [toks, tail.reshape(rows, toks.shape[1])])
            return toks, kept, state, pools, slot_state

        return jax.jit(run, donate_argnums=(1, 6))

    def _build_spec_decode(self, k: int, any_sample: bool):
        """One speculative tick, fully on device: draft k tokens from the
        slot's history (DraftProvider, no model cost for n-gram lookup),
        verify all k in ONE (k+1)-wide forward (``decode_verify_paged``),
        and commit the agreeing prefix — 1..k+1 tokens per weight pass.

        Acceptance reuses the replay-exact per-(seed, rid, token_index)
        keys: the target token at in-tick offset j is sampled (or argmax)
        from the verify logits with the SAME key the non-speculative scan
        would use at that token index, and a draft is accepted iff it
        EQUALS that target. The committed stream is therefore the
        non-speculative stream token for token (greedy and sampled), and
        a rejection just means next tick re-derives the correction as its
        first token from the carried logits row — same logits, same key,
        same token, no rollback.

        Rejected suffixes fold into the existing ``decode_stop_update``
        carry exactly like retired slots do: their tokens leave the tick
        as pad with ``kept=False`` (the drain's prefix-mask contract is
        unchanged) and their K/V is either overwritten by the next verify
        chunk (positions only advance by the committed prefix) or routed
        to the garbage page (beyond the table span) — so a speculatively
        dispatched NEXT block self-masks what this block rejected and the
        depth-2 in-flight window is preserved."""
        core, bind, head = self.core, self._bind, self._head
        provider = self._draft

        def spec_decode_block(params, pools, tables, base_key, state, knobs,
                              hist):
            with bind(params):
                logits, pos, active, budget, gen = state
                B = logits.shape[0]
                H = hist.shape[1]
                b_idx = jnp.arange(B)

                def keys_at(off):
                    # token index gen+off: identical to the key the
                    # non-spec scan folds at that stream position
                    return fold_sampling_keys(base_key, knobs["rseed"],
                                              gen + off)

                def pick(lf, off):
                    if any_sample:
                        return sample_logits_per_slot(
                            lf, knobs["temp"], knobs["topk"],
                            knobs["topp"], knobs["dosample"], keys_at(off))
                    return jnp.argmax(lf, axis=-1)

                # tick's first token: sampled from the carried logits —
                # committed unconditionally (it IS the non-spec token)
                tok0 = pick(logits.astype(jnp.float32), 0)
                tok0 = jnp.where(active, tok0, 0).astype(jnp.int32)
                # draft conditioned on history INCLUDING tok0
                wp = jnp.minimum(pos, H - 1)
                hist = hist.at[b_idx, wp].set(
                    jnp.where(active, tok0, hist[b_idx, wp]))
                drafts = provider.propose(
                    hist, pos + active.astype(jnp.int32), k)
                drafts = jnp.where(active[:, None], drafts, 0)
                inputs = jnp.concatenate([tok0[:, None], drafts], axis=1)
                # inactive rows (mid-prefill or stopped by an earlier
                # in-flight block) write to the garbage page, as always
                tbl = tables * active[:, None].astype(tables.dtype)
                h, pools = core.decode_verify_paged(inputs, pos, pools,
                                                    tbl)
                logits_all = head(h)               # [B, k+1, V]
                lf_all = logits_all.astype(jnp.float32)
                # target token at each draft position, with its stream key
                targets = jnp.stack(
                    [pick(lf_all[:, j - 1], j) for j in range(1, k + 1)],
                    axis=1).astype(jnp.int32)      # [B, k]
                acc = drafts == targets
                n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                                axis=1)
                n_commit = 1 + n_acc               # [B] in 1..k+1
                # fold the 1..k+1 candidate commits through the SAME stop
                # update the non-spec scan carries: eos/budget landing
                # mid-accepted-run truncates the run on device (later
                # tokens pad, row retires), rejection truncates via the
                # (j < n_commit) prefix — one mask, rollback free
                alive, bud = active, budget
                toks_rows, kept_rows = [], []
                for j in range(k + 1):
                    tj = inputs[:, j]
                    commit = alive & (j < n_commit)
                    toks_rows.append(jnp.where(commit, tj, 0))
                    kept_rows.append(commit)
                    cont, bud = decode_stop_update(tj, commit, bud,
                                                   knobs["eos"])
                    alive = jnp.where(commit, cont, alive)
                toks = jnp.stack(toks_rows)        # [k+1, B]
                kept = jnp.stack(kept_rows)        # [k+1, B] prefix mask
                nkept = jnp.sum(kept.astype(jnp.int32), axis=0)
                # append committed drafts to history (tok0 already there)
                for j in range(1, k + 1):
                    wp = jnp.minimum(pos + j, H - 1)
                    hist = hist.at[b_idx, wp].set(
                        jnp.where(kept[j], toks[j], hist[b_idx, wp]))
                # carry logits: the row after the last ACCEPT-committed
                # token — valid because its whole input prefix matched
                # the committed stream. (A stop-truncated row retires, so
                # its junk carry is never read.)
                sel = jnp.minimum(n_commit - 1, k)
                new_logits = jnp.take_along_axis(
                    logits_all, sel[:, None, None], axis=1)[:, 0]
                new_state = (new_logits, pos + nkept, alive, bud,
                             gen + nkept)
            return toks, kept, new_state, pools, hist
        # hist is threaded input→output every tick like pools: donate it
        # so the [B, max_len] buffer updates in place (nothing else holds
        # the old history — in-flight blocks only reference toks/kept/
        # pos/active)
        return jax.jit(spec_decode_block, donate_argnums=(1, 6))

    def _participants(self) -> List[Tuple[int, _Request]]:
        """Slots the NEXT block decodes for: prefill done and not yet
        scheduled through their whole token budget (a slot whose budget
        is fully in flight has nothing left to dispatch — the device
        would mask every step anyway)."""
        if self.spec_k:
            # variable-stride: _proj_gen assumes the MAX stride per
            # in-flight block, but the device may commit fewer — a slot
            # excluded on the over-count would keep decoding on device
            # (its row is still active in the carry) and its committed
            # tokens would never be drained. Exclude only when the
            # MINIMUM the device can have committed (>= 1 per in-flight
            # block while the row lives) already exhausts the budget; a
            # slot that actually finished early just drains an all-False
            # kept column, like any stopped slot.
            def _done(s, r):
                # count only THIS request's in-flight blocks: a reused
                # slot may appear in stale blocks of its previous
                # occupant (they drain all-False for it)
                min_gen = len(r.generated) + sum(
                    1 for b in self._inflight
                    if any(s2 == s and r2 is r
                           for s2, r2 in b.participants))
                return min_gen >= r.max_new_tokens
            return [(s, r) for s in range(self.max_batch)
                    if self._decode_ready(r := self._slots[s])
                    and not _done(s, r)]
        return [(s, r) for s in range(self.max_batch)
                if self._decode_ready(r := self._slots[s])
                and int(self._proj_gen[s]) < r.max_new_tokens]

    def _ensure_decode_pages(self, K: int = 1):
        """Claim every page any active slot may KEEP writes in within the
        next K decode steps (against the in-flight PROJECTION of its
        position); preempt (recompute policy) when the pool is dry. A
        slot's claim span is capped by its remaining max_new budget —
        in-block steps past that are masked on device, so claiming for
        them would evict victims for pages never legitimately written.
        With speculative blocks outstanding a dry pool raises _PoolDry
        instead: draining may retire slots and free pages without an
        eviction. A model without a paged layer claims nothing."""
        for slot in range(self.max_batch if self.paged_layers else 0):
            req = self._slots[slot]
            if not self._decode_ready(req):
                continue              # mid-prefill slots claim at admission
            pos = int(self._proj_pos[slot])
            span = min(K, req.max_new_tokens - int(self._proj_gen[slot]))
            if span <= 0:
                continue              # budget fully in flight already
            first = pos // self.page_size    # ceil == floor at a boundary;
            # a mid-page pos's current page is already held (tables check)
            last = (pos + span - 1) // self.page_size
            for pidx in range(first, last + 1):
                if pidx >= self.pages_per_seq:
                    raise RuntimeError("sequence exceeded engine max_len")
                existing = int(self.tables[slot, pidx])
                if existing != 0:
                    if self._prefix is not None \
                            and self._prefix.owns(existing):
                        # decode is about to write into a tree-owned
                        # page: copy-on-write it into a private page.
                        # (Admission keeps the mapped prefix strictly
                        # below the first decode position, so today
                        # this only guards future mapping policies —
                        # but the write-a-shared-page hazard is fatal
                        # enough to keep the net under it.)
                        assert self._tree_locks[slot] is None or all(
                            existing not in n.pages
                            for n in self._tree_locks[slot].nodes), \
                            "decode diverged inside its own locked prefix"
                        self.tables[slot, pidx] = self._claim_one(slot)
                        self._cow_page(existing, int(self.tables[slot,
                                                                 pidx]))
                        self._tables_dirty = True
                    continue                  # already holds this page
                self.tables[slot, pidx] = self._claim_one(slot)
                self._tables_dirty = True

    def _claim_one(self, exclude_slot: int) -> int:
        """One page for a decode-time claim; recompute-preempts (policy
        victim when configured, newest-rid otherwise) once the pool AND
        the evictable prefix tree are dry, raising _PoolDry first while
        speculative blocks are still in flight."""
        page = self._alloc_pages(1)
        while page is None:
            if self._inflight:
                raise _PoolDry()
            cands = [i for i in range(self.max_batch)
                     if self._slots[i] is not None and i != exclude_slot]
            if not cands:
                raise RuntimeError("page pool too small for one request")
            if self._admission is not None:
                infos = []
                for i in cands:
                    r = self._slots[i]
                    priv = shared = 0
                    for p in self.tables[i]:
                        if p == 0:
                            continue
                        if self._prefix is not None \
                                and self._prefix.owns(int(p)):
                            shared += 1
                        else:
                            priv += 1
                    infos.append(VictimInfo(slot=i, rid=r.rid,
                                            progress=len(r.generated),
                                            private_pages=priv,
                                            shared_pages=shared))
                victim = self._admission.choose_victim(infos)
            else:
                victim = max(cands, key=lambda i: self._slots[i].rid)
            self.preemptions += 1
            vreq = self._slots[victim]
            vreq.preemptions += 1
            self._deactivate(victim)
            # donate the victim's completed pages (prefix mode): its
            # replay re-maps them instead of re-prefilling, and at ref 0
            # they stay first in line for LRU eviction if pressure holds
            self._free_slot(victim, cache=True)
            self._queue.appendleft(vreq)
            page = self._alloc_pages(1)
        return page[0]

    def _dispatch_block(self, emitted: List[tuple]) -> bool:
        """Issue the next decode block WITHOUT waiting for in-flight
        ones. Returns False when no decode-ready slot has budget left."""
        while True:
            parts = self._participants()
            if not parts:
                return False
            if self.spec_k:
                # spec tick: a fixed (spec_k+1)-row block — page claims
                # use the same budget-capped span; draft writes past the
                # table span garbage-route inside decode_verify_paged
                K = self.spec_k + 1
            else:
                # block length this tick: the configured K, capped so no
                # slot's in-block writes can run past its page-table
                # capacity
                cap = self.pages_per_seq * self.page_size
                K = min(self.decode_block,
                        min(cap - int(self._proj_pos[s]) for s, _ in parts))
                K = max(K, 1)
            try:
                self._ensure_decode_pages(K)
            except _PoolDry:
                # drain the pipeline: retirements it reveals may free
                # pages; only preempt once the engine is fully caught up
                self.pool_dry_drains += 1
                emitted.extend(self._drain_all())
                continue
            # a preemption may have emptied or reshuffled the slots
            parts = self._participants()
            if not parts:
                return False
            break
        any_sample = bool(any(self._dosample[s] for s, _ in parts))
        # context-aware dense/paged choice: the batch's max context after
        # this block (projection includes in-flight steps) vs the measured
        # crossover — dense at or below it, the paged kernel above
        spec = bool(self.spec_k)
        # kv_quant folds into the executable key (PR 5 stale-executable
        # posture): pool layout is constructor-fixed today, but an engine
        # whose pools are ever swapped (resharded resume, pool migration)
        # must never reuse a tick compiled for the other layout
        if spec:
            # the verify forward has its own chunk attention (gathers the
            # paged history directly) — no dense/paged fork, so neither
            # the executable key nor attn_path_ticks may depend on it
            fkey = ("spec", K, any_sample, self.kv_quant)
        else:
            ctx_len = max(int(self._proj_pos[s]) for s, _ in parts) + K
            attn_impl = ("dense" if ctx_len <= self.attn_crossover
                         else "paged")
            self.attn_path_ticks[attn_impl] += 1
            fkey = (K, any_sample, attn_impl, self.kv_quant)
        if self.kv_quant:
            self.kv_quant_ticks += 1
        # tables upload BEFORE executable resolution: the cost-observatory
        # eager compile below lowers on the concrete args of this dispatch
        if self._tables_dirty:
            with RecordEvent("serving::tables_upload"):
                self._tables_dev = jnp.asarray(self.tables)
            self._tables_dirty = False
        seq = self._block_seq
        self._block_seq += 1
        booked = self._books.dispatched(self._stream_busy(),
                                        bool(self._inflight))
        fn = self._decode_fns.get(fkey)
        with RecordEvent("serving::dispatch", block=seq, K=K,
                         active=len(parts)), \
                self._building("spec_decode_block" if spec else "run",
                               key="/".join(map(str, fkey))):
            if fn is None:
                jfn = (self._build_spec_decode(self.spec_k, any_sample)
                       if spec else self._build_decode(K, any_sample,
                                                       attn_impl))
                fn = self._decode_fns[fkey] = \
                    self._maybe_compile_with_costs(jfn, spec)
            out = fn(*self._decode_args(spec))
            if spec:
                toks, kept, self._state, self.pools, self._hist = out
            else:
                toks, kept, self._state, self.pools, self.slot_state = out
            # start the device→host copies NOW so reconciliation (one or
            # more blocks later) finds the bytes already on host
            for arr in (toks, kept, self._state[1], self._state[2]):
                copy = getattr(arr, "copy_to_host_async", None)
                if copy is not None:
                    copy()
        stride: Optional[Dict[int, int]] = {} if spec else None
        for s, req in parts:
            steps = min(K, req.max_new_tokens - int(self._proj_gen[s]))
            if spec:
                # the min-stride participant rule can dispatch a slot
                # whose projection is already saturated (stride 0): it
                # rides along so its device commits drain, claiming and
                # projecting nothing new
                steps = max(0, steps)
                stride[s] = steps
            self._proj_gen[s] += steps
            self._proj_pos[s] += steps
        self._inflight.append(_InflightBlock(
            toks, kept, self._state[1], self._state[2], parts, K, seq=seq,
            steps=stride, **booked))
        return True

    def _block_ready(self, blk: _InflightBlock) -> bool:
        try:
            return bool(blk.toks.is_ready()) and bool(blk.active.is_ready())
        except Exception:
            return False

    def _stream_busy(self) -> bool:
        """Whether a block dispatched before is still unfinished: a
        program enqueued now starts straight behind it."""
        return bool(self._inflight) and not self._block_ready(
            self._inflight[-1])

    def _drain_all(self) -> List[tuple]:
        emitted: List[tuple] = []
        while self._inflight:
            emitted.extend(self._reconcile_one())
        return emitted

    def _reconcile_one(self) -> List[tuple]:
        """Drain the OLDEST in-flight block and run the host bookkeeping
        the device already moved past: append kept tokens, retire slots
        whose done flag came back, record arrival-time latency metrics."""
        blk = self._inflight.popleft()
        # a block already finished has a late stamp: the stream's books
        # cannot end its interval here
        waited = not self._block_ready(blk)
        with RecordEvent("serving::drain", block=blk.seq) as span:
            toks = np.asarray(blk.toks)            # [K, B]
            kept = np.asarray(blk.kept)            # [K, B] prefix mask
            if self._tick_counters and not self.spec_k:
                tail = toks[blk.K:].reshape(-1)    # the tick's counters
                for name, n in zip(self._tick_counters, tail):
                    self.tick_counts[name] += int(n)
                toks = toks[:blk.K]
            pos_after = np.asarray(blk.pos)
            active_after = np.asarray(blk.active)
            # TTFT/ITL stamp at token-ARRIVAL time: under pipelining a
            # block's tokens only exist on host once its drain completes,
            # so percentiles stay honest about what a client would
            # observe; where the host waited it is also the instant the
            # block finished on the device, which the books go by
            now, said = self._books.drained(
                blk.K, blk.admit_calls, blk.quiet_t, blk.behind, waited)
            span.tag(**said)
        with RecordEvent("serving::reconcile", block=blk.seq):
            return self._reconcile_host(blk, toks, kept, pos_after,
                                        active_after, now)

    def _reconcile_host(self, blk: _InflightBlock, toks, kept, pos_after,
                        active_after, now: float) -> List[tuple]:
        """The host bookkeeping of one block drained at ``now``."""
        emitted: List[tuple] = []
        for slot, req in blk.participants:
            if self._slots[slot] is not req or req.done:
                continue      # retired by an earlier block's reconcile
            nk = 0
            for j in range(blk.K):
                if not kept[j, slot]:
                    break     # active only falls within a block: prefix
                t = int(toks[j, slot])
                req.generated.append(t)
                nk += 1
                if req.first_tok_t == 0.0:
                    req.first_tok_t = now
                    self.first_tokens += 1
                    self.prefill_dispatch_s += (req.prefill_dispatched_t
                                                - req.prefill_start_t)
                    self.first_token_wait_s += (now
                                                - req.prefill_dispatched_t)
                emitted.append((req.rid, t))
            if nk:
                self._tokens_emitted += nk
                if self.spec_k:
                    # acceptance accounting: every committing drain
                    # scored spec_k drafts; commits beyond the tick's
                    # one guaranteed token are accepted drafts (stop
                    # truncation undercounts — that's the honest number,
                    # it measures tokens a client actually got)
                    self._spec_drains += 1
                    self.spec_tokens_proposed += self.spec_k
                    self.spec_tokens_accepted += nk - 1
                # per-TOKEN inter-token latency: a multi-token drain
                # (decode_block>1, or nk accepted speculative tokens)
                # emits together, so the drain interval is divided
                # across its tokens; an nk==1 drain keeps the old
                # per-tick gap bit-for-bit. The stall a long peer
                # prefill or a preemption inflicts still shows up — as
                # nk equal shares instead of one outsized gap.
                if req.last_emit_t:
                    gap = (now - req.last_emit_t) / nk
                    req.itl_gaps.extend([gap] * nk)
                req.last_emit_t = now
                if req.tspans is not None:
                    # one replica::decode span per committing drain,
                    # covering [previous commit -> this one]: ITL gap
                    # attribution sees decode as contiguous ownership
                    ts = req.tspans
                    wnow = time.time()
                    sp = ts["tr"].start(
                        "replica::decode",
                        parent=ts.get("res") or ts["parent"],
                        start=ts.get("last", wnow), tags={"n": nk})
                    if sp is not None:
                        sp.end(wnow)
                    ts["last"] = wnow
            if not active_after[slot]:
                # the device's done flag: eos or budget hit inside this
                # block. Tokens past the stop were masked on device and
                # their KV routed to the garbage page; _free_slot resets
                # tables so even the kept KV becomes unreachable.
                req.done = True
                req.done_t = now
                self._requests_retired += 1
                self._timelines.append((
                    req.rid, req.submit_t, req.admit_t,
                    req.prefill_start_t, req.prefill_dispatched_t,
                    req.first_tok_t, req.done_t, len(req.generated),
                    req.preemptions))          # _TIMELINE's order
                self._itl_gaps.extend(req.itl_gaps)
                # cache=True: donate the whole conversation's completed
                # pages to the prefix tree before the slot's lock
                # releases (prefix mode; no-op otherwise)
                self._free_slot(slot, cache=True)
            else:
                self.pos[slot] = int(pos_after[slot])
        if self.spec_k:
            # variable-stride reconciliation: the dispatch-time
            # projection assumed the MAX stride (spec_k+1) per block;
            # the device may have committed fewer. Re-anchor at the
            # drained truth plus the recorded strides of blocks still in
            # flight. Claims stay safe through corrections: tables keep
            # every page ever claimed (coverage is monotone), and a
            # budget-capped stride only ever occurs once the claim
            # frontier has already reached the slot's full budget span.
            for slot, req in blk.participants:
                if self._slots[slot] is not req or req.done:
                    continue
                extra = sum((b2.steps or {}).get(slot, 0)
                            for b2 in self._inflight)
                self._proj_gen[slot] = len(req.generated) + extra
                self._proj_pos[slot] = int(pos_after[slot]) + extra
        return emitted

    def _check_page_invariants(self) -> None:
        """Test hook (fuzz-asserted): every pool page is exactly one of
        free / privately owned by ONE table / tree-owned with
        ``node.ref == number of tables mapping it`` — the refcount
        invariant prefix sharing lives or dies by."""
        from collections import Counter as _Counter
        free = [int(p) for p in self._free]
        assert len(set(free)) == len(free), "duplicate pages in free list"
        assert 0 not in free, "garbage page leaked into the free list"
        mapped = _Counter(int(p) for row in self.tables for p in row if p)
        tree = dict(self._prefix._pages) if self._prefix is not None \
            else {}
        assert not set(free) & set(mapped), "page both free and mapped"
        assert not set(free) & set(tree), "page both free and tree-owned"
        for p, node in tree.items():
            assert mapped.get(p, 0) == node.ref, (
                f"tree page {p}: refcount {node.ref} != "
                f"{mapped.get(p, 0)} mapping tables")
        for p, c in mapped.items():
            if p not in tree:
                assert c == 1, f"private page {p} mapped by {c} tables"
        accounted = (len(free) + len(tree)
                     + sum(1 for p in mapped if p not in tree))
        assert accounted == self._total_pages, (
            f"page leak: {self._total_pages - accounted} unaccounted")
        if self._prefix is not None:
            self._prefix.check()

    def reset_latency_stats(self) -> None:
        """Drop the retired-request latency window (e.g. after a warmup
        phase whose TTFTs include one-time jit compiles)."""
        self._timelines.clear()
        self._itl_gaps.clear()

    def request_timelines(self) -> List[dict]:
        """One record per retired request in the window (most recent
        10,000): ``rid``, the ``perf_counter`` stamps ``submit_t``,
        ``admit_t``, ``prefill_start_t``, ``prefill_dispatched_t``,
        ``first_tok_t``, ``done_t`` (each the FIRST occurrence, so they
        never run backwards through a preemption), ``tokens`` and
        ``preemptions``."""
        return [dict(zip(_TIMELINE, r)) for r in self._timelines]

    def latency_stats(self) -> Dict[str, float]:
        """TTFT / end-to-end latency percentiles over a sliding window of
        the most recent 10,000 retired requests (survives run()'s request
        release; ``requests``/``tokens`` count the window, not lifetime) —
        the serving SLO numbers (reference: PaddleNLP llm serving
        benchmarks report the same trio: throughput, TTFT, p99).
        Timestamps are token-ARRIVAL times (post-drain), so pipelined
        dispatch cannot flatter the percentiles. Computed from
        ``request_timelines()``; beside ``ttft``/``latency``/``itl`` it
        splits a request's wait for its first token into ``queue_wait``
        (submit → admitted), ``prefill`` (first prefill program starting →
        last one dispatched; host time, the device may lag) and
        ``first_drain_wait`` (→ first token drained), p50 and p99 each."""
        if not self._timelines:
            return {}
        arr = np.asarray(self._timelines, np.float64)
        col = {k: arr[:, i] for i, k in enumerate(_TIMELINE)}
        out = {"requests": int(arr.shape[0]),
               "tokens": int(col["tokens"].sum())}
        # the request's wait split where the engine's layers hand it on:
        # queued until admitted, its prefill programs' dispatch, then the
        # wait for the decode block that drains its first token
        for key, a, b in (
                ("ttft", "submit_t", "first_tok_t"),
                ("latency", "submit_t", "done_t"),
                ("queue_wait", "submit_t", "admit_t"),
                ("prefill", "prefill_start_t", "prefill_dispatched_t"),
                ("first_drain_wait", "prefill_dispatched_t",
                 "first_tok_t")):
            d = col[b] - col[a]
            out[f"{key}_p50_s"] = float(np.percentile(d, 50))
            out[f"{key}_p99_s"] = float(np.percentile(d, 99))
        if self._itl_gaps:
            gaps = np.asarray(self._itl_gaps, np.float64)
            # per-TOKEN gaps: a multi-token drain (decode_block>1 or an
            # accepted speculative run) divides its interval across the
            # tokens it delivered, so percentiles describe what a client
            # streaming tokens observes. The fairness signal
            # chunked_prefill exists to bound still shows — a long peer
            # prefill or a preemption raises every share in its drain.
            out["itl_p50_s"] = float(np.percentile(gaps, 50))
            out["itl_p99_s"] = float(np.percentile(gaps, 99))
        return out


_NULL = contextlib.nullcontext()


__all__ = ["ContinuousBatchingEngine", "HANDOFF_FMT", "HANDOFF_FMT_V1"]
