"""One run of a serving cell: set-up, a measured window, the check.

The load generator is a schedule consumed by the very loop that calls
``engine.step()``: one thread, so nothing fights the engine for the host's
cores, and how late the generator ran is measured and reported. Every time
is the harness's own clock, stamped when ``step()`` hands a token over; an
open-loop request is timed from the instant it was DUE.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from . import check, program, reduce, traffic


class _Rec:
    __slots__ = ("req", "sent", "admitted", "tokens", "stamps")

    def __init__(self, req):
        self.req, self.sent, self.admitted = req, None, None
        self.tokens, self.stamps = [], []

    @property
    def done(self):
        return len(self.tokens) >= self.req.out_len


class _Driver:
    """Submits, steps, stamps (``time.perf_counter()``, re-based on the
    window's opening when the metrics are taken)."""

    def __init__(self, eng, span):
        self.eng, self.span = eng, span
        self.by_rid, self.order = {}, []
        self.admitted_n = 0
        self.live = {}          # rid -> rec, between first token and done
        self.steps = []         # (t0, t1, active slots, live tokens)

    def submit(self, req):
        rec = _Rec(req)
        rec.sent = time.perf_counter()
        with self.span("bm::submit"):
            rid = self.eng.submit(req.prompt, max_new_tokens=req.out_len)
        self.by_rid[rid] = rec
        self.order.append(rec)

    def step(self):
        """One ``engine.step()``; returns the requests it finished."""
        t0 = time.perf_counter()
        with self.span("bm::step"):
            emitted = self.eng.step()
        t1 = time.perf_counter()
        stats = self.eng.stats()
        # FIFO admission: the first (sent - queued) requests hold a slot
        n_adm = len(self.order) - stats["queued"]
        for rec in self.order[self.admitted_n:n_adm]:
            rec.admitted = t0
        self.admitted_n = max(self.admitted_n, n_adm)
        finished = []
        for rid, tok in emitted:
            rec = self.by_rid[rid]
            rec.tokens.append(int(tok))
            rec.stamps.append(t1)
            if rec.done:
                finished.append(rec)
                self.live.pop(rid, None)
            else:
                self.live[rid] = rec
        self.steps.append((t0, t1, stats["active"], sum(
            len(r.req.prompt) + len(r.tokens) for r in self.live.values())))
        if finished:
            self.eng.take_finished()     # release what the engine keeps
        return finished


def _warm_up(eng, sched, vocab):
    """Every prefill bucket this cell's prompts reach, and the decode
    program, compiled (or read from the cache) before the window."""
    page = eng.page_size
    buckets = sorted({-(-len(r.prompt) // page) * page for r in sched.requests})
    rng = np.random.default_rng(0)
    for b in buckets:
        n = min(b, eng.max_len - 2)
        eng.submit(rng.integers(0, vocab, n, dtype=np.int32), max_new_tokens=2)
    eng.run()
    return buckets


def _ticks(eng):
    return sum(eng.attn_path_ticks.values())


def build(ctx):
    """(model, engine, schedule), every shape this cell uses warmed."""
    config = ctx.config
    model, eng = program.build_engine(config, ctx.seed)
    ctx.stage("engine_built")
    sched = traffic.serving_schedule(ctx.mix, ctx.seed, ctx.seconds,
                                     config["vocab_size"],
                                     config["engine"]["max_len"])
    buckets = _warm_up(eng, sched, config["vocab_size"])
    ctx.stage("warmed_up")
    ctx.log(warmed_prefill_buckets=buckets, requests=len(sched.requests),
            loop=sched.loop)
    return model, eng, sched


def measure(ctx, eng, sched, seconds):
    """Drive ``sched`` through ``eng`` for one window. Returns (end-to-end
    metrics, attempted, failed, [(prompt, served tokens)] of the requests
    that finished inside the window); ``ctx.window_s`` is its length."""
    drv = _Driver(eng, ctx.span)
    reqs, nxt = sched.requests, 0
    closed = sched.loop == "closed"

    if closed:
        # prime: one request per client, first outputs staggered; the window
        # opens once every slot is decoding, which is the steady state
        for _ in range(sched.clients):
            drv.submit(reqs[nxt])
            nxt += 1
        while len(drv.live) < sched.clients:
            for _ in drv.step():
                drv.submit(reqs[nxt % len(reqs)])
                nxt += 1
        t_open = time.perf_counter()
    else:
        t_open = time.perf_counter() + sched.warmup_s   # warm-up traffic first
    ctx.stage("primed")
    end = seconds + (0.0 if closed else sched.grace_s)
    length = seconds            # of the window, in seconds
    at_open = at_close = None
    while True:
        now = time.perf_counter() - t_open
        if at_open is None and now >= 0.0:
            at_open = (_ticks(eng), eng.preemptions, eng.stats())
            ctx.open_window()
        if at_close is None and now >= seconds:
            stats = eng.stats()
            at_close = (_ticks(eng), eng.preemptions, stats["queued"])
            ctx.count_program("engine", at_open[2], stats)
            ctx.close_window()
            if closed:
                # the closed loop's window ends with the step during which
                # the time ran out, so it holds whole ticks only and its
                # rate is all its tokens over all its time
                length = now
        if now >= end:
            break
        ctx.tick(now)
        if not closed:
            while nxt < len(reqs) and reqs[nxt].due_s <= now:
                drv.submit(reqs[nxt])
                nxt += 1
            if at_close is not None and all(
                    r.stamps for r in drv.order
                    if 0.0 <= r.req.due_s < seconds):
                break               # every request due in the window answered
            if not eng.has_work():
                with ctx.span("bm::idle"):
                    time.sleep(0.002)
                continue
        for _ in drv.step():
            if closed:
                drv.submit(reqs[nxt % len(reqs)])
                nxt += 1

    # -- metrics of the window (times re-based on its opening) ---------------
    def inside(t):
        return 0.0 <= t - t_open < length

    ctx.counters.update(decode_ticks=at_close[0] - at_open[0],
                        preemptions=at_close[1] - at_open[1],
                        queued_at_close=at_close[2])
    tokens_in = sum(1 for r in drv.order for t in r.stamps if inside(t))
    samples = ctx.samples
    samples["itl_ms"] = [(b - a) * 1e3 for r in drv.order
                         for a, b in zip(r.stamps, r.stamps[1:]) if inside(b)]
    steps = [s for s in drv.steps if inside(s[0])]
    samples["occupancy_pct"] = [100.0 * s[2] / eng.max_batch for s in steps]
    samples["step_ms"] = [(s[1] - s[0]) * 1e3 for s in steps]
    ctx.counters["live_tokens_mean"] = (sum(s[3] for s in steps)
                                        / max(len(steps), 1))
    ctx.counters["tokens_in_window"] = tokens_in
    e2e, failed = {}, 0
    if closed:
        attempted = sum(1 for r in drv.order if any(map(inside, r.stamps)))
        e2e["serve_tok_s"] = tokens_in / length
    else:
        due = [r for r in drv.order if 0.0 <= r.req.due_s < seconds]
        unsent = sum(1 for r in reqs[nxt:] if 0.0 <= r.due_s < seconds)
        attempted = len(due) + unsent
        ttft = [r.stamps[0] - t_open - r.req.due_s for r in due if r.stamps]
        failed = attempted - len(ttft)
        samples["ttft_s"] = ttft
        samples["gen_late_ms"] = [(r.sent - t_open - r.req.due_s) * 1e3
                                  for r in due]
        samples["queue_wait_ms"] = [
            max(r.admitted - t_open - r.req.due_s, 0.0) * 1e3
            for r in due if r.admitted is not None]
        # a request with no first token within the grace is missing: it
        # counts in ``failed`` and, in the tail, as the whole wait
        e2e["ttft_p90_s"] = reduce.percentile(ttft + [end] * failed, 90)
    ctx.log(tokens_in_window=tokens_in, gaps=len(samples["itl_ms"]),
            attempted=attempted, failed=failed,
            decode_ticks=ctx.counters["decode_ticks"],
            queued_at_close=at_close[2],
            finished=sum(r.done for r in drv.order), steps=len(steps))
    served = [(r.req.prompt, np.asarray(r.tokens, np.int32))
              for r in drv.order if r.done and inside(r.stamps[-1])]
    ctx.unfinished = [rid for rid, r in drv.by_rid.items() if not r.done]
    ctx.window_s = length
    return e2e, attempted, failed, served


def run(ctx):
    model, eng, sched = build(ctx)
    e2e, attempted, failed, served = measure(ctx, eng, sched, ctx.seconds)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.devices())
    # the check: served tokens against the plain reference, once the
    # program's state is freed so that the peak above stays the program's
    for a in jax.tree.leaves((eng.pools, dict(model.raw_parameters()))):
        a.delete()
    del eng, model
    gc.collect()
    numbers = check.served_tokens(ctx.config, ctx.seed, served, ctx.log)
    return {"e2e": e2e, "attempted": attempted, "failed": failed,
            "memory_peak_bytes": mem, "window_s": ctx.window_s,
            "numbers": numbers}
