"""One run of a training cell: set-up, a measured window, the check.

Set-up builds ONE trainer, drives it from the seed through its first three
steps (the steps the reference follows), warms it, and hands that same
object to the window. The window's rate is all its tokens over all its
time: steps are dispatched until the time is up and the clock stops when
the last of them has finished on the device.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import check, program, traffic, weights

CHECKED_STEPS = 3


def _norms(tree):
    return jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree)


def _program_side(tr, names, config, seed, feed):
    """The first steps through the window's own call and feed: losses, the
    first gradient as the optimizer got it, from its state after one step
    (AdamW: m = (1 - beta1) g) — its norm per leaf, and the gradient itself
    copied to the host for the reference to difference — and the change of
    the master weights after the three against the seeded start."""
    beta1 = config["optimizer"]["beta1"]
    losses, grad, first = [], None, None
    for k in range(CHECKED_STEPS):
        losses.append(float(tr.train_step(next(feed))))
        if k == 0:
            m = {names[n]: s["m"] for n, s in tr.opt_state["slots"].items()}
            grad = {n: float(v) / (1.0 - beta1) for n, v in _norms(m).items()}
            first = {n: np.asarray(v, np.float32) / (1.0 - beta1)
                     for n, v in m.items()}
    master = tr.opt_state.get("master", {})
    change = {}
    for n, p in tr.params.items():      # against the seeded start, leaf by leaf
        now = master.get(n, p)
        start = weights.make_some(seed, config, [names[n]])[names[n]]
        change[names[n]] = float(jnp.sqrt(jnp.sum(jnp.square(
            now.astype(jnp.float32) - start))))
    return losses, grad, change, first


def run(ctx):
    config, mix, seed, seconds = ctx.config, ctx.mix, ctx.seed, ctx.seconds
    chips = config.get("chips", 1)
    rows = config["trainer"]["rows_per_chip"] * chips
    seq = int(mix["seq_len"])
    tr, names, model = program.build_trainer(config, seed)
    feed = program.make_loader(
        lambda first, n: traffic.training_rows(mix, seed, first, n,
                                               config["vocab_size"]), rows)
    ctx.stage("trainer_built")
    side = _program_side(tr, names, config, seed, feed)
    ctx.stage("first_steps_done")
    ctx.log(first_losses=side[0])
    for _ in range(int(config["trainer"].get("warmup_steps", 2))):
        loss = tr.train_step(next(feed))
    loss.block_until_ready()

    ctx.open_window()
    at_open = program.trainer_counters(tr)
    t_open = time.perf_counter()
    steps, pending = 0, []
    while True:
        now = time.perf_counter() - t_open
        ctx.tick(now)
        if now >= seconds:
            break
        with ctx.span("bm::input"):
            batch = next(feed)
        with ctx.span("bm::step"):
            pending.append(tr.train_step(batch))
        steps += 1
        if len(pending) > 2:          # keep two steps in flight
            with ctx.span("bm::sync"):
                pending.pop(0).block_until_ready()
    with ctx.span("bm::sync"):
        last = float(pending[-1])
    elapsed = time.perf_counter() - t_open
    ctx.count_program("trainer", at_open, program.trainer_counters(tr))
    ctx.close_window()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.devices())
    tokens = steps * rows * seq
    e2e = {"train_tok_s_chip": tokens / elapsed / chips}
    ctx.counters.update(steps=steps)
    ctx.samples["step_ms"] = [elapsed / steps * 1e3]
    ctx.shapes.update(seq_len=seq, rows_per_chip=config["trainer"]["rows_per_chip"])
    ctx.log(steps=steps, elapsed_s=elapsed, tokens=tokens, last_loss=last)

    # free the program's state for good before the reference takes the chip
    for a in jax.tree.leaves((tr.params, tr.opt_state)):
        a.delete()
    del tr, model, feed, pending
    gc.collect()
    ctx.log(bytes_in_use_after_free=(jax.devices()[0].memory_stats() or {}).get(
        "bytes_in_use"))
    t0 = time.perf_counter()
    ref = check.reference_training(config, mix, seed, CHECKED_STEPS,
                                   first_grads=side[3])
    numbers = check.training_numbers(config, side, ref)
    numbers.append(check.number("last_loss_not_finite", 0.0 if last == last
                                 and abs(last) < 1e9 else 1.0, 0.0))
    ctx.log(check="first steps vs float32 reference",
            seconds=round(time.perf_counter() - t0, 2),
            program_losses=side[0], reference_losses=ref[0], numbers=numbers)
    return {"e2e": e2e, "attempted": steps, "failed": 0,
            "memory_peak_bytes": mem, "window_s": elapsed, "numbers": numbers}
