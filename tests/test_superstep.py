"""Superstep dispatch + persistent compile/AOT cache (ISSUE 2).

Acceptance surface:
* ``Trainer.fit(steps_per_dispatch=K)`` is bit-identical to the per-step
  loop for K∈{1,2,4}, donate on/off, accumulate_steps>1 (the scan body IS
  the per-step function);
* K steps cost ONE dispatch (monkeypatched dispatch counter);
* resume from a checkpoint landing mid-superstep is bit-exact vs an
  uninterrupted run;
* ``precompile`` AOT round-trip: serialize → simulated process restart →
  reload without re-tracing → identical outputs;
* a second in-process cold construction of the same step skips
  tracing/compilation (hit counter);
* the persistent-compile-cache env wiring is a strict no-op when unset.
"""

import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.core import compile_cache
from paddle_tpu.io import DataLoader, TensorDataset, stack_batches, superbatches
from paddle_tpu.nn.layer import Layer
from paddle_tpu.optimizer import SGD, AdamW
from paddle_tpu.optimizer.lr import (CosineAnnealingDecay, ExponentialDecay,
                                     LinearWarmup, MultiStepDecay,
                                     NoamDecay, PiecewiseDecay,
                                     PolynomialDecay, StepDecay)
from paddle_tpu.resilience import AnomalyGuard, CheckpointManager
from paddle_tpu.trainer import Trainer


class TinyReg(Layer):
    def __init__(self):
        super().__init__()
        self.l1 = nn.Linear(8, 16)
        self.l2 = nn.Linear(16, 1)

    def forward(self, x, y):
        h = jnp.tanh(self.l1(x))
        return jnp.mean((self.l2(h) - y) ** 2)


def make_batches(n=12, batch=4, seed=1234):
    rs = np.random.RandomState(seed)
    xs = rs.randn(n * batch, 8).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) * 0.1).astype(np.float32)
    return [{"x": jnp.asarray(xs[i * batch:(i + 1) * batch]),
             "y": jnp.asarray(ys[i * batch:(i + 1) * batch])}
            for i in range(n)]


def build(donate=True, lr=0.05, accumulate_steps=1):
    pt.seed(0)
    m = TinyReg()
    opt = SGD(learning_rate=lr, parameters=m)
    return Trainer(m, opt, donate=donate, accumulate_steps=accumulate_steps)


def build_loader(n=320, batch=16):
    pt.seed(0)
    rs = np.random.RandomState(1234)
    xs = rs.randn(n, 8).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) * 0.1).astype(np.float32)
    loader = DataLoader(TensorDataset([xs, ys]), batch_size=batch,
                        shuffle=False, drop_last=True,
                        collate_fn=lambda items: {
                            "x": np.stack([i[0] for i in items]),
                            "y": np.stack([i[1] for i in items])})
    m = TinyReg()
    return Trainer(m, SGD(learning_rate=0.05, parameters=m),
                   donate=False), loader


def digest(params):
    h = hashlib.sha256()
    for k in sorted(params):
        h.update(k.encode())
        h.update(np.ascontiguousarray(np.asarray(params[k])).tobytes())
    return h.hexdigest()


# -- bit-exactness: superstep vs per-step ------------------------------------

@pytest.mark.parametrize("donate", [True, False])
def test_superstep_bit_exact_vs_per_step(donate):
    res = {}
    for K in (1, 2, 4):
        tr = build(donate=donate)
        hist = tr.fit(iter(make_batches(12)), steps=12, log_every=1,
                      steps_per_dispatch=K)
        res[K] = (digest(tr.params), [m.loss for m in hist], tr._step,
                  int(np.asarray(tr.opt_state["step"])))
    assert res[1] == res[2] == res[4]
    assert res[1][2] == 12 and res[1][3] == 12


def test_superstep_bit_exact_opt_state():
    """Full optimizer state (AdamW moments + step) must match, not just
    params."""
    def run(K):
        pt.seed(0)
        m = TinyReg()
        tr = Trainer(m, AdamW(learning_rate=1e-2, weight_decay=0.01,
                              parameters=m))
        tr.fit(iter(make_batches(8)), steps=8, log_every=100,
               steps_per_dispatch=K)
        flat = {f"{k}/{sk}": v for k, s in tr.opt_state["slots"].items()
                for sk, v in s.items()}
        return digest(tr.params), digest(flat)
    assert run(1) == run(4)


def test_superstep_bit_exact_with_functional_scheduler():
    """In-jit lr_of(step) (StepDecay here) must give the identical schedule
    in the per-step jit and the superstep scan."""
    res = {}
    for K in (1, 4):
        pt.seed(0)
        m = TinyReg()
        opt = SGD(learning_rate=StepDecay(learning_rate=0.05, step_size=3,
                                          gamma=0.5), parameters=m)
        tr = Trainer(m, opt)
        hist = tr.fit(iter(make_batches(12)), steps=12, log_every=1,
                      steps_per_dispatch=K)
        res[K] = (digest(tr.params), [m.loss for m in hist],
                  opt.lr_scheduler.last_epoch)
    assert res[1] == res[4]


def test_superstep_bit_exact_accumulate_steps():
    """steps_per_dispatch composes with gradient accumulation: [A, ...]
    microbatch stacks become [K, A, ...]."""
    singles = make_batches(16, 4)
    pairs = [{"x": jnp.stack([a["x"], b["x"]]),
              "y": jnp.stack([a["y"], b["y"]])}
             for a, b in zip(singles[0::2], singles[1::2])]
    res = {}
    for K in (1, 2):
        tr = build(accumulate_steps=2)
        hist = tr.fit(iter(pairs), steps=8, log_every=1,
                      steps_per_dispatch=K)
        res[K] = (digest(tr.params), [m.loss for m in hist])
    assert res[1] == res[2]


def test_superstep_dispatch_count(monkeypatch):
    """K steps = ONE compiled dispatch (monkeypatched dispatch counter);
    a non-multiple tail is one smaller dispatch, never K per-step calls."""
    calls = []
    orig = Trainer._dispatch

    def counting(self, kind, args):
        calls.append(kind)
        return orig(self, kind, args)

    monkeypatch.setattr(Trainer, "_dispatch", counting)
    tr = build()
    tr.fit(iter(make_batches(10)), steps=10, log_every=100,
           steps_per_dispatch=4)
    assert calls == ["superstep"] * 3          # 4 + 4 + 2
    assert tr.dispatch_stats["dispatches"] == 3
    assert tr.dispatch_stats["steps"] == 10
    assert tr._step == 10


def test_superstep_host_dispatch_overhead_amortized():
    """The host time spent enqueueing per trained step must drop with K>1
    (``dispatch_stats``: dispatch_host_s / steps). Interleaved min-of-rounds so a
    loaded CI machine's scheduling spikes can't flip the verdict."""
    tr = build()
    batches = make_batches(8)
    tr.fit(iter(batches), steps=8, log_every=100)       # warm compiles
    tr.fit(iter(batches), steps=8, log_every=100, steps_per_dispatch=4)

    def overhead(K):
        tr.dispatch_stats = {"steps": 0, "dispatches": 0,
                             "dispatch_host_s": 0.0}
        tr.fit(iter(batches), steps=8, log_every=100, steps_per_dispatch=K)
        return tr.dispatch_stats["dispatch_host_s"] / 8

    best = {1: float("inf"), 4: float("inf")}
    for _ in range(4):
        for K in (1, 4):
            best[K] = min(best[K], overhead(K))
    assert best[4] < best[1], best


def test_superstep_adopts_late_offload_flag(monkeypatch):
    """group_sharded_parallel(offload=True) set AFTER Trainer construction
    must be honored by the superstep path too, not only train_step. The
    CPU tier-1 backend has no pinned_host memory, so placement is stubbed
    and only the adoption + per-dispatch round-trip is asserted."""
    placements = []
    monkeypatch.setattr(
        Trainer, "_place_opt_state",
        lambda self, kind: (placements.append(kind), self.opt_state)[1])
    tr = build()
    tr.optimizer._offload_opt_state = True
    tr.fit(iter(make_batches(4)), steps=4, log_every=100,
           steps_per_dispatch=2)
    assert tr._offload
    assert tr._step == 4
    # adoption park + device/pinned_host round trip around each dispatch
    assert placements[0] == "pinned_host"
    assert placements[1:] == ["device", "pinned_host"] * 2


def test_superstep_metrics_lr_matches_per_step():
    """TrainMetrics.lr from the superstep drain must report the LR at the
    logged step (per-step convention), not the scheduler's already-advanced
    current value."""
    lrs = {}
    for K in (1, 4):
        pt.seed(0)
        m = TinyReg()
        opt = SGD(learning_rate=StepDecay(learning_rate=0.05, step_size=2,
                                          gamma=0.5), parameters=m)
        tr = Trainer(m, opt)
        hist = tr.fit(iter(make_batches(8)), steps=8, log_every=1,
                      steps_per_dispatch=K)
        lrs[K] = [m.lr for m in hist]
    np.testing.assert_allclose(lrs[4], lrs[1], rtol=1e-6)


def test_superstep_rejects_skip_policy():
    tr = build(donate=False)
    guard = AnomalyGuard(policy="skip")
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        tr.fit(iter(make_batches(4)), steps=4, steps_per_dispatch=2,
               anomaly_guard=guard)


# -- resilience interaction ---------------------------------------------------

def test_resume_mid_superstep_bit_exact(tmp_path):
    """A checkpoint landing off the K-grid (step 8 here, then resume to a
    14-step target with K=4 → dispatches of 4 and 2) must equal an
    uninterrupted per-step run."""
    tr, loader = build_loader()
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=5)
    tr.fit(loader, steps=9, log_every=100, checkpoint_manager=mgr,
           steps_per_dispatch=4)
    assert 8 in mgr.committed_steps()      # dispatch boundary ≥ interval

    tr2, loader2 = build_loader()
    mgr2 = CheckpointManager(str(tmp_path), save_interval_steps=5)
    tr2.fit(loader2, steps=14, log_every=100, checkpoint_manager=mgr2,
            resume="auto", steps_per_dispatch=4)
    assert tr2._step == 14

    tr3, loader3 = build_loader()
    tr3.fit(loader3, steps=14, log_every=100)
    assert digest(tr2.params) == digest(tr3.params)


def test_superstep_mid_run_saves_async_and_all_committed(tmp_path):
    """ISSUE 14: mid-run superstep checkpoints enqueue asynchronously —
    the next superstep dispatches while the write drains in the
    background — and every save is committed by a later finalize
    (PENDING -> _COMMITTED, PR 1 protocol). The end-of-fit save stays
    synchronous, so nothing is left pending when fit returns."""
    from paddle_tpu.observability.metrics import REGISTRY
    tr = build()
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=4)
    REGISTRY.enable()
    try:
        tr.fit(iter(make_batches(12)), steps=12, log_every=100,
               steps_per_dispatch=4, checkpoint_manager=mgr)
        c = REGISTRY.counter("pt_checkpoint_saves_total")
        assert c.value(mode="async") >= 2      # steps 4 and 8, mid-run
        assert c.value(mode="sync") >= 1       # end-of-fit save
    finally:
        REGISTRY.disable()
    assert mgr._pending is None
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".PENDING")]
    assert mgr.latest_committed() == 12
    # async-written steps verify their manifests and restore bit-exactly
    s, tree = mgr.restore(tr._ckpt_tree(), step=8)
    assert s == 8
    s, tree = mgr.restore(tr._ckpt_tree())
    assert s == 12
    assert digest({k: np.asarray(v) for k, v in tree["params"].items()}) \
        == digest(tr.params)


def test_superstep_anomaly_rollback(tmp_path):
    """A NaN batch inside a superstep window rolls back to the last good
    checkpoint at the drain boundary and the run completes finite."""
    tr, loader = build_loader()
    batches = list(loader)
    batches[9]["x"] = np.full_like(batches[9]["x"], np.nan)
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=4)
    g = AnomalyGuard(policy="rollback", warmup_steps=100)
    hist = tr.fit(iter(batches), steps=12, log_every=100,
                  checkpoint_manager=mgr, anomaly_guard=g,
                  steps_per_dispatch=4)
    assert g.rollbacks == 1
    assert tr._step == 12
    assert all(np.isfinite(m.loss) for m in hist)
    for v in tr.params.values():
        assert np.all(np.isfinite(np.asarray(v)))


def test_per_step_anomaly_window_batched(tmp_path, monkeypatch):
    """check_every>1 with a non-skip policy consumes losses as a window:
    the guard still catches the poison batch, with one drain per window
    instead of one fence per step."""
    drains = []
    orig = Trainer._drain_loss_window

    def counting(self, window, *a, **kw):
        drains.append(len(window))
        return orig(self, window, *a, **kw)

    monkeypatch.setattr(Trainer, "_drain_loss_window", counting)
    tr, loader = build_loader()
    batches = list(loader)
    batches[5]["x"] = np.full_like(batches[5]["x"], np.nan)
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=3)
    g = AnomalyGuard(policy="rollback", warmup_steps=100, check_every=4)
    tr.fit(iter(batches), steps=10, log_every=100, checkpoint_manager=mgr,
           anomaly_guard=g)
    assert g.rollbacks == 1
    assert tr._step == 10
    for v in tr.params.values():
        assert np.all(np.isfinite(np.asarray(v)))
    assert drains and max(drains) > 1      # batched, not per-step


def test_skip_policy_still_per_step():
    """policy='skip' must keep per-step semantics even when check_every>1
    (the undo needs pre-step references before the next step runs)."""
    tr, loader = build_loader()
    batches = list(loader)
    batches[3]["x"] = np.full_like(batches[3]["x"], np.nan)
    g = AnomalyGuard(policy="skip", warmup_steps=100, check_every=8)
    hist = tr.fit(iter(batches), steps=8, log_every=1, anomaly_guard=g)
    assert g.skips == 1
    assert tr._step == 8
    assert all(np.isfinite(m.loss) for m in hist)


# -- compile / AOT cache ------------------------------------------------------

def test_second_cold_construction_skips_compile():
    """Acceptance: a second in-process cold construction of the same step
    function resolves from the executable cache — no new trace."""
    compile_cache.clear()
    b = make_batches(1)[0]
    tr1 = build()
    tr1.train_step(b)
    s1 = compile_cache.stats()
    assert s1["misses"] == 1 and s1["traces"] >= 1
    tr2 = build()
    l2 = tr2.train_step(b)
    s2 = compile_cache.stats()
    assert s2["traces"] == s1["traces"]        # no re-trace
    assert s2["hits"] == s1["hits"] + 1
    assert s2["misses"] == s1["misses"]
    # and the cached executable computes the same thing as a fresh compile
    compile_cache.clear()
    tr_ref = build()
    l_ref = tr_ref.train_step(b)
    assert float(l2) == float(l_ref)


def test_precompile_aot_roundtrip(tmp_path):
    """serialize → (simulated) process restart → reload: no re-trace, same
    outputs as a freshly compiled trainer."""
    compile_cache.clear()
    b = make_batches(1)[0]
    d = str(tmp_path / "aot")
    tr = build()
    info = tr.precompile(b, cache_dir=d)
    assert info["outcome"] == "miss"
    assert any(f.endswith(".stablehlo.bin") for f in os.listdir(d))
    loss_compiled = float(tr.train_step(b))

    compile_cache.clear()                     # "restart": drop executables
    tr2 = build()
    info2 = tr2.precompile(b, cache_dir=d)
    assert info2["outcome"] == "aot_hit"
    assert compile_cache.stats()["traces"] == 0   # deserialized, not rebuilt
    loss_aot = float(tr2.train_step(b))
    assert loss_aot == loss_compiled
    assert digest(tr2.params) == digest(tr.params)


def test_precompile_aot_stale_fingerprint_recompiles(tmp_path):
    """An artifact written by a DIFFERENT config must be ignored (compile,
    not wrong-reuse)."""
    compile_cache.clear()
    b = make_batches(1)[0]
    d = str(tmp_path / "aot")
    tr = build(lr=0.05)
    tr.precompile(b, cache_dir=d)
    compile_cache.clear()
    tr2 = build(lr=0.01)                      # different hyperparameters
    info = tr2.precompile(b, cache_dir=d)
    assert info["outcome"] == "miss"


def test_superstep_precompile(tmp_path):
    """precompile(steps_per_dispatch=K) primes the superstep executable:
    the following fit pays zero compiles."""
    compile_cache.clear()
    batches = make_batches(8)
    tr = build()
    info = tr.precompile(batches[0], steps_per_dispatch=4,
                         cache_dir=str(tmp_path / "aot"))
    assert info["kind"] == "superstep" and info["outcome"] == "miss"
    before = compile_cache.stats()["misses"]
    tr.fit(iter(batches), steps=8, log_every=100, steps_per_dispatch=4)
    assert compile_cache.stats()["misses"] == before
    assert tr._step == 8


def test_fingerprint_keys_on_schedule_sequence_constants():
    """Milestone/boundary LISTS are baked into the in-jit lr_of trace —
    two schedules differing only there must NOT share an executable."""
    compile_cache.clear()
    b = make_batches(1)[0]

    def build_ms(milestones):
        pt.seed(0)
        m = TinyReg()
        opt = SGD(learning_rate=MultiStepDecay(learning_rate=0.1,
                                               milestones=milestones,
                                               gamma=0.1), parameters=m)
        return Trainer(m, opt)

    tr_a = build_ms([1])       # decays immediately
    tr_b = build_ms([1000])    # never decays in this test
    for _ in range(2):
        tr_a.train_step(b)
        tr_b.train_step(b)
    assert compile_cache.stats()["misses"] == 2      # distinct executables
    # step 1 uses lr 0.01 for A vs 0.1 for B → params diverge (an
    # under-keyed cache hit would make them identical)
    assert digest(tr_a.params) != digest(tr_b.params)


def test_fingerprint_keys_on_model_scalar_attrs():
    """A scalar constant closed over by forward() (same shapes, same class)
    must produce a distinct executable — not silently reuse another
    model's program."""
    compile_cache.clear()

    class Scaled(Layer):
        def __init__(self, scale):
            super().__init__()
            self.scale = scale
            self.l1 = nn.Linear(8, 1)

        def forward(self, x, y):
            return jnp.mean((self.l1(x) * self.scale - y) ** 2)

    b = make_batches(1)[0]
    outs = {}
    for scale in (1.0, 100.0):
        pt.seed(0)
        m = Scaled(scale)
        tr = Trainer(m, SGD(learning_rate=0.05, parameters=m))
        outs[scale] = float(tr.train_step(b))
    assert compile_cache.stats()["misses"] == 2
    assert outs[1.0] != outs[100.0]


def test_precompile_after_train_still_writes_artifact(tmp_path):
    """An in-process executable hit must not skip persisting the restart
    artifact — train first, precompile at checkpoint time is a supported
    order."""
    compile_cache.clear()
    b = make_batches(1)[0]
    d = str(tmp_path / "aot")
    tr = build()
    tr.train_step(b)                        # compiles, populates the cache
    info = tr.precompile(b, cache_dir=d)
    assert info["outcome"] == "hit"
    assert any(f.endswith(".stablehlo.bin") for f in os.listdir(d))
    # and the artifact is valid: a restarted process deserializes it
    compile_cache.clear()
    tr2 = build()
    assert tr2.precompile(b, cache_dir=d)["outcome"] == "aot_hit"


def test_fingerprint_keys_on_callable_attrs():
    """A resolved activation CALLABLE (relu vs gelu, identical shapes) is
    baked into the trace and must key the executable cache."""
    compile_cache.clear()

    class Acted(Layer):
        def __init__(self, act):
            super().__init__()
            self.act = act
            self.l1 = nn.Linear(8, 1)

        def forward(self, x, y):
            return jnp.mean((self.act(self.l1(x)) - y) ** 2)

    b = make_batches(1)[0]
    outs = {}
    for act in (jax.nn.relu, jax.nn.gelu):
        pt.seed(0)
        m = Acted(act)
        tr = Trainer(m, SGD(learning_rate=0.05, parameters=m))
        outs[act.__name__] = float(tr.train_step(b))
    assert compile_cache.stats()["misses"] == 2
    assert outs["relu"] != outs["gelu"]


def test_superstep_metrics_timing_amortized():
    """Multiple log boundaries drained together must share the real wall
    span — not each claim a microsecond window (which read as
    multi-million tokens/sec)."""
    tr = build()
    hist = tr.fit(iter(make_batches(8)), steps=8, log_every=1,
                  steps_per_dispatch=4)
    assert len(hist) == 8
    assert all(m.step_time_s > 1e-5 for m in hist), \
        [m.step_time_s for m in hist]
    times = [m.step_time_s for m in hist]
    # loose bound (first window carries compile time); the pre-fix bug put
    # later boundaries ~1e6x below the first
    assert max(times) / min(times) < 1e5


def test_aot_resume_preserves_donation(tmp_path):
    """The deserialized-artifact path must re-establish buffer donation:
    after a step, the PRE-step param buffers are deleted (donated), not
    kept live alongside the new ones."""
    compile_cache.clear()
    b = make_batches(1)[0]
    d = str(tmp_path / "aot")
    tr = build(donate=True)
    tr.precompile(b, cache_dir=d)
    compile_cache.clear()
    tr2 = build(donate=True)
    assert tr2.precompile(b, cache_dir=d)["outcome"] == "aot_hit"
    before = dict(tr2.params)
    tr2.train_step(b)
    assert all(v.is_deleted() for v in before.values())


# -- satellites: key/LR hygiene, functional schedulers, stacking --------------

def test_lr_scalar_transferred_only_on_change():
    """Constant LR: one device scalar, reused every step (no per-step
    host→device transfer)."""
    tr = build()
    batches = make_batches(4)
    tr.train_step(batches[0])
    first = tr._lr_cache
    tr.train_step(batches[1])
    assert tr._lr_cache is first               # same cached (host, device)
    tr.optimizer.set_lr(0.01)
    tr.train_step(batches[2])
    assert tr._lr_cache is not first           # changed → re-synced once


def test_base_key_cached_not_recreated():
    tr = build()
    batches = make_batches(3)
    tr.train_step(batches[0])
    kd = tr._base_key_data
    tr.train_step(batches[1])
    assert tr._base_key_data is kd


@pytest.mark.parametrize("sched_fn", [
    lambda: StepDecay(learning_rate=0.1, step_size=3, gamma=0.5),
    lambda: MultiStepDecay(learning_rate=0.1, milestones=[2, 5], gamma=0.5),
    lambda: PiecewiseDecay(boundaries=[3, 6], values=[0.1, 0.05, 0.01]),
    lambda: ExponentialDecay(learning_rate=0.1, gamma=0.9),
    lambda: CosineAnnealingDecay(learning_rate=0.1, T_max=10),
    lambda: PolynomialDecay(learning_rate=0.1, decay_steps=8),
    lambda: NoamDecay(d_model=64, warmup_steps=4, learning_rate=1.0),
    lambda: LinearWarmup(learning_rate=0.1, warmup_steps=4, start_lr=0.0,
                         end_lr=0.1),
])
def test_functional_lr_of_matches_host_schedule(sched_fn):
    """lr_of(step) (the in-jit functional view) must agree with the stepped
    host scheduler at every epoch."""
    s = sched_fn()
    assert s.functional
    probe = sched_fn()
    for epoch in range(10):
        host = float(probe.get_last_lr())
        fn = float(np.asarray(s.lr_of(epoch)))
        np.testing.assert_allclose(fn, host, rtol=1e-6, atol=1e-9)
        probe.step()
    # and lr_of must not have mutated the scheduler
    assert s.last_epoch == sched_fn().last_epoch


def test_scalar_batch_leaves_still_dispatch():
    """A python-scalar batch leaf (jit-legal weak-typed arg) must not crash
    the signature/caching layer the way bare `.shape` access would."""
    class ScaledLoss(Layer):
        def __init__(self):
            super().__init__()
            self.l1 = nn.Linear(8, 1)

        def forward(self, x, y, w):
            return jnp.mean((self.l1(x) - y) ** 2) * w

    pt.seed(0)
    m = ScaledLoss()
    tr = Trainer(m, SGD(learning_rate=0.05, parameters=m))
    b = dict(make_batches(1)[0])
    l1 = float(tr.train_step({**b, "w": 0.5}))
    l2 = float(tr.train_step({**b, "w": 2.0}))   # same executable, new value
    assert l1 > 0 and l2 > 0


def test_linear_warmup_lr_of_does_not_corrupt_wrapped_plateau():
    """The host lr_of probe must not leak state into a wrapped
    metric-driven scheduler (best/num_bad/cooldown are beyond
    state_dict())."""
    from paddle_tpu.optimizer.lr import ReduceOnPlateau
    lw = LinearWarmup(learning_rate=ReduceOnPlateau(learning_rate=1.0,
                                                    patience=2),
                      warmup_steps=3, start_lr=0.0, end_lr=1.0)
    assert not lw.functional
    before = dict(vars(lw.lr_after))
    for s in range(12):
        lw.lr_of(s)
    after = dict(vars(lw.lr_after))
    assert before == after


def test_lr_of_host_fallback_non_functional():
    from paddle_tpu.optimizer.lr import LambdaDecay, ReduceOnPlateau
    lam = LambdaDecay(learning_rate=0.1, lr_lambda=lambda e: 0.95 ** e)
    assert not lam.functional
    assert lam.lr_of(4) == pytest.approx(0.1 * 0.95 ** 4)
    assert lam.last_epoch == 0                  # probe did not mutate
    rop = ReduceOnPlateau(learning_rate=0.2)
    assert rop.lr_of(7) == pytest.approx(0.2)   # stateful: current LR


def test_stack_batches_shapes():
    batches = make_batches(3, batch=4)
    stack = stack_batches(batches)
    assert stack["x"].shape == (3, 4, 8)
    assert stack["y"].shape == (3, 4, 1)
    np.testing.assert_array_equal(np.asarray(stack["x"][1]),
                                  np.asarray(batches[1]["x"]))
    with pytest.raises(ValueError):
        stack_batches([])


def test_superbatches_iterator_and_cursor():
    _, loader = build_loader(n=96, batch=16)   # 6 batches
    feeds = list(superbatches(iter(loader), 4))
    assert feeds[0]["x"].shape == (4, 16, 8)
    assert feeds[1]["x"].shape == (2, 16, 8)   # partial tail kept
    assert loader.state_dict()["batches_served"] == 6  # microbatch cursor
    feeds = list(loader.superbatches(4, drop_last=True))
    assert len(feeds) == 1
