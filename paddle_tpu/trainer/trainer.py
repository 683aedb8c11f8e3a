"""Training loop with built-in throughput/MFU accounting.

Reference analogue: the hapi Model.fit loop (python/paddle/hapi/model.py:1756)
+ fleet's hybrid training step (SURVEY.md §3.3), redesigned around one jitted
functional step: params/opt-state are donated pytrees, the loss fn comes from
the Layer functional bridge, randomness enters as a key argument, and the LR
is either a pure on-device function of the step counter (functional
schedulers) or a cached scalar argument.

**Superstep dispatch** (reference analogue: the new executor's async
dispatch + GradientMerge, SURVEY §L5): ``fit(steps_per_dispatch=K)`` fuses K
optimizer steps into ONE compiled ``lax.scan`` over a device-stacked batch
feed. Per-step host work — key creation, LR transfer, loss fence — leaves
the critical path entirely: PRNG keys derive on-device via
``fold_in(base_key, step)`` from the opt-state step counter, the LR is
evaluated in-jit (``scheduler.lr_of(step)``), and per-step losses accumulate
into a device array the host fetches in batches at log/anomaly/checkpoint
boundaries only. The scan body IS the per-step function, so K>1 is
bit-identical to K=1.

**Compile/AOT cache** (core/compile_cache.py): step executables are cached
process-wide by a structural fingerprint; ``precompile()`` AOT-lowers and
serializes them via ``jax.export`` next to the checkpoint dir so a resumed
worker restarts without re-tracing.

MFU = achieved_flops / peak_flops, with model FLOPs from
``model.flops_per_token`` (PaLM convention) and per-chip peak from a small
device table — the calculator the reference lacks (BASELINE.md requires it
from day one).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..core import compile_cache
from ..core.rng import rng_tracker
from ..distributed.overlap import overlap_fingerprint as _overlap_fingerprint
from ..nn.layer import Layer
from ..optimizer.optimizer import Optimizer
from ..profiler import RecordEvent

# span names the trainer emits through RecordEvent (profiler traces and
# the flight recorder's span ring both see them; near-zero cost when
# neither is attached — same contract as SERVING_EVENTS)
TRAINER_EVENTS = ("trainer::dispatch", "trainer::checkpoint")

# names of the trainer's compiled programs as the device trace's
# ``XLA Modules`` line prints them after ``jit_`` (the benchmark's
# flash_attn_roofline reads ``^jit_one_step``)
TRAINER_PROGRAMS = ("one_step", "superstep")

# bf16 peak FLOP/s per chip, keyed by the (lower-cased) ``device_kind``
# jax reports — a v5e chip reports "TPU v5 lite". Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s) and
# the sibling per-generation pages.
PEAK_FLOPS = {
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,   # v5e
    "tpu v5e": 197e12,
    "tpu v5": 459e12,        # v5p
    "tpu v5p": 459e12,
    "tpu v6 lite": 918e12,   # v6e (trillium)
    "cpu": 1e12,             # NOMINAL: keeps the MFU gauge defined in CPU
    #                          tests; never a measurement of anything
}
# HBM per chip, same keys and source: capacity (bytes) and bandwidth
# (bytes/s)
PEAK_HBM = {
    "tpu v5 lite": {"bytes": 16e9, "bytes_per_s": 819e9},
}


def peak_lookup(table: dict, kind: str):
    """The entry of ``table`` whose key is the longest substring of the
    lower-cased ``kind`` ("tpu v5 lite" beats "tpu v5"); LookupError for a
    device the table does not hold — an unknown device is an error, never
    a default to divide by."""
    kind = kind.lower()
    keys = [k for k in table if k in kind]
    if not keys:
        raise LookupError(
            f"device kind {kind!r} is not in the peaks table "
            f"({sorted(table)}); add it with its source")
    return table[max(keys, key=len)]


def device_peak_flops() -> float:
    return peak_lookup(PEAK_FLOPS, jax.devices()[0].device_kind)


@dataclass
class TrainMetrics:
    step: int
    loss: float
    step_time_s: float
    tokens_per_sec: float
    tokens_per_sec_per_chip: float
    mfu: float
    lr: float

    def as_dict(self):
        return self.__dict__.copy()


class Trainer:
    """Single-program trainer: works 1-chip or over a mesh (pass sharded
    params/opt-state; the jitted step inherits their shardings via GSPMD).

    ``offload_opt_state=True`` parks the optimizer moments in HOST memory
    between steps (pinned_host memory space): train_step pulls them to
    device for the (donated) update and pushes the result back, one
    batched transfer each way. Device HBM then holds params+grads+acts
    plus only a transient optimizer copy — the TPU analogue of the
    reference's GroupSharded CPU offload.

    ``seed`` fixes the base PRNG key; step keys derive on-device as
    ``fold_in(key(seed), step)`` so neither the per-step nor the superstep
    path ever creates a key host-side."""

    def __init__(self, model: Layer, optimizer: Optimizer,
                 loss_key: Optional[str] = None, donate: bool = True,
                 accumulate_steps: int = 1,
                 offload_opt_state: Optional[bool] = None,
                 seed: int = 0):
        self.model = model
        self.optimizer = optimizer
        self._named = dict(model.named_parameters())
        # plain dict, not raw_parameters()' OrderedDict: apply_gradients
        # rebuilds plain dicts, and a treedef flip between the first and
        # second dispatch would cost a spurious recompile
        self.params = dict(model.raw_parameters())
        self.opt_state = optimizer.init_state(self.params)
        # None = inherit from the optimizer flag (group_sharded_parallel /
        # fleet set it); an explicit True/False always wins, including over
        # a flag set later
        self._offload_explicit = offload_opt_state is not None
        if offload_opt_state is None:
            offload_opt_state = getattr(optimizer, "_offload_opt_state",
                                        False)
        self._offload = bool(offload_opt_state)
        if self._offload:
            self.opt_state = self._place_opt_state("pinned_host")
        self._donate = donate
        self._step = 0
        self._seed = int(seed)
        self._peak = device_peak_flops()
        self._watchdog = None
        self._active_plan = None      # set by apply_plan
        self._active_mesh = None
        self.accumulate_steps = max(1, int(accumulate_steps))
        # compiled-step machinery (built lazily on first dispatch)
        self._one_step = None          # shared python body (step == scan body)
        self._lr_fn = None
        self._step_jit = None
        self._superstep_jit = None
        self._step_exec: Dict = {}     # aval-signature -> compiled callable
        self._superstep_exec: Dict = {}
        self._fast_exec: Dict = {}     # (kind, batch shapes) -> callable
        self._built_sched = None
        self._lr_cache = None          # (host float, device f32 scalar)
        self._base_key_data = None
        self._aot_dir: Optional[str] = None
        #: one row per program this trainer built (compile_cache.building:
        #: name, t_s, seconds, cache, how) — the split of set-up time
        self.build_log: list = []
        #: host-side dispatch accounting: `dispatch_host_s` is the wall time
        #: spent ENQUEUEING compiled programs (not waiting on them) — the
        #: per-step host overhead the superstep amortizes (per trained step:
        #: dispatch_host_s / steps).
        self.dispatch_stats = {"steps": 0, "dispatches": 0,
                               "dispatch_host_s": 0.0}
        # cost observatory (ISSUE 9): lazily attached at the first log
        # boundary with the metrics plane on; publishes the step-time
        # breakdown + analytical-MFU gauges (observability/costs/live.py).
        # _last_exec tracks the executable the CURRENT dispatch actually
        # ran (bucketed batch shapes mean several live executables — the
        # gauges must attribute the one on the clock, not the first
        # compiled)
        self._cost_watch = None
        self._cost_watch_kind = None
        self._last_exec = None
        self._last_exec_kind = None

    # -- step function -------------------------------------------------------

    def _build_step(self):
        model, opt = self.model, self.optimizer

        accum = self.accumulate_steps

        # models with a fused forward+backward schedule (1F1B pipeline)
        # provide loss_and_grads instead of being differentiated through
        fused = (getattr(model, "pp_schedule", None) == "1f1b"
                 and hasattr(model, "loss_and_grads"))

        sched = opt.lr_scheduler
        # functional scheduler: LR becomes a pure on-device function of the
        # step counter, evaluated inside the compiled program — the same
        # derivation in the per-step jit and the superstep scan body, so
        # the two paths stay bit-identical
        lr_fn = (sched.lr_of
                 if sched is not None and getattr(sched, "functional", False)
                 else None)
        self._built_sched = sched

        def loss_of(params, batch, key):
            if fused:
                with rng_tracker().scope(key):
                    return model.loss_and_grads(params, **batch)

            def loss_fn(p):
                with rng_tracker().scope(key):
                    out = model.functional_call(p, **batch)
                loss = out[0] if isinstance(out, tuple) else out
                return loss
            return jax.value_and_grad(loss_fn)(params)

        def one_step(params, opt_state, batch, lr, key_data):
            compile_cache.note_trace()
            # the opt-state step counter IS the trainer step (both restored
            # together on resume/rollback): derive key + LR from it on-device
            step = opt_state["step"]
            key = jax.random.fold_in(jax.random.wrap_key_data(key_data),
                                     step)
            lr_t = lr_fn(step) if lr_fn is not None else lr
            if accum == 1:
                loss, grads = loss_of(params, batch, key)
            else:
                # gradient accumulation (reference: GradientMerge pass /
                # accumulate_steps): batch arrays carry a leading microbatch
                # dim [A, ...]; one lax.scan accumulates grads in-place —
                # a single compiled program, activations of only one
                # microbatch live at a time
                keys = jax.random.split(key, accum)

                def body(carry, inp):
                    g_acc, l_acc = carry
                    mb, k = inp
                    l, g = loss_of(params, mb, k)
                    return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), None

                zeros = jax.tree.map(jnp.zeros_like, params)
                (grads, loss_sum), _ = jax.lax.scan(
                    body, (zeros, 0.0), (batch, keys))
                grads = jax.tree.map(lambda g: g / accum, grads)
                loss = loss_sum / accum
            new_params, new_opt_state = opt.apply_gradients(params, grads,
                                                            opt_state,
                                                            lr=lr_t)
            return new_params, new_opt_state, loss

        def superstep(params, opt_state, batch_stack, lr_stack, key_data):
            # K fused steps, one dispatch: the scan body IS one_step, so
            # numerics are bit-identical to K calls of the per-step jit.
            # raw_parameters() hands an OrderedDict while apply_gradients
            # rebuilds plain dicts — normalize so the scan carry structure
            # is closed under the body
            params = dict(params)

            def body(carry, inp):
                p, s = carry
                mb, lr_i = inp
                p, s, loss = one_step(p, s, mb, lr_i, key_data)
                return (p, s), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), (batch_stack, lr_stack))
            return params, opt_state, losses

        donate = (0, 1) if self._donate else ()
        self._one_step = one_step
        self._lr_fn = lr_fn
        self._step_jit = jax.jit(one_step, donate_argnums=donate)
        self._superstep_jit = jax.jit(superstep, donate_argnums=donate)
        self._step_exec = {}
        self._superstep_exec = {}
        self._fast_exec = {}
        self._static_fp = None

    def _ensure_built(self):
        if (self._one_step is None
                or self.optimizer.lr_scheduler is not self._built_sched):
            self._build_step()

    # -- compile-cache plumbing ---------------------------------------------

    def _fp_parts(self):
        """Structural fingerprint of the traced program: everything that
        changes the compiled step WITHOUT changing argument avals (model
        wiring, optimizer/scheduler hyperparameters, donation/accum flags).
        Conservative by design — an over-keyed miss costs one compile, an
        under-keyed hit would be a correctness bug."""
        if getattr(self, "_static_fp", None) is not None:
            return self._static_fp

        def scalars(obj):
            # "name" is a process-serial label (LRScheduler registry), not
            # program structure — keying on it would defeat reuse. Scalar
            # SEQUENCES (milestones/boundaries/values...) and CALLABLE attrs
            # (a resolved activation fn: relu vs gelu with identical shapes)
            # are constants the trace bakes in, so they must key too.
            out = []
            for k, v in vars(obj).items():
                if k == "name":
                    continue
                if isinstance(v, (int, float, bool, str)):
                    out.append((k, v))
                elif isinstance(v, (list, tuple)) and all(
                        isinstance(x, (int, float, bool, str)) for x in v):
                    out.append((k, tuple(v)))
                elif callable(v) and not isinstance(v, Layer):
                    # qualname, never repr(): a repr with an object address
                    # would be unique per construction and kill reuse
                    out.append((k, f"{getattr(v, '__module__', '?')}."
                                   f"{getattr(v, '__qualname__', type(v).__name__)}"))
            return sorted(out)

        model, opt = self.model, self.optimizer
        cfg = getattr(model, "cfg", None)
        try:
            # per-sublayer SCALAR attrs too, not just the type: Dropout p,
            # norm eps, a scale constant — all baked into the trace with no
            # aval footprint. (Python closures can never be fingerprinted
            # exhaustively; this covers every attribute-carried constant.)
            structure = tuple(
                (n, type(l).__qualname__, tuple(scalars(l)))
                for n, l in model.named_sublayers())
        except Exception:
            structure = ()
        sched, clip = opt.lr_scheduler, opt.grad_clip

        def sched_constants(s):
            # the schedule FORMULA is baked into the trace (in-jit lr_of):
            # key on its constants — including those of a WRAPPED scheduler
            # (LinearWarmup.lr_after) — but NOT on mutable progress state
            # (last_epoch/last_lr advance every step — including them would
            # break artifact reuse across a resume, the whole point)
            from ..optimizer.lr import LRScheduler
            mutable = set(s.state_dict())
            consts = [(k, v) for k, v in scalars(s) if k not in mutable]
            nested = tuple(
                (k, type(v).__qualname__, sched_constants(v))
                for k, v in sorted(vars(s).items())
                if isinstance(v, LRScheduler))
            return (tuple(consts), nested)

        sched_part = ()
        if sched is not None and self._lr_fn is not None:
            sched_part = sched_constants(sched)
        import os
        # LABELED parts (ISSUE 8): the fingerprint used to be a bare
        # positional tuple, so a stale-AOT-artifact rejection could only
        # say "fingerprint mismatch". Named keys make
        # compile_cache.explain_fingerprint_change render actionable paths
        # (env.PT_NAIVE_LOSS_HEAD: False -> True). Hash COVERAGE (which
        # program facts key the cache) is identical, but the JSON
        # rendering — and hence the hash VALUE — changes once at this
        # boundary: pre-existing AOT artifacts recompile one time (their
        # tuple-era sidecars carry no "parts", so that one rejection is
        # silent, exactly the old behavior).
        self._static_fp = {
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "model_class": type(model).__qualname__,
            "model_scalars": scalars(model),
            "config_scalars": (scalars(cfg) if cfg is not None
                               and hasattr(cfg, "__dict__") else ()),
            # quantized layouts retrace the whole program with different
            # param avals AND different traced ops (registry int8_matmul
            # vs dense matmul) — config_scalars already covers the str
            # fields, but the labeled entry makes a stale-artifact
            # rejection render as "quantization.weight_dtype: native ->
            # int8" instead of a config_scalars diff (ISSUE 17)
            "quantization": {
                "weight_dtype": getattr(cfg, "weight_dtype", "native"),
                "kv_dtype": getattr(cfg, "kv_dtype", "native"),
            },
            # trace-affecting env escapes: the loss-head override flips
            # which program gets traced with identical avals and cfg —
            # without this key a restart under PT_NAIVE_LOSS_HEAD=1 would
            # aot-hit the stale FUSED executable (and vice versa)
            "env": {
                "PT_NAIVE_LOSS_HEAD":
                    bool(os.environ.get("PT_NAIVE_LOSS_HEAD")),
                "PT_DISABLE_PALLAS":
                    bool(os.environ.get("PT_DISABLE_PALLAS")),
                # overlap scheduler flags change the compiled schedule
                # (async start/done placement) with identical avals — a
                # flag flip between runs must not aot-hit the executable
                # compiled under the other schedule (ISSUE 14)
                "overlap": _overlap_fingerprint(),
            },
            "sublayers": structure,
            "optimizer_class": type(opt).__qualname__,
            "optimizer_scalars": scalars(opt),
            "scheduler_class": (type(sched).__qualname__
                                if sched is not None else None),
            "scheduler_constants": sched_part,
            "functional_lr": bool(self._lr_fn),
            "grad_clip_class": (type(clip).__qualname__
                                if clip is not None else None),
            "grad_clip_scalars": scalars(clip) if clip is not None else (),
            "donate": self._donate,
            "accumulate_steps": self.accumulate_steps,
        }
        return self._static_fp

    def _dispatch(self, kind: str, args):
        """Dispatch one compiled program through the process-wide compile
        cache (core/compile_cache.py): first call per argument-shape
        signature resolves an executable (in-process hit → AOT artifact →
        lower+compile); subsequent calls are a dict lookup + enqueue."""
        t0 = time.perf_counter()
        # fast path: params/opt_state avals are fixed between builds, so
        # steady-state lookup keys only on the batch leaves' shapes —
        # flattening the full param tree per step is exactly the recurring
        # host work this runtime exists to strip
        batch = args[2]
        try:
            # shape AND dtype: a same-shape batch whose leaf dtype drifts
            # (e.g. labels int32 → int64 from a numpy default) must fall
            # through to the aval-keyed slow path and recompile, not hit a
            # stale executable and die on an aval-mismatch TypeError
            fast = (kind, tuple(sorted((k, v.shape, str(v.dtype))
                                       for k, v in batch.items())))
        except Exception:
            fast = None
        fn = self._fast_exec.get(fast) if fast is not None else None
        if fn is None:
            jitted = (self._step_jit if kind == "step"
                      else self._superstep_jit)
            exec_cache = (self._step_exec if kind == "step"
                          else self._superstep_exec)
            sig = compile_cache.aval_signature(args)
            fn = exec_cache.get(sig)
            if fn is None:
                parts = {"static": self._fp_parts(), "kind": kind,
                         "avals": sig}
                fp = compile_cache.fingerprint(
                    (self._fp_parts(), kind, sig))
                fn, _ = compile_cache.acquire(
                    fp, jitted, args, aot_dir=self._aot_dir, name=kind,
                    donate_argnums=(0, 1) if self._donate else (),
                    fp_parts=parts, build_log=self.build_log)
                exec_cache[sig] = fn
            if fast is not None:
                self._fast_exec[fast] = fn
        self._last_exec = fn
        self._last_exec_kind = kind
        # a step span: ``step_num`` makes it a StepTraceAnnotation, which
        # xprof's step view groups the device's work by
        with RecordEvent("trainer::dispatch", step_num=self._step,
                         kind=kind):
            out = fn(*args)
        self.dispatch_stats["dispatches"] += 1
        self.dispatch_stats["dispatch_host_s"] += time.perf_counter() - t0
        return out

    def _key_data(self):
        """Cached base-key data (uint32): created ONCE, folded with the step
        counter on-device — never a fresh jax.random.key per step."""
        if self._base_key_data is None:
            self._base_key_data = jax.random.key_data(
                jax.random.key(self._seed))
        return self._base_key_data

    def _lr_scalar(self):
        """Device LR scalar, re-transferred only when the host scheduler
        actually changed the value (satellite: trainer.py no longer pays a
        host→device LR copy per step). With a functional scheduler the lr
        argument is dead (one_step computes lr_of(step) in-jit) — a fixed
        zero avoids re-syncing a value nobody reads."""
        if self._lr_fn is not None:
            if self._lr_cache is None or self._lr_cache[0] is not None:
                self._lr_cache = (None, jnp.zeros((), jnp.float32))
            return self._lr_cache[1]
        host = float(self.optimizer.get_lr())
        if self._lr_cache is None or self._lr_cache[0] != host:
            self._lr_cache = (host, jnp.asarray(host, jnp.float32))
        return self._lr_cache[1]

    def precompile(self, sample_batch: Dict[str, jax.Array],
                   steps_per_dispatch: int = 1,
                   cache_dir: Optional[str] = None) -> Dict[str, Any]:
        """AOT-lower and compile the training (super)step before the first
        batch arrives, and persist a ``jax.export`` artifact for restarts.

        ``cache_dir`` (defaults to the dir wired by a previous call or by
        ``fit(checkpoint_manager=...)``, i.e. ``<ckpt_root>/_compile_cache``)
        receives the serialized StableHLO + fingerprint sidecar; a relaunch
        whose fingerprint matches deserializes it instead of re-tracing
        (``compile_cache.stats()["traces"]`` proves it). Returns
        ``{"kind", "outcome" (hit|aot_hit|miss), "fingerprint", "aot_dir"}``.
        """
        self._ensure_built()
        if cache_dir:
            os.makedirs(cache_dir, exist_ok=True)
            self._aot_dir = cache_dir
        k = max(1, int(steps_per_dispatch))
        lr = self._lr_scalar()
        kd = self._key_data()
        if k == 1:
            kind = "step"
            args = (self.params, self.opt_state, sample_batch, lr, kd)
            jitted, exec_cache = self._step_jit, self._step_exec
        else:
            from ..io.dataloader import stack_batches
            kind = "superstep"
            stack = stack_batches([sample_batch] * k)
            lr_stack = jnp.zeros((k,), jnp.float32)
            args = (self.params, self.opt_state, stack, lr_stack, kd)
            jitted, exec_cache = self._superstep_jit, self._superstep_exec
        # avals keep the inputs' SHARDINGS (compile_cache.to_avals): the
        # executable is specialized to placement, and the cache key
        # (aval_signature) includes it — an unsharded lowering stored under
        # a sharded key would blow up at the first real dispatch
        avals = compile_cache.to_avals(args)
        sig = compile_cache.aval_signature(args)
        fp = compile_cache.fingerprint((self._fp_parts(), kind, sig))
        fn, outcome = compile_cache.acquire(
            fp, jitted, avals, aot_dir=self._aot_dir, name=kind,
            save_artifact=self._aot_dir is not None,
            donate_argnums=(0, 1) if self._donate else (),
            fp_parts={"static": self._fp_parts(), "kind": kind,
                      "avals": sig}, build_log=self.build_log)
        exec_cache[sig] = fn
        return {"kind": kind, "outcome": outcome, "fingerprint": fp,
                "aot_dir": self._aot_dir}

    def _place_opt_state(self, kind: str):
        from ..optimizer.optimizer import place_opt_state
        return place_opt_state(self.opt_state, self.params, kind)

    def _adopt_offload_flag(self):
        """group_sharded_parallel(offload=True) may run AFTER this Trainer
        was built — honor the optimizer's flag from here on (unless the
        caller explicitly passed offload_opt_state=False). Shared by the
        per-step and superstep entry points."""
        if (not self._offload and not self._offload_explicit
                and getattr(self.optimizer, "_offload_opt_state", False)):
            self._offload = True
            self.opt_state = self._place_opt_state("pinned_host")

    def apply_plan(self, plan, devices=None):
        """Adopt a sharding-planner plan (ISSUE 11): place params and
        optimizer state per the emitted ``ShardingPlan`` and return the
        mesh to train under. The next dispatch recompiles against the
        new placements automatically (the compile-cache aval signature
        includes shardings). Usage::

            report = auto_parallel.plan(cfg, n_devices=8)
            hm = trainer.apply_plan(report.chosen.plan)
            with hm:
                trainer.fit(loader, steps=...)
        """
        from ..parallel.api import shard_optimizer_state
        hm = plan.apply(self.model, devices=devices)
        self.params = dict(self.model.raw_parameters())
        self.opt_state = shard_optimizer_state(
            self.opt_state, plan.param_specs, mesh=hm)
        # remembered so fit() can hand the plan to the checkpoint manager
        # (saves record it as _PLAN.json; restores on a different mesh
        # reshard against it) without extra caller wiring
        self._active_plan = plan
        self._active_mesh = hm
        return hm

    def train_step(self, batch: Dict[str, jax.Array]) -> jax.Array:
        """One optimization step. ``batch`` maps forward kwarg names to
        arrays (e.g. {"input_ids": ..., "labels": ...}). Returns the loss
        as a DEVICE scalar — callers fence (float()) only when they need
        the value."""
        self._adopt_offload_flag()
        self._ensure_built()
        if self._watchdog is not None:
            self._watchdog.tick()
        lr = self._lr_scalar()
        kd = self._key_data()
        if self._offload:
            # pull the state up for the step, push the update back down:
            # host<->device streams around a device-resident step (the
            # transient device copy is donated straight into the update).
            # In-jit memory-space annotation is deliberately not used —
            # mixed-space operands are rejected by XLA and the CPU test
            # backend lacks annotate_device_placement entirely.
            self.opt_state = self._place_opt_state("device")
        self.params, self.opt_state, loss = self._dispatch(
            "step", (self.params, self.opt_state, batch, lr, kd))
        if self._offload:
            self.opt_state = self._place_opt_state("pinned_host")
        self._step += 1
        self.dispatch_stats["steps"] += 1
        if self._donate:
            # donation invalidates the previous param buffers, which the
            # Layer's Parameters still reference — rebind them to the new
            # arrays so imperative model use never touches deleted buffers
            self.sync_model()
        sched = self.optimizer.lr_scheduler
        if sched is not None:
            sched.step()
        return loss

    # -- full loop with metrics ---------------------------------------------

    def fit(self, data: Iterable[Dict[str, jax.Array]], steps: int,
            log_every: int = 10, on_metrics: Optional[Callable] = None,
            seq_len: Optional[int] = None, checkpoint_manager=None,
            resume=None, anomaly_guard=None, preemption_guard=None,
            steps_per_dispatch: int = 1):
        """Run the training loop. Beyond the metrics loop, this is the
        fault-tolerant runtime (resilience subsystem):

        * ``checkpoint_manager`` (resilience.CheckpointManager): periodic
          saves every ``save_interval_steps`` plus a final synchronous save;
        * ``resume="auto"``: restore params/opt_state/step/LR-scheduler from
          the newest COMMITTED checkpoint and fast-forward the data cursor
          (via ``data.set_state_dict`` when the loader supports it). With
          resume, ``steps`` is the TOTAL step budget of the run — a relaunch
          trains to the same target as an uninterrupted run;
        * ``preemption_guard`` (resilience.PreemptionGuard): on SIGTERM the
          loop writes one final sync checkpoint at the next step boundary
          and raises TrainingPreempted (exit code = resumable);
        * ``anomaly_guard`` (resilience.AnomalyGuard): NaN/Inf or loss-spike
          steps are skipped (undo the update; needs donate=False) or rolled
          back to the last good checkpoint, within bounded budgets. With
          ``check_every > 1`` (and a non-skip policy) loss verdicts are
          consumed as a batched window — ONE device fence per window instead
          of one per step;
        * ``steps_per_dispatch=K`` (superstep): K steps compiled into one
          ``lax.scan`` dispatch over stacked batches; losses are fetched
          asynchronously at log/anomaly/checkpoint boundaries. Bit-identical
          to K=1 (shared step body). Checkpoint/anomaly cadence aligns to
          dispatch boundaries (first boundary at-or-after the configured
          interval); resume may land mid-superstep — the next dispatch is
          simply sized ``min(K, target - step)``. Incompatible with
          ``policy="skip"`` (a mid-scan poisoned update cannot be undone
          from pre-step references). The hung-step watchdog
          (``PT_STEP_TIMEOUT_S``) is ticked per DISPATCH and around window
          fetches, so calibrate it against ``ring_depth*K`` step times, not
          one.
        """
        # hung-step watchdog (PT_STEP_TIMEOUT_S): armed only for the
        # duration of this bounded loop — inter-step gaps here ARE steps
        # (device sync + next-batch wait), so a stall is a real hang, and
        # stopping it on exit means eval/checkpoint phases outside fit()
        # can never trigger a spurious kill (reference:
        # phi/core/distributed/comm_task_manager.cc per-task timeouts)
        from ..distributed.watchdog import watchdog_from_env
        if self._watchdog is None:
            self._watchdog = watchdog_from_env()
        if resume and checkpoint_manager is None:
            raise ValueError("resume requires a checkpoint_manager")
        if (anomaly_guard is not None and anomaly_guard.policy == "skip"
                and self._donate):
            raise ValueError(
                "AnomalyGuard(policy='skip') requires Trainer(donate=False): "
                "undoing a poisoned update needs pre-step parameter "
                "references, which buffer donation invalidates. Use "
                "policy='rollback' (with a checkpoint_manager) or disable "
                "donation.")
        K = max(1, int(steps_per_dispatch))
        if K > 1 and anomaly_guard is not None \
                and anomaly_guard.policy == "skip":
            raise ValueError(
                "steps_per_dispatch>1 cannot honor AnomalyGuard("
                "policy='skip'): a poisoned update inside a compiled "
                "superstep cannot be undone from pre-step references. Use "
                "policy='rollback' (checkpoint-backed) or "
                "steps_per_dispatch=1.")
        if checkpoint_manager is not None and self._aot_dir is None:
            # precompiled AOT artifacts live next to the checkpoints — a
            # resumed worker picks them up without re-tracing
            d = os.path.join(checkpoint_manager.root, "_compile_cache")
            if os.path.isdir(d):
                self._aot_dir = d
        if (checkpoint_manager is not None and self._active_plan is not None
                and getattr(checkpoint_manager, "plan", None) is None):
            # hand the applied ShardingPlan to the manager: saves record
            # it as _PLAN.json, and a restore whose saved plan has
            # different axes goes through the reshard path (ISSUE 15)
            checkpoint_manager.plan = self._active_plan
            if checkpoint_manager.mesh is None:
                checkpoint_manager.mesh = getattr(
                    self._active_mesh, "mesh", self._active_mesh)
            if checkpoint_manager.spec_tree is None:
                checkpoint_manager.spec_tree = dict(
                    self._active_plan.param_specs)
        if (checkpoint_manager is not None
                and _obs.flight_recorder.recorder().active):
            # crash dumps land next to the quarantine dir so a post-mortem
            # ships with the checkpoint state it describes
            _obs.flight_recorder.set_dir(
                os.path.join(checkpoint_manager.root, "_flight"))
        # goodput ledger: the whole fit window is accounted wall-time;
        # everything not claimed by a span (compile/save/restore/preempt)
        # books as productive_step, and metering happens only at the
        # boundaries this loop already crosses — no new device fences
        led = _obs.ledger()
        led.run_start()
        try:
            if resume and checkpoint_manager is not None:
                self._resume_from(checkpoint_manager, data)
                target = int(steps)
            else:
                target = self._step + int(steps)
            it = iter(data)
            history = []
            t_last = time.perf_counter()
            tokens_since = 0
            loss = None
            if K > 1:
                return self._fit_superstep(it, target, K, log_every,
                                           on_metrics, seq_len, history,
                                           mgr=checkpoint_manager,
                                           anomaly=anomaly_guard,
                                           guard=preemption_guard, data=data)
            return self._fit_loop(it, target, log_every, on_metrics, seq_len,
                                  history, t_last, tokens_since, loss,
                                  mgr=checkpoint_manager,
                                  anomaly=anomaly_guard,
                                  guard=preemption_guard, data=data)
        finally:
            led.run_end()
            if _obs.enabled():
                _obs.publish()       # goodput buckets + snapshot -> exporters
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None

    def _publish_step_costs(self, m: "TrainMetrics", kind: str = "step",
                            steps_per_exec: int = 1) -> None:
        """Cost-observatory gauges at a log boundary (ISSUE 9): the
        measured step time split into compute/collective/host/stall, plus
        analytical MFU / HBM-BW utilization and the predicted-over-
        measured drift ratio — all derived from the ACTIVE executable's
        optimized HLO by the one ``observability/costs`` analyzer.
        Lazily attached, cached per executable, and fully guarded: the
        loop never fails (or slows down, beyond one HLO parse per
        compile) on account of its own telemetry."""
        if not _obs.enabled():
            return
        try:
            if (self._cost_watch is None
                    or self._cost_watch_kind != kind):
                from ..observability.costs import CostWatch
                self._cost_watch = CostWatch("train")
                self._cost_watch_kind = kind
            watch = self._cost_watch
            # attribute the executable the clocked window actually
            # dispatched (re-observed on change — bucketed shapes mean
            # several live executables; reports are cached per id)
            if self._last_exec_kind == kind:
                watch.observe_executable(self._last_exec)
            # per-WINDOW host overhead: the lifetime average would carry
            # the first dispatch's trace+compile seconds forever and the
            # host bucket would swallow the whole breakdown
            ds = self.dispatch_stats
            mark = getattr(self, "_cost_disp_mark", None) or (0, 0.0)
            dsteps = ds["steps"] - mark[0]
            dhost = ds["dispatch_host_s"] - mark[1]
            self._cost_disp_mark = (ds["steps"], ds["dispatch_host_s"])
            if dsteps <= 0 or dhost < 0:      # stats were reset externally
                dsteps, dhost = max(ds["steps"], 1), ds["dispatch_host_s"]
            watch.publish(m.step_time_s, host_s=dhost / max(dsteps, 1),
                          steps_per_exec=steps_per_exec)
        except Exception:
            pass

    def _fit_loop(self, it, target, log_every, on_metrics, seq_len,
                  history, t_last, tokens_since, loss, mgr=None, anomaly=None,
                  guard=None, data=None):
        # anomaly windowing: policy="skip" must fence every step (the undo
        # needs pre-step references from BEFORE the next step runs);
        # rollback/abort verdicts can consume a batched loss window — one
        # device fence per check_every steps (satellite: trainer.py:283)
        window = []
        per_step_check = (anomaly is None or anomaly.policy == "skip"
                          or getattr(anomaly, "check_every", 1) <= 1)
        while self._step < target:
            if guard is not None and guard.preempted:
                if window:
                    it, _ = self._drain_loss_window(window, anomaly, mgr,
                                                    data, it)
                self._preempt_exit(mgr, data)
            try:
                batch = next(it)
            except StopIteration:
                break
            ids = batch.get("input_ids")
            ntok = int(ids.shape[0] * ids.shape[1]) if ids is not None else 0
            prev = None
            if anomaly is not None and not self._donate:
                # pre-step references (immutable jax arrays — free to hold)
                # let "skip" undo a poisoned update without any checkpoint
                sched = self.optimizer.lr_scheduler
                prev = (self.params, self.opt_state,
                        sched.state_dict() if sched is not None else None)
            loss = self.train_step(batch)
            tokens_since += ntok
            if anomaly is not None:
                if per_step_check:
                    verdict = anomaly.check(float(loss))
                    if verdict != "ok":
                        it = self._handle_anomaly(verdict, anomaly, mgr,
                                                  prev, data, it,
                                                  float(loss))
                        continue
                else:
                    window.append((self._step, loss))
                    if len(window) >= anomaly.check_every:
                        it, rolled = self._drain_loss_window(
                            window, anomaly, mgr, data, it)
                        if rolled:
                            continue
            if self._step % log_every == 0:
                loss_v = float(loss)  # blocks; amortized over log_every
                now = time.perf_counter()
                dt = now - t_last
                tps = tokens_since / dt if dt > 0 else 0.0
                n_dev = jax.device_count()
                sl = seq_len or (ids.shape[1] if ids is not None else 1)
                fpt = (self.model.flops_per_token(sl)
                       if hasattr(self.model, "flops_per_token") else 0.0)
                mfu = (tps / n_dev) * fpt / self._peak if fpt else 0.0
                m = TrainMetrics(step=self._step, loss=loss_v,
                                 step_time_s=dt / log_every,
                                 tokens_per_sec=tps,
                                 tokens_per_sec_per_chip=tps / n_dev,
                                 mfu=mfu, lr=self.optimizer.get_lr())
                history.append(m)
                _obs.observe_train_metrics(m)
                self._publish_step_costs(m)
                # SLO sentry (ISSUE 10): rules evaluate at the same log
                # boundary the gauges above were refreshed at — no
                # sentry installed or plane off is a load + branch
                _obs.sentry.maybe_tick()
                if on_metrics:
                    on_metrics(m)
                t_last = time.perf_counter()
                tokens_since = 0
            if guard is not None and guard.preempted:
                if window:
                    it, _ = self._drain_loss_window(window, anomaly, mgr,
                                                    data, it)
                self._preempt_exit(mgr, data)
            if (mgr is not None
                    and self._step % mgr.save_interval_steps == 0
                    and self._step < target):
                if window:
                    # never checkpoint params the guard has not cleared
                    it, rolled = self._drain_loss_window(window, anomaly,
                                                         mgr, data, it)
                    if rolled:
                        continue
                self._save_ckpt(mgr, data)
        if window:
            it, rolled = self._drain_loss_window(window, anomaly, mgr,
                                                 data, it)
            if rolled and self._step < target:
                # rollback at the tail re-enters training for the remainder
                return self._fit_loop(it, target, log_every, on_metrics,
                                      seq_len, history,
                                      time.perf_counter(), 0, loss, mgr=mgr,
                                      anomaly=anomaly, guard=guard,
                                      data=data)
        if guard is not None and guard.preempted:
            self._preempt_exit(mgr, data)
        if mgr is not None:
            self._save_ckpt(mgr, data, async_save=False)
        # write trained params back into the Layer (imperative view);
        # train_step already does this when donation is on
        self.sync_model()
        return history

    # -- superstep loop ------------------------------------------------------

    def _fit_superstep(self, it, target, K, log_every, on_metrics, seq_len,
                       history, mgr=None, anomaly=None, guard=None,
                       data=None):
        """K-steps-per-dispatch loop: stack K batches → ONE compiled scan →
        append the [K] device loss vector to a small in-flight ring. The
        host only fences at boundaries (ring full / log / anomaly window /
        checkpoint / end), so between boundaries the device queue stays
        full and per-step host work is one dict lookup + enqueue."""
        from ..io.dataloader import stack_batches
        self._adopt_offload_flag()
        self._ensure_built()
        ring = []          # (last_step, [ntok per step], device losses [k])
        ring_depth = 2
        state = {"tokens": 0, "steps": 0, "t_last": time.perf_counter(),
                 "sl": seq_len or 1}
        last_saved = self._step
        exhausted = False

        def drain(it):
            """Fetch every pending loss window with ONE host sync, then run
            anomaly verdicts + metric emission in step order."""
            nonlocal exhausted
            if not ring:
                return it, False
            entries = list(ring)
            ring.clear()
            if self._watchdog is not None:
                self._watchdog.tick()    # the fetch below blocks on device
            flat = np.asarray(jnp.concatenate([e[2] for e in entries]))
            if self._watchdog is not None:
                self._watchdog.tick()
            # amortized timing: every step since the last emission shares
            # the wall span [t_last, now] equally — multiple log boundaries
            # inside ONE drain must not each claim a microsecond window
            # (that read as multi-million tokens/sec)
            now = time.perf_counter()
            new_steps = sum(len(e[1]) for e in entries)
            span = max(now - state["t_last"], 1e-9)
            per_step_s = span / max(state["steps"] + new_steps, 1)
            i = 0
            for last_step, ntoks, _ in entries:
                first = last_step - len(ntoks) + 1
                for j, ntok in enumerate(ntoks):
                    step = first + j
                    v = float(flat[i])
                    i += 1
                    if anomaly is not None:
                        verdict = anomaly.check(v)
                        if verdict != "ok":
                            # a rollback rewinds a stateful loader to the
                            # checkpoint cursor — the replay pass may have
                            # batches even if the old iterator ran dry
                            exhausted = False
                            return self._handle_anomaly(
                                verdict, anomaly, mgr, None, data, it,
                                v), True
                    state["tokens"] += ntok
                    state["steps"] += 1
                    if step % log_every == 0:
                        dt = per_step_s * max(state["steps"], 1)
                        tps = state["tokens"] / dt if dt > 0 else 0.0
                        n_dev = jax.device_count()
                        fpt = (self.model.flops_per_token(state["sl"])
                               if hasattr(self.model, "flops_per_token")
                               else 0.0)
                        mfu = (tps / n_dev) * fpt / self._peak if fpt else 0.0
                        sched = self.optimizer.lr_scheduler
                        # the host scheduler mirror has already advanced past
                        # this window — report the LR AT the logged step
                        # (same convention as the per-step loop: lr of
                        # metric.step)
                        lr_at = (float(np.asarray(sched.lr_of(step)))
                                 if sched is not None
                                 else self.optimizer.get_lr())
                        m = TrainMetrics(
                            step=step, loss=v,
                            step_time_s=per_step_s,
                            tokens_per_sec=tps,
                            tokens_per_sec_per_chip=tps / n_dev,
                            mfu=mfu, lr=lr_at)
                        history.append(m)
                        _obs.observe_train_metrics(m)
                        self._publish_step_costs(m, kind="superstep",
                                                 steps_per_exec=K)
                        _obs.sentry.maybe_tick()
                        if on_metrics:
                            on_metrics(m)
                        # advance by the consumed share; the steps after the
                        # last boundary keep their slice of the span
                        state["t_last"] += dt
                        state["tokens"] = 0
                        state["steps"] = 0
            return it, False

        while True:
            if guard is not None and guard.preempted:
                it, _ = drain(it)
                self._preempt_exit(mgr, data)
            if self._step >= target or exhausted:
                it, rolled = drain(it)
                if rolled and self._step < target:
                    # re-anchor the save cadence at the restored step, or
                    # the whole replay window would go uncheckpointed
                    last_saved = self._step
                    continue
                break
            k = min(K, target - self._step)
            batches = []
            try:
                while len(batches) < k:
                    batches.append(next(it))
            except StopIteration:
                exhausted = True
                if not batches:
                    continue
                k = len(batches)   # loader tail: smaller final dispatch
            if self._watchdog is not None:
                self._watchdog.tick()
            ids = batches[-1].get("input_ids")
            if seq_len is None and ids is not None:
                state["sl"] = ids.shape[1]
            ntoks = [int(b["input_ids"].shape[0] * b["input_ids"].shape[1])
                     if b.get("input_ids") is not None else 0
                     for b in batches]
            start = self._step
            sched = self.optimizer.lr_scheduler
            if sched is not None and getattr(sched, "functional", False):
                # LR computed in-jit from the step counter; the stack is a
                # dead scan input (zeros keep the signature K-shaped)
                lr_stack = jnp.zeros((k,), jnp.float32)
            elif sched is not None:
                lr_stack = jnp.asarray(
                    [sched.lr_of(start + i) for i in range(k)], jnp.float32)
            else:
                lr_stack = jnp.full((k,), float(self.optimizer.get_lr()),
                                    jnp.float32)
            stack = stack_batches(batches)
            if self._offload:
                self.opt_state = self._place_opt_state("device")
            self.params, self.opt_state, losses = self._dispatch(
                "superstep", (self.params, self.opt_state, stack, lr_stack,
                              self._key_data()))
            if self._offload:
                self.opt_state = self._place_opt_state("pinned_host")
            self._step += k
            self.dispatch_stats["steps"] += k
            if self._donate:
                self.sync_model()
            if sched is not None:
                for _ in range(k):     # host mirror advances at boundaries
                    sched.step()
            ring.append((self._step, ntoks, losses))
            crossed_log = (self._step // log_every) > (start // log_every)
            if len(ring) >= ring_depth or crossed_log:
                it, rolled = drain(it)
                if rolled:
                    last_saved = self._step
                    continue
            if (mgr is not None and self._step < target
                    and (self._step // mgr.save_interval_steps)
                    > (last_saved // mgr.save_interval_steps)):
                it, rolled = drain(it)   # validate before checkpointing
                last_saved = self._step
                if rolled:
                    continue
                # async: the save enqueues (synchronous device->host
                # snapshot, background serialize/IO) and the NEXT
                # superstep dispatches immediately — the write overlaps
                # compute instead of extending the drain. Commit
                # (PENDING -> _COMMITTED, PR 1 protocol) happens at the
                # manager's next finalize: the following save, a
                # restore, or the sync end-of-fit save below (ISSUE 14).
                self._save_ckpt(mgr, data, async_save=True)
        if guard is not None and guard.preempted:
            self._preempt_exit(mgr, data)
        if mgr is not None:
            self._save_ckpt(mgr, data, async_save=False)
        self.sync_model()
        return history

    # -- resilience runtime --------------------------------------------------

    def _save_ckpt(self, mgr, data, async_save=None):
        """One checkpoint save from the fit loop: traced as a
        trainer::checkpoint span, watermarked in the goodput ledger (the
        anchor a later rollback reclassifies against)."""
        with RecordEvent("trainer::checkpoint"):
            # async_save=None = manager default (same contract as
            # CheckpointManager.save itself)
            mgr.save(self._step, self._ckpt_tree(data),
                     async_save=async_save, watchdog=self._watchdog)
        _obs.ledger().note_checkpoint(self._step)

    def _drain_loss_window(self, window, anomaly, mgr, data, it):
        """Consume a pending (step, device-loss) window with ONE device→host
        sync; returns ``(iterator, rolled_back)``. Verdicts run in step
        order so budgets/EWMA see the same sequence the per-step path
        would."""
        entries = list(window)
        window.clear()
        if self._watchdog is not None:
            self._watchdog.tick()        # the fetch below blocks on device
        vals = np.asarray(jnp.stack([l for _, l in entries]))
        for (s, _), v in zip(entries, vals):
            verdict = anomaly.check(float(v))
            if verdict != "ok":
                return self._handle_anomaly(verdict, anomaly, mgr, None,
                                            data, it, float(v)), True
        return it, False

    def _ckpt_tree(self, data=None):
        """Full training state as one checkpointable tree. The structure is
        FIXED (extra always present, same keys) so the restore target always
        matches the saved layout."""
        sched = self.optimizer.lr_scheduler
        if data is not None and hasattr(data, "state_dict"):
            # the loader's own count: batches actually handed out this pass.
            # NOT self._step — anomaly skips consume a batch without keeping
            # the step, so the two drift apart exactly when resume must not
            # replay the poisoned batch
            cursor = int(data.state_dict().get("batches_served", self._step))
        else:
            cursor = self._step    # 1 batch per step for stateless iterables
        return {
            "step": np.asarray(self._step, np.int64),
            "params": self.params,
            "opt_state": self.opt_state,
            "extra": {
                "sched_last_epoch": np.asarray(
                    sched.last_epoch if sched is not None else -1, np.int64),
                # last_lr as VALUE, not formula: adaptive schedulers
                # (ReduceOnPlateau) cannot recompute it from last_epoch
                "sched_last_lr": np.asarray(
                    sched.last_lr if sched is not None else -1.0, np.float64),
                "data_cursor": np.asarray(cursor, np.int64),
            },
        }

    def _apply_restored(self, tree) -> int:
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        if self._offload:
            self.opt_state = self._place_opt_state("pinned_host")
        self._step = int(np.asarray(tree["step"]))
        sched = self.optimizer.lr_scheduler
        le = int(np.asarray(tree["extra"]["sched_last_epoch"]))
        llr = float(np.asarray(tree["extra"]["sched_last_lr"]))
        if sched is not None and le >= 0:
            # set_state_dict, NOT step(epoch=le): ReduceOnPlateau.step is a
            # no-op without metrics, which would silently reset its decayed
            # LR to the constructor value
            sched.set_state_dict({"last_epoch": le, "last_lr": (
                llr if llr >= 0 else sched.last_lr)})
        self._lr_cache = None     # host LR may have moved: re-sync the scalar
        self._fast_exec = {}      # restored arrays may carry new placements
        self.sync_model()
        return int(np.asarray(tree["extra"]["data_cursor"]))

    def _resume_from(self, mgr, data) -> Optional[int]:
        """resume="auto": restore the newest committed checkpoint (corrupt
        ones are quarantined by the manager and the previous step is used)
        and position the data cursor."""
        res = mgr.restore(self._ckpt_tree(), watchdog=self._watchdog)
        if res is None:
            return None          # nothing saved yet: cold start
        step, tree = res
        cursor = self._apply_restored(tree)
        if hasattr(data, "set_state_dict"):
            data.set_state_dict({"batches_served": cursor})
        return step

    def _preempt_exit(self, mgr, data=None):
        """Step-boundary preemption: one final SYNCHRONOUS checkpoint, then
        exit with the resumable status (the elastic relauncher resumes
        instead of restarting)."""
        from ..resilience.preemption import TrainingPreempted
        # the wind-down books as preemption_lost (minus the nested
        # checkpoint_save span the manager opens for the final save)
        with _obs.ledger().span("preemption_lost"):
            if mgr is not None:
                self._save_ckpt(mgr, data, async_save=False)
            self.sync_model()
            if _obs.REGISTRY.enabled:
                _obs.REGISTRY.counter(
                    "pt_preemptions_total",
                    "orderly SIGTERM checkpoint-and-exit events").inc()
            _obs.flight_recorder.maybe_dump(
                "preemption", extra={"step": self._step})
        raise TrainingPreempted(self._step)

    def _handle_anomaly(self, verdict, anomaly, mgr, prev, data, it, loss):
        """Apply the anomaly verdict; returns the (possibly replaced) data
        iterator."""
        from ..resilience.anomaly import SKIP
        if verdict == SKIP and prev is not None:
            # undo this step's (poisoned) update in memory and move past
            # the batch
            params, opt_state, sched_sd = prev
            self.params, self.opt_state = params, opt_state
            sched = self.optimizer.lr_scheduler
            if sched is not None and sched_sd is not None:
                sched.set_state_dict(sched_sd)
            self._step -= 1
            self.sync_model()
            return it
        if verdict == "abort" or mgr is None:
            # no checkpoint to roll back to (or policy says die): fail loudly
            anomaly.raise_divergence(self._step, loss)
        res = mgr.restore(self._ckpt_tree(), watchdog=self._watchdog)
        if res is None:
            anomaly.raise_divergence(self._step, loss)
        _, tree = res
        cursor = self._apply_restored(tree)
        # productive time since the restored step's watermark is replayed
        # ground: reclassify it as rollback_wasted
        _obs.ledger().note_rollback(self._step)
        if data is not None and hasattr(data, "set_state_dict"):
            # replay from the checkpointed cursor; without a stateful
            # loader the current iterator continues forward (documented:
            # rollback then sees new batches rather than a replay)
            close = getattr(it, "close", None)
            if close is not None:
                close()        # retire the old pass (and its prefetch thread)
            data.set_state_dict({"batches_served": cursor})
            return iter(data)
        return it

    def sync_model(self):
        for k, v in self.params.items():
            self._named[k].value = v

    def state_dict(self):
        return {"params": self.params, "opt_state": self.opt_state,
                "step": self._step}

    def set_state_dict(self, sd):
        self.params = sd["params"]
        self.opt_state = sd["opt_state"]
        self._step = sd["step"]
