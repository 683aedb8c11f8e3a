"""Deterministic elastic training script (subprocess side of ISSUE 15).

``python -m paddle_tpu.testing._elastic_train --ckpt-dir D --steps N
--virtual-devices V --config dp4_tp2 [...]`` trains the llama-micro model
on a virtual-device mesh with the full elastic stack wired in
(ShardingPlan via apply_plan + CheckpointManager(plan=...) +
resume="auto") and prints one machine-readable ``ELASTIC_RESULT {...}``
line. Elastic knobs:

* ``--hard-exit-at K``     — os._exit(137) when step K completes (SIGKILL
  shape: no final checkpoint; a later invocation with fewer
  ``--virtual-devices`` is the scale-in resume);
* ``--plan-auto``          — ask the auto-parallel planner for the best
  legal config on THIS process's devices (``--candidates`` bounds the
  priced set; the chosen config is reported);
* ``--switch-at K --switch-config C`` — the uninterrupted REFERENCE leg:
  at step K a WorldSizeChanged is raised in-process and
  ``ElasticManager.run_elastic`` re-plans onto ``C`` (fewer devices of
  the same process) and re-enters ``fit(resume="auto")`` through the
  resharded restore — the same mesh schedule as a killed+resumed run,
  with no process death. Chaos-vs-reference loss comparison is therefore
  about the kill/restore machinery alone, not cross-mesh numerics.

Per-attempt segments (config, world size, steps, losses) ride in the
result so tests can assert bit-exactness modulo the batch schedule.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys


def _parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--save-interval", type=int, default=4)
    p.add_argument("--async-save", action="store_true")
    p.add_argument("--virtual-devices", type=int, default=None)
    p.add_argument("--config", default="dp4_tp2")
    p.add_argument("--plan-auto", action="store_true")
    p.add_argument("--candidates", default="")
    p.add_argument("--switch-at", type=int, default=None)
    p.add_argument("--switch-config", default="dp2_tp2")
    p.add_argument("--switch-devices", type=int, default=None)
    p.add_argument("--hard-exit-at", type=int, default=None)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=16)
    p.add_argument("--probe-reshard", action="store_true",
                   help="no training: run the timed mini reshard cycle "
                        "and print ELASTIC_PROBE {json} (bench rows)")
    return p.parse_args(argv)


def micro_config():
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(vocab_size=320, hidden_size=64, intermediate_size=96,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=128)


def build_data(global_batch: int, seq_len: int, steps: int):
    import numpy as np
    from paddle_tpu.io import DataLoader, TensorDataset
    rs = np.random.RandomState(1234)
    toks = rs.randint(0, 320, (global_batch * (steps + 4), seq_len + 1))
    ds = TensorDataset([toks.astype(np.int64)])
    return DataLoader(ds, batch_size=global_batch, shuffle=False,
                      drop_last=True,
                      collate_fn=lambda items: {
                          "input_ids": np.stack([i[0][:-1] for i in items]),
                          "labels": np.stack([i[0][1:] for i in items])})


class ShardedLoader:
    """Wrap a DataLoader: place each batch per the CURRENT plan (the
    holder is swapped on a mesh switch so later batches land on the new
    mesh) and forward the cursor protocol so resume fast-forwards."""

    def __init__(self, inner, holder):
        self.inner = inner
        self.holder = holder      # dict with "plan" and "mesh"

    def __iter__(self):
        for b in self.inner:
            yield self.holder["plan"].shard_batch(b, self.holder["mesh"])

    def state_dict(self):
        return self.inner.state_dict()

    def set_state_dict(self, sd):
        return self.inner.set_state_dict(sd)


def params_digest(tree) -> str:
    import numpy as np
    import jax
    from jax.tree_util import tree_flatten_with_path
    h = hashlib.sha256()
    leaves, _ = tree_flatten_with_path(tree)
    for path, x in sorted(leaves, key=lambda kv: str(kv[0])):
        h.update(str(path).encode())
        h.update(np.ascontiguousarray(
            np.asarray(jax.device_get(x))).tobytes())
    return h.hexdigest()[:16]


def pick_plan(args, mcfg, devices):
    """Explicit config, or the planner over the candidate set."""
    from paddle_tpu.distributed.auto_parallel import (
        ParallelConfig, plan as ap_plan, plan_for_config)
    if not args.plan_auto:
        cfg = ParallelConfig.parse(args.config)
        return plan_for_config(mcfg, cfg, devices=devices)
    cand = ([ParallelConfig.parse(s) for s in args.candidates.split(",")
             if s.strip()] or None)
    report = ap_plan(mcfg, devices=devices, global_batch=args.global_batch,
                     seq_len=args.seq_len, configs=cand, drift="ignore")
    return report.chosen.plan


def reshard_probe() -> dict:
    """Timed mini elastic cycle (``--probe-reshard``): llama-micro
    state checkpointed every 4 steps under the largest feasible dp×tp
    plan, a SIGKILL-shape death at step 6, resharded restore onto HALF
    the devices. ``elastic_reshard_seconds`` is the verify+reshard+place
    wall time; ``elastic_resume_steps_replayed`` is killed_step −
    restored_step (the work the save cadence forfeits, 2 here by
    construction — a regression means the cadence or the fallback
    broke)."""
    import shutil
    import tempfile
    import time

    import numpy as np
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import shard_optimizer_state
    from paddle_tpu.resilience import CheckpointManager
    from paddle_tpu.distributed.auto_parallel import (ParallelConfig,
                                                      plan_for_config)

    devs = jax.devices()
    n = 1
    while n * 2 <= len(devs):
        n *= 2
    if n < 2:
        raise RuntimeError(f"reshard probe needs >=2 devices, have "
                           f"{len(devs)}")
    src_cfg = (ParallelConfig(dp=n // 2, tp=2) if n >= 4
               else ParallelConfig(dp=2, tp=1))
    dst_cfg = (ParallelConfig(dp=n // 4, tp=2) if n >= 8
               else ParallelConfig(dp=1, tp=2) if n >= 4
               else ParallelConfig(dp=1, tp=1))
    mcfg = micro_config()
    pt.seed(0)
    model = LlamaForCausalLM(mcfg)
    src = plan_for_config(mcfg, src_cfg, devices=devs[:n])
    with src.apply(model):
        opt = AdamW(learning_rate=1e-3, parameters=model)
        params = {k: p.value for k, p in model.named_parameters()}
        opt_state = shard_optimizer_state(opt.init_state(params),
                                          src.param_specs)
    tree = {"step": np.asarray(0, np.int64), "params": params,
            "opt_state": opt_state}

    root = tempfile.mkdtemp(prefix="pt_reshard_probe_")
    try:
        mgr = CheckpointManager(root, save_interval_steps=4,
                                keep_last_n=2, plan=src)
        killed_at = 6
        for s in range(1, killed_at + 1):   # trainer cadence: step 4 only
            if s % mgr.save_interval_steps == 0:
                mgr.save(s, tree)
        dst = plan_for_config(mcfg, dst_cfg, devices=devs[:n // 2])
        hm = dst.build_mesh(devices=devs[:n // 2])
        t0 = time.perf_counter()
        mgr2 = CheckpointManager(root, plan=dst, mesh=hm.mesh)
        restored = mgr2.restore(tree)
        dt = time.perf_counter() - t0
        assert restored is not None
        return {"elastic_reshard_seconds": round(dt, 4),
                "elastic_resume_steps_replayed": killed_at - restored[0],
                "elastic_probe_configs": f"{src.config_str}"
                                         f"->{dst.config_str}"}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main(argv=None) -> int:
    args = _parse_args(argv)
    if args.virtual_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.virtual_devices}").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.probe_reshard:
        print("ELASTIC_PROBE " + json.dumps(reshard_probe()), flush=True)
        return 0

    import jax
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.resilience import CheckpointManager
    from paddle_tpu.distributed.elastic import (ElasticManager,
                                                WorldSizeChanged)
    from paddle_tpu.distributed.auto_parallel import plan_for_config, \
        ParallelConfig

    mcfg = micro_config()
    pt.seed(0)
    model = LlamaForCausalLM(mcfg)
    trainer = Trainer(model, AdamW(learning_rate=1e-3, parameters=model),
                      donate=False)
    loader = build_data(args.global_batch, args.seq_len, args.steps)

    devices = list(jax.devices())
    holder = {"plan": None, "mesh": None}
    data = ShardedLoader(loader, holder)
    segments = []

    def train_leg(attempt: int, world_size: int) -> None:
        if attempt == 0 and not args.switch_at:
            plan = pick_plan(args, mcfg, devices[:world_size])
        elif attempt == 0:
            plan = plan_for_config(mcfg, ParallelConfig.parse(args.config),
                                   devices=devices[:world_size])
        else:
            # post-switch leg of the reference run: the agreed smaller
            # config on the surviving devices
            plan = plan_for_config(
                mcfg, ParallelConfig.parse(args.switch_config),
                devices=devices[:world_size])
        hm = trainer.apply_plan(plan, devices=devices[:world_size])
        holder["plan"], holder["mesh"] = plan, hm
        mgr = CheckpointManager(args.ckpt_dir,
                                save_interval_steps=args.save_interval,
                                keep_last_n=4, async_save=args.async_save)
        seg = {"attempt": attempt, "world_size": world_size,
               "config": plan.config_str, "steps": [], "losses": []}
        segments.append(seg)

        def cb(m):
            seg["steps"].append(int(m.step))
            seg["losses"].append(float(m.loss))
            if (args.hard_exit_at is not None
                    and m.step >= args.hard_exit_at):
                os._exit(137)
            if (args.switch_at is not None and attempt == 0
                    and m.step > args.switch_at):
                raise WorldSizeChanged(world_size,
                                       args.switch_devices
                                       or world_size // 2)

        with hm:
            trainer.fit(data, steps=args.steps, log_every=1,
                        on_metrics=cb, checkpoint_manager=mgr,
                        resume="auto")

    if args.switch_at is not None:
        em = ElasticManager(np=1, heartbeat_timeout=60.0)
        schedule = iter([len(devices),
                         args.switch_devices or len(devices) // 2])
        last = [len(devices)]

        def ws_fn():
            try:
                last[0] = next(schedule)
            except StopIteration:
                pass
            return last[0]

        ok = em.run_elastic(train_leg, world_size_fn=ws_fn,
                            sleep=lambda _s: None)
        em.exit()
        assert ok, "reference elastic run did not complete"
    else:
        train_leg(0, len(devices))

    tree = {"params": trainer.params, "opt_state": trainer.opt_state}
    print("ELASTIC_RESULT " + json.dumps({
        "step": trainer._step,
        "devices": len(devices),
        "segments": segments,
        "digest": params_digest(tree),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
