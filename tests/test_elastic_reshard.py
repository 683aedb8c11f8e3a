"""Elastic scale-in/out tests (ISSUE 15): checkpoint resharding across
ShardingPlans, membership-change flow, startup torn-dir hygiene, the
reshard CLI, the elastic sentry pack — and the end-to-end chaos proof
(real subprocess SIGKILL on a dp4×tp2 virtual mesh, planner-picked resume
on dp2×tp2, bit-exact modulo batch schedule).

All meshes are virtual CPU devices (conftest forces 8)."""

import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu.distributed.auto_parallel import (ParallelConfig,
                                                  plan_for_config)
from paddle_tpu.distributed.elastic import (ElasticManager,
                                            WorldSizeChanged)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.optimizer import AdamW
from paddle_tpu.parallel import shard_optimizer_state
from paddle_tpu.resilience import (CheckpointManager, ReshardError,
                                   reshard)
from paddle_tpu.testing import chaos

chaosmark = pytest.mark.chaos

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")

CFG_8 = ParallelConfig(dp=4, tp=2)
CFG_4 = ParallelConfig(dp=2, tp=2)


def micro_cfg():
    return LlamaConfig(vocab_size=320, hidden_size=64, intermediate_size=96,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=128)


def make_state(plan, step=4):
    """Llama-micro params + AdamW slots placed per ``plan``."""
    pt.seed(0)
    model = LlamaForCausalLM(micro_cfg())
    hm = plan.apply(model)
    with hm:
        opt = AdamW(learning_rate=1e-3, parameters=model)
        params = {k: p.value for k, p in model.named_parameters()}
        opt_state = shard_optimizer_state(opt.init_state(params),
                                          plan.param_specs)
    return {"step": np.asarray(step, np.int64), "params": params,
            "opt_state": opt_state}, hm


def digest(tree):
    """sha256 over params + optimizer slots (placement-independent)."""
    from jax.tree_util import tree_flatten_with_path
    h = hashlib.sha256()
    sub = {"params": tree["params"], "opt_state": tree["opt_state"]}
    leaves, _ = tree_flatten_with_path(sub)
    for path, x in sorted(leaves, key=lambda kv: str(kv[0])):
        h.update(str(path).encode())
        h.update(np.ascontiguousarray(
            np.asarray(jax.device_get(x))).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def plans():
    return (plan_for_config(micro_cfg(), CFG_8),
            plan_for_config(micro_cfg(), CFG_4))


# ---------------------------------------------------------------------------
# _PLAN.json sidecar
# ---------------------------------------------------------------------------

def test_plan_sidecar_recorded_hashed_and_surfaced(tmp_path, plans):
    """save() records the active plan inside the step dir, the manifest
    hashes it (tamper ⇒ verify fails), restore surfaces it."""
    plan8, _ = plans
    tree, _hm = make_state(plan8)
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=1, plan=plan8)
    mgr.save(4, tree)
    pf = os.path.join(mgr.step_dir(4), reshard.PLAN_NAME)
    assert os.path.isfile(pf)
    man = json.load(open(os.path.join(mgr.step_dir(4), "_MANIFEST.json")))
    assert reshard.PLAN_NAME in man["files"]
    assert mgr.verify(4)
    saved = reshard.read_plan(mgr.step_dir(4))
    assert saved is not None and saved.axes["dp"] == 4

    got = mgr.restore(tree)
    assert got is not None and got[0] == 4
    assert mgr.last_restored_plan.config_str == plan8.config_str

    # tampering with the recorded plan breaks the manifest like any file
    with open(pf, "a") as f:
        f.write(" ")
    assert not mgr.verify(4)


def test_plan_sidecar_null_for_implicit_single_device(tmp_path):
    """No plan ⇒ the sidecar still exists and records the implicit
    single-device layout as null; read_plan returns None."""
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=1)
    mgr.save(1, {"w": np.ones((4, 4), np.float32)})
    payload = json.load(open(os.path.join(mgr.step_dir(1),
                                          reshard.PLAN_NAME)))
    assert payload["implicit_single_device"] is True
    assert payload["plan"] is None
    assert reshard.read_plan(mgr.step_dir(1)) is None


# ---------------------------------------------------------------------------
# resharded restore
# ---------------------------------------------------------------------------

def test_reshard_roundtrip_8_4_8_digest_exact(tmp_path, plans):
    """dp4×tp2 → dp2×tp2 → dp4×tp2: parameter + optimizer trees come back
    digest-exact, and each hop places per the target plan's specs."""
    plan8, plan4 = plans
    tree, _hm8 = make_state(plan8)
    d0 = digest(tree)

    root_a = str(tmp_path / "a")
    mgr = CheckpointManager(root_a, save_interval_steps=1, plan=plan8)
    mgr.save(4, tree)

    hm4 = plan4.build_mesh()
    mgr4 = CheckpointManager(root_a, plan=plan4, mesh=hm4.mesh)
    s, tree4 = mgr4.restore(tree)
    assert s == 4
    assert mgr4.last_restored_plan.config_str == plan8.config_str
    assert digest(tree4) == d0

    # placement followed the TARGET plan — params and optimizer slots
    name = next(k for k, v in plan4.param_specs.items()
                if any(e is not None for e in tuple(v)))
    spec = plan4.param_specs[name]
    assert tree4["params"][name].sharding.spec == spec
    assert tree4["opt_state"]["slots"][name]["m"].sharding.spec == spec

    root_b = str(tmp_path / "b")
    mgr_b = CheckpointManager(root_b, save_interval_steps=1, plan=plan4)
    mgr_b.save(4, tree4)
    hm8 = plan8.build_mesh()
    mgr8 = CheckpointManager(root_b, plan=plan8, mesh=hm8.mesh)
    s, tree8 = mgr8.restore(tree)
    assert s == 4
    assert digest(tree8) == d0
    assert tree8["params"][name].sharding.spec == plan8.param_specs[name]


def test_reshard_fsdp_boundary_roundtrip_digest_exact(tmp_path):
    """ISSUE 18: dp2×fsdp2 → dp4 → dp2×fsdp2 across 4 devices. A ZeRO
    checkpoint (params AND AdamW slots fsdp-sharded) restores under a
    pure-dp plan digest-exact — the fsdp axis rides the same _PLAN.json
    sidecar machinery as every other axis — and comes back fsdp-sharded
    on the return hop."""
    plan_z = plan_for_config(micro_cfg(), ParallelConfig(dp=2, fsdp=2),
                             devices=jax.devices()[:4])
    plan_d = plan_for_config(micro_cfg(), ParallelConfig(dp=4),
                             devices=jax.devices()[:4])
    assert plan_z.axes.get("fsdp") == 2
    tree, _hm = make_state(plan_z)
    d0 = digest(tree)

    root_a = str(tmp_path / "a")
    CheckpointManager(root_a, save_interval_steps=1, plan=plan_z).save(
        4, tree)
    hmd = plan_d.build_mesh()
    mgr_d = CheckpointManager(root_a, plan=plan_d, mesh=hmd.mesh)
    s, tree_d = mgr_d.restore(tree)
    assert s == 4 and digest(tree_d) == d0
    # under pure dp the params replicate — no fsdp axis left in any spec
    name = next(k for k, v in plan_z.param_specs.items()
                if "fsdp" in str(v))
    assert "fsdp" not in str(tree_d["params"][name].sharding.spec)

    root_b = str(tmp_path / "b")
    CheckpointManager(root_b, save_interval_steps=1, plan=plan_d).save(
        4, tree_d)
    hmz = plan_z.build_mesh()
    mgr_z = CheckpointManager(root_b, plan=plan_z, mesh=hmz.mesh)
    s, tree_z = mgr_z.restore(tree)
    assert s == 4 and digest(tree_z) == d0
    # params AND optimizer slots landed fsdp-sharded per the target plan
    spec = plan_z.param_specs[name]
    assert tree_z["params"][name].sharding.spec == spec
    assert tree_z["opt_state"]["slots"][name]["m"].sharding.spec == spec


def test_reshard_check_feasible_names_fsdp_on_indivisible_shrink(
        tmp_path):
    """An fsdp target that does not divide the hidden dim (64 % 3) is
    rejected up front with ReshardError naming the fsdp axis and the
    remainder — not a GSPMD crash after bytes moved."""
    plan_z = plan_for_config(micro_cfg(), ParallelConfig(dp=2, fsdp=2),
                             devices=jax.devices()[:4])
    tree, _hm = make_state(plan_z)
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=1,
                            plan=plan_z)
    mgr.save(4, tree)

    plan3 = plan_for_config(micro_cfg(), ParallelConfig(dp=1, fsdp=3),
                            devices=jax.devices()[:3])
    mgr3 = CheckpointManager(str(tmp_path), plan=plan3)
    with pytest.raises(ReshardError) as ei:
        mgr3.restore(tree)
    msg = str(ei.value)
    assert "fsdp=3" in msg and "remainder" in msg


def test_reshard_rejects_uneven_axis_with_actionable_error(tmp_path, plans):
    """tp-shrink onto tp=3 (does not divide heads/hidden): ReshardError
    names the axis, the parameter, and the remainder — and does NOT fall
    back to an older step (infeasibility is permanent)."""
    plan8, _ = plans
    tree, _hm = make_state(plan8)
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=1, plan=plan8)
    mgr.save(4, tree)

    plan3 = plan_for_config(micro_cfg(), ParallelConfig(dp=1, tp=3),
                            devices=jax.devices()[:3])
    mgr3 = CheckpointManager(str(tmp_path), plan=plan3)
    with pytest.raises(ReshardError) as ei:
        mgr3.restore(tree)
    msg = str(ei.value)
    assert "tp=3" in msg and "remainder" in msg


@chaosmark
def test_corrupt_shard_mid_reshard_quarantines_and_falls_back(
        tmp_path, plans):
    """Bit-rot in the newest step discovered on a scale-in restore: the
    step is quarantined and the PREVIOUS committed step is resharded
    instead — degrade, don't die."""
    plan8, plan4 = plans
    tree, _hm = make_state(plan8)
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=1,
                            keep_last_n=4, plan=plan8)
    mgr.save(4, tree)
    mgr.save(8, tree)
    chaos.corrupt_checkpoint(mgr.step_dir(8), mode="flip")

    hm4 = plan4.build_mesh()
    mgr4 = CheckpointManager(str(tmp_path), plan=plan4, mesh=hm4.mesh)
    s, tree4 = mgr4.restore(tree)
    assert s == 4                                   # fell back
    assert digest(tree4) == digest(tree)
    assert any("step_8" in q for q in mgr4.quarantined())


def test_opt_slot_leaves_reshard_via_component_match(tmp_path, plans):
    """checkpoint._target_like matches spec keys against enclosing path
    components, so ``slots/<param>/m`` inherits the param's spec instead
    of silently replicating."""
    plan8, plan4 = plans
    tree, _hm = make_state(plan8)
    from paddle_tpu import checkpoint as ckpt
    path = str(tmp_path / "raw")
    ckpt.save_state_dict(tree, path)
    hm4 = plan4.build_mesh()
    out = ckpt.load_state_dict(path, tree, mesh=hm4.mesh,
                               spec_tree=dict(plan4.param_specs))
    name = next(k for k, v in plan4.param_specs.items()
                if any(e is not None for e in tuple(v)))
    assert out["opt_state"]["slots"][name]["v"].sharding.spec \
        == plan4.param_specs[name]


# ---------------------------------------------------------------------------
# startup torn-dir hygiene
# ---------------------------------------------------------------------------

def test_sweep_cleans_torn_async_dirs_with_one_warning(tmp_path):
    """A SIGKILL mid-async-save leaves an orbax tmp dir (never renamed)
    and possibly a bare torn step dir. Construction quarantines the
    non-empty ones, deletes the empty ones, and warns ONCE — they are
    cleaned, not just skipped by latest_step."""
    root = str(tmp_path)
    mgr = CheckpointManager(root, save_interval_steps=1)
    mgr.save(2, {"w": np.ones((2, 2), np.float32)})

    torn_tmp = os.path.join(root, "step_7.orbax-checkpoint-tmp-1234")
    os.makedirs(torn_tmp)
    with open(os.path.join(torn_tmp, "shard.bin"), "wb") as f:
        f.write(b"\x00" * 64)
    torn_bare = os.path.join(root, "step_9")
    os.makedirs(torn_bare)
    with open(os.path.join(torn_bare, "partial"), "wb") as f:
        f.write(b"\x01" * 16)
    empty = os.path.join(root, "step_11")
    os.makedirs(empty)

    with pytest.warns(RuntimeWarning, match="torn"):
        mgr2 = CheckpointManager(root)
    assert not os.path.exists(torn_tmp)
    assert not os.path.exists(torn_bare)
    assert not os.path.exists(empty)                # empty ⇒ deleted
    qs = mgr2.quarantined()
    assert any("step_7" in q for q in qs)
    assert any("step_9" in q for q in qs)
    assert mgr2.committed_steps() == [2]            # survivors untouched

    # idempotent: a second construction finds nothing and stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        mgr3 = CheckpointManager(root)
    assert mgr3.committed_steps() == [2]


# ---------------------------------------------------------------------------
# membership-change flow
# ---------------------------------------------------------------------------

def test_run_elastic_membership_change_spares_restart_budget():
    """A WorldSizeChanged unwind re-enters with the new world size after
    a full-jitter backoff — consuming membership-change budget, never
    the failure-restart budget."""
    em = ElasticManager(np=1, max_restarts=0, heartbeat_timeout=60.0)
    try:
        sizes = iter([8, 4])
        cur = [8]

        def ws_fn():
            try:
                cur[0] = next(sizes)
            except StopIteration:
                pass
            return cur[0]

        calls = []
        slept = []

        def train(attempt, ws):
            calls.append((attempt, ws))
            if len(calls) == 1:
                raise WorldSizeChanged(8, 4)

        ok = em.run_elastic(train, world_size_fn=ws_fn,
                            sleep=slept.append)
        assert ok
        assert calls == [(0, 8), (1, 4)]
        assert em.restarts == 0                     # budget untouched
        assert len(slept) == 1 and slept[0] >= 0.0  # jittered backoff ran
    finally:
        em.exit()


def test_run_elastic_gives_up_after_membership_budget():
    em = ElasticManager(np=1, heartbeat_timeout=60.0)
    try:
        flip = [0]

        def ws_fn():
            flip[0] += 1
            return 8 if flip[0] % 2 else 4

        def train(attempt, ws):
            raise WorldSizeChanged(ws, 12 - ws)

        ok = em.run_elastic(train, world_size_fn=ws_fn,
                            max_membership_changes=3,
                            sleep=lambda _s: None)
        assert ok is False
    finally:
        em.exit()


def test_membership_probe_raises_on_disagreement():
    em = ElasticManager(np=1, heartbeat_timeout=60.0)
    try:
        em._register_keys()
        assert em.world_size() == 1
        em.membership_probe(expected=1)()           # agrees: no raise
        with pytest.raises(WorldSizeChanged) as ei:
            em.membership_probe(expected=2)()
        assert ei.value.old_size == 2 and ei.value.new_size == 1
    finally:
        em.exit()


# ---------------------------------------------------------------------------
# sentry pack
# ---------------------------------------------------------------------------

def test_elastic_rules_fire_on_flapping_and_reshard_failure():
    from paddle_tpu.observability import sentry as sn
    from paddle_tpu.observability.metrics import REGISTRY
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        rules = sn.elastic_rules(membership_changes_per_window=2.0,
                                 reshard_failures_per_window=0.0,
                                 world_size_floor=4.0,
                                 breach_for=1, cooldown_s=0.0)
        s = sn.SloSentry(rules)
        ch = REGISTRY.counter("pt_elastic_membership_changes_total", "t")
        rf = REGISTRY.counter("pt_elastic_reshard_failures_total", "t")
        ws = REGISTRY.gauge("pt_elastic_world_size", "t")
        ch.inc(); rf.inc(0.0); ws.set(8.0)
        assert s.tick(now=1.0) == []                # delta anchors
        for _ in range(3):
            ch.inc()                                # 3 changes > ceiling 2
        rf.inc()                                    # any failure pages
        ws.set(2.0)                                 # below floor 4
        fired = {i.rule for i in s.tick(now=2.0)}
        assert fired == {"elastic_membership_change_rate",
                         "elastic_reshard_failures",
                         "elastic_world_size_floor"}
    finally:
        REGISTRY.reset()


# ---------------------------------------------------------------------------
# tools/reshard.py CLI
# ---------------------------------------------------------------------------

def _cli(argv):
    sys.path.insert(0, TOOLS)
    try:
        import reshard as reshard_cli
        return reshard_cli.main(argv)
    finally:
        sys.path.remove(TOOLS)


def test_reshard_cli_dry_run_and_write(tmp_path, plans, capsys):
    plan8, plan4 = plans
    tree, _hm = make_state(plan8)
    root = str(tmp_path / "src")
    CheckpointManager(root, save_interval_steps=1, plan=plan8).save(4, tree)

    assert _cli(["--from", root, "--mesh", "2x2", "--dry-run"]) == 0
    said = capsys.readouterr().out
    assert "feasible" in said
    # a walk that matched no leaf would approve any target
    assert int(re.search(r"(\d+) sharded leaves", said).group(1)) > 0

    out = str(tmp_path / "dst")
    assert _cli(["--from", root, "--mesh", "2x2", "--out", out]) == 0
    step_dir = os.path.join(out, "step_4")
    assert os.path.isfile(os.path.join(step_dir, "_COMMITTED"))
    rewritten = reshard.read_plan(step_dir)
    assert rewritten.axes["dp"] == 2 and rewritten.axes["tp"] == 2

    # the rewritten checkpoint restores digest-exact under the new plan
    hm4 = plan4.build_mesh()
    mgr = CheckpointManager(out, plan=plan4, mesh=hm4.mesh)
    s, tree4 = mgr.restore(tree)
    assert s == 4 and digest(tree4) == digest(tree)


def test_reshard_cli_infeasible_target_exits_2(tmp_path, plans, capsys):
    plan8, _ = plans
    tree, _hm = make_state(plan8)
    root = str(tmp_path)
    CheckpointManager(root, save_interval_steps=1, plan=plan8).save(4, tree)
    assert _cli(["--from", root, "--config", "dp1_tp3", "--dry-run"]) == 2
    assert "tp=3" in capsys.readouterr().err
    # more devices than exist is infeasible too
    assert _cli(["--from", root, "--mesh", "8x4", "--dry-run"]) == 2


def test_reshard_cli_refuses_planless_source_exit_2(tmp_path, capsys):
    root = str(tmp_path)
    CheckpointManager(root, save_interval_steps=1).save(
        1, {"w": np.ones((4, 4), np.float32)})
    assert _cli(["--from", root, "--mesh", "2x2", "--dry-run"]) == 2
    assert "no recorded ShardingPlan" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# end-to-end chaos proof (acceptance)
# ---------------------------------------------------------------------------

def _run_elastic_child(ckpt_dir, *, devices, extra):
    proc = chaos.spawn_elastic(ckpt_dir, steps=12,
                               virtual_devices=devices, extra_args=extra)
    out, _ = proc.communicate(timeout=420)
    text = out.decode()
    result = None
    for line in text.splitlines():
        if line.startswith("ELASTIC_RESULT "):
            result = json.loads(line[len("ELASTIC_RESULT "):])
    return proc.returncode, result, text


@chaosmark
def test_e2e_elastic_scale_in_bit_exact(tmp_path):
    """The ISSUE 15 acceptance flow. Train llama-micro on a dp4×tp2
    virtual mesh, checkpoint at step 4, SIGKILL-shape death at step 6
    (real subprocess, exit code 137), resume in a FRESH process that only
    has 4 virtual devices: the planner picks dp2×tp2 over the candidate
    set, the restore reshards against the recorded plan, and steps 5..12
    replay + continue. The reference run performs the SAME mesh schedule
    (voluntary in-process switch at step 4 through run_elastic +
    WorldSizeChanged) with no kill — so the comparison isolates the
    kill/restore machinery: losses must be BIT-exact, digests equal."""
    ref_dir = str(tmp_path / "ref")
    rc, ref, text = _run_elastic_child(
        ref_dir, devices=8,
        extra=["--config", "dp4_tp2", "--save-interval", "4",
               "--switch-at", "4", "--switch-config", "dp2_tp2",
               "--switch-devices", "4"])
    assert rc == 0, text
    assert [s["config"] for s in ref["segments"]] \
        == ["dp4_tp2_pp1_sep1", "dp2_tp2_pp1_sep1"]

    chaos_dir = str(tmp_path / "chaos")
    rc, res, text = _run_elastic_child(
        chaos_dir, devices=8,
        extra=["--config", "dp4_tp2", "--save-interval", "4",
               "--hard-exit-at", "6"])
    assert rc == 137, text                          # exit-code contract
    assert res is None                              # died before printing
    committed = [d for d in os.listdir(chaos_dir)
                 if d == "step_4"]
    assert committed, os.listdir(chaos_dir)

    rc, res, text = _run_elastic_child(
        chaos_dir, devices=4,
        extra=["--save-interval", "4", "--plan-auto",
               "--candidates", "dp2_tp2,dp1_tp2"])
    assert rc == 0, text
    seg = res["segments"][0]
    assert seg["config"] == "dp2_tp2_pp1_sep1"      # planner-picked
    assert seg["steps"][0] == 5                     # resumed from step 4
    assert res["step"] == 12

    # bit-exact modulo batch schedule: every post-switch step's loss in
    # the killed+resumed run equals the uninterrupted reference's
    ref_post = {s: l for s, l in zip(ref["segments"][1]["steps"],
                                     ref["segments"][1]["losses"])}
    got_post = {s: l for s, l in zip(seg["steps"], seg["losses"])}
    assert got_post == ref_post
    assert res["digest"] == ref["digest"]
