"""Graph contracts (ISSUE 8): the static-analysis subsystem over lowered
jaxpr/HLO artifacts.

What is pinned here:

* the HLO parser (aliasing tables with nested braces, /*index*/ comments,
  tuple shapes, attribute extraction) on synthetic + real dumps;
* the materialization analyzer catches a naive logits matmul and stays
  silent on the fused head (ONE definition, shared with
  test_fused_vocab_ce's HLO guard);
* the donation audit: trainer params/opt_state and serving pools/history
  ARE donated, and DELIBERATELY un-donating the history carry makes the
  contract fail with the history named in the message (ISSUE 8
  acceptance);
* deliberately breaking the materialization budget (PT_NAIVE_LOSS_HEAD=1)
  fails the train-step contract with the offending buffers listed
  (ISSUE 8 acceptance);
* collective census on parallel_fused_linear_cross_entropy under a
  dp=2 x tp=2 CPU mesh: exactly one pmax + two psum all-reduces over tp,
  zero all-gathers (an implicit GSPMD reshard would add one);
* trace_lint rules + inline waivers + the false-positive guards
  (tree.map is not lax.map, `def run(self)` is not the jitted `run`);
* tools/graph_lint.py runs green in-process against the checked-in
  budgets (the tier-1 gate, like tools/obs_smoke.py);
* compile_cache explains WHY a fingerprint changed (labeled parts diff,
  stale-AOT-artifact warning naming the drifted key).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.analysis as A
from paddle_tpu.analysis import trace_lint

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


# -- parser ------------------------------------------------------------------

_SYNTH = """\
HloModule jit_f, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias), {2}: (3, {}, must-alias) }, entry_computation_layout={(f32[4,8]{1,0})->f32[4,8]{1,0}}

%fused_computation (param_0.2: f32[4,8]) -> f32[] {
  %param_0.2 = f32[4,8]{1,0} parameter(0)
  %multiply.0 = f32[4,8]{1,0} multiply(f32[4,8]{1,0} %param_0.2, f32[4,8]{1,0} %param_0.2)
  ROOT %reduce.0 = f32[] reduce(f32[4,8]{1,0} %multiply.0, f32[] %multiply.0), dimensions={0,1}, to_apply=%region_0.6
}

ENTRY %main.12 (Arg_0.1: f32[4,8], Arg_1.2: s32[2]) -> (f32[4,8], f32[], s32[2]) {
  %Arg_0.1 = f32[4,8]{1,0} parameter(0), metadata={op_name="x"}
  %Arg_1.2 = s32[2]{0} parameter(1), metadata={op_name="state[\\'k\\']"}
  %ar = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %Arg_0.1), channel_id=1, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%region_0.6, metadata={op_name="jit(f)/psum"}
  %cc = () custom-call(f32[4,8]{1,0} %ar), custom_call_target="xla_python_cpu_callback"
  ROOT %tuple.9 = (f32[4,8]{1,0}, f32[], /*index=2*/s32[2]{0}) tuple(f32[4,8]{1,0} %ar, f32[] %ar, s32[2]{0} %Arg_1.2)
}
"""


def test_parser_synthetic_module():
    mod = A.parse_hlo(_SYNTH)
    # aliasing: nested-brace table parsed, both kinds
    assert [(a.output_index, a.param_number, a.kind) for a in mod.aliases] \
        == [((0,), 0, "may-alias"), ((2,), 3, "must-alias")]
    # params labeled from op_name metadata (escapes stripped)
    assert mod.param_label(0) == "x"
    assert mod.param_label(1) == "state['k']"
    # ROOT tuple with /*index=N*/ comments: all three output leaves seen
    assert [str(s) for s in mod.entry_output_shapes] \
        == ["f32[4,8]", "f32[]", "s32[2]"]
    # attributes: brace-balanced replica_groups, quoted call target
    ar = mod.find("all-reduce")[0]
    assert ar.attr("replica_groups") == "{{0,1},{2,3}}"
    assert ar.attr("channel_id") == "1"
    cc = mod.find("custom-call")[0]
    assert cc.attr("custom_call_target") == "xla_python_cpu_callback"
    # fusion-internal instructions enumerated too
    assert any(i.computation == "fused_computation"
               for i in mod.instructions)


def test_transfer_detector_on_synthetic():
    rep = A.host_transfer_report(A.parse_hlo(_SYNTH))
    assert rep["host_transfer_count"] == 1
    assert "xla_python_cpu_callback" in rep["host_callbacks"][0]


def test_real_callback_detected():
    from jax.experimental import io_callback

    def f(x):
        y = x * 2
        io_callback(lambda v: None, None, y)
        return y.sum()

    txt = jax.jit(f).lower(jnp.ones((4,))).compile().as_text()
    rep = A.host_transfer_report(A.parse_hlo(txt))
    assert rep["host_transfer_count"] >= 1


# -- materialization ---------------------------------------------------------

def test_materialization_ban_catches_naive_not_fused():
    """The generalized _bsv_buffers: a naive logits+log_softmax graph
    trips the rule; the fused blockwise head does not. ONE detector for
    the fused-CE test, the train-step contract and graph_lint."""
    from paddle_tpu.ops.pallas.fused_vocab_ce import (
        fused_linear_cross_entropy)
    N, H, V = 48, 16, 640
    rule = A.BanRule(V, N, label="logits")
    h = jnp.zeros((N, H), jnp.float32)
    w = jnp.zeros((H, V), jnp.float32)
    lab = jnp.zeros((N,), jnp.int32)

    def naive(h, w):
        logits = (h @ w).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, lab[:, None], axis=-1).mean()

    naive_txt = jax.jit(naive).lower(h, w).compile().as_text()
    hits = A.banned_buffers(A.parse_hlo(naive_txt), [rule])
    assert hits, "naive head must materialize [N, V]"
    assert all(hit.bytes == N * V * 4 for hit in hits[:1])

    def fused(h, w):
        return fused_linear_cross_entropy(h, w, lab, block_n=16,
                                          block_v=128, impl="xla")

    fused_txt = jax.jit(fused).lower(h, w).compile().as_text()
    assert A.banned_buffers(A.parse_hlo(fused_txt), [rule]) == []


def test_break_materialization_contract_naive_env(monkeypatch):
    """ISSUE 8 acceptance: PT_NAIVE_LOSS_HEAD=1 must make the train-step
    materialization contract fail, and the failure must name the logits
    buffers (actionable diff, not a bare boolean)."""
    monkeypatch.setenv("PT_NAIVE_LOSS_HEAD", "1")
    g = A.build_graph("train_step_k1")
    rep = A.analyze(g.compiled, g.name, g.contract)
    viols = A.check_contract(g.contract, rep)
    ban = [v for v in viols if v.rule == "materialization.ban"]
    assert ban, "naive loss head must trip the BSV ban"
    rendered = ban[0].render()
    assert "320]" in rendered       # the buffer shape is in the message
    assert "B" in rendered and "<-" in rendered   # bytes + producer


# -- donation ----------------------------------------------------------------

def test_trainer_step_donation_contract():
    """params + opt_state are donated in the compiled per-step program —
    the regression test pinning Trainer._dispatch's donation."""
    g = A.build_graph("train_step_k1")
    rep = A.analyze(g.compiled, g.name, g.contract)
    assert A.check_contract(g.contract, rep) == []
    assert rep.donation["aliased_param_count"] >= 46
    # every params/opt_state leaf aliased; batch not a candidate
    labels = [a["label"] for a in rep.donation["aliased"]]
    assert any(l.startswith("params[") for l in labels)
    assert any(l.startswith("opt_state[") for l in labels)
    assert all(not c.label.startswith("batch")
               for c in rep.donation["undonated_candidates"])


def test_serving_tick_donation_and_waived_state():
    """Pools donated; the state tuple surfaces as donat-able-but-undonated
    candidates — exactly the set the budget file waives with a rationale
    (in-flight blocks hold pos/active for async drains)."""
    g = A.build_graph("serving_tick")
    rep = A.analyze(g.compiled, g.name, g.contract)
    assert A.check_contract(g.contract, rep) == []
    cand = sorted(c.label for c in rep.donation["undonated_candidates"])
    assert cand == ["state[0]", "state[1]", "state[2]", "state[3]",
                    "state[4]"]
    budgets = A.load_budgets(os.path.join(TOOLS, "graph_budgets.json"))
    waivers = budgets["graphs"]["serving_tick"]["waivers"]
    assert set(cand) <= set(waivers)
    assert all(len(reason) > 10 for reason in waivers.values())


def test_undonating_history_fails_contract():
    """ISSUE 8 acceptance: strip the spec tick's donation (the jit a
    refactor might rebuild without donate_argnums) and the contract must
    fail, naming hist and pools."""
    from paddle_tpu.analysis.graphs import _engine
    eng = _engine(spec_k=3)
    donated = eng._build_spec_decode(3, any_sample=False)
    undonated = jax.jit(donated.__wrapped__)      # same body, no donation
    compiled = undonated.lower(
        eng._params, eng.pools, jnp.asarray(eng.tables), eng._base_key,
        eng._state, eng._knobs, eng._hist).compile()
    contract = A.GraphContract("spec_no_donate",
                               require_aliased=("pools", "hist"))
    rep = A.analyze(compiled, "spec_no_donate", contract)
    viols = A.check_contract(contract, rep)
    rules = {v.rule for v in viols}
    assert "donation.require_aliased[hist]" in rules
    assert "donation.require_aliased[pools]" in rules
    hist_v = next(v for v in viols
                  if v.rule == "donation.require_aliased[hist]")
    assert "hist" in "\n".join(hist_v.lines)
    assert rep.donation["donated_bytes"] == 0


def test_budget_floor_catches_donation_drop():
    """Budget semantics: a donated_bytes floor fails when the actual graph
    donates less (the snapshot-diff path, without touching the repo's real
    budget file)."""
    g = A.build_graph("prefix_admit")
    rep = A.analyze(g.compiled, g.name, g.contract)
    snap = A.snapshot_report(rep)
    entry = {"budget": dict(snap), "waivers": {}}
    assert A.check_budget(rep, entry) == []
    entry["budget"]["donated_bytes"] = snap["donated_bytes"] + 1
    viols = A.check_budget(rep, entry)
    assert any(v.rule == "budget.donated_bytes" for v in viols)
    entry["budget"]["donated_bytes"] = snap["donated_bytes"]
    entry["budget"]["collective_counts"] = {"all-gather[tp]": 1}
    viols = A.check_budget(rep, entry)
    assert any(v.rule == "budget.collective_counts" for v in viols)
    assert "all-gather" in "\n".join(viols[0].lines)


# -- collective census -------------------------------------------------------

def test_collective_census_tp_fused_ce():
    """dp=2 x tp=2: the TP fused CE emits exactly one pmax + its two psums
    over the tp axis and ZERO all-gathers — the implicit-reshard regression
    the census exists to catch. The two psums (exp-sum and target logit,
    each f32[16] per device) travel in ONE combined all-reduce on jax
    0.9.0: two all-reduces in all, the psum one carrying both payloads."""
    g = A.build_graph("tp_fused_ce")
    rep = A.analyze(g.compiled, g.name, g.contract, mesh=g.mesh)
    assert A.check_contract(g.contract, rep) == []
    assert rep.collectives["counts"] == {"all-reduce[tp]": 2}
    by_op = {("pmax" if "pmax" in c.op_name else "psum"): c.bytes
             for c in rep.collectives["table"]}
    assert by_op == {"pmax": 64, "psum": 128}
    # every collective classified to the tp axis, none over dp
    assert all(c.axis == "tp" for c in rep.collectives["table"])
    assert rep.collectives["bytes_by_op"].get("all-gather", 0) == 0


def test_mesh_axis_groups_classification():
    from paddle_tpu.parallel import HybridMesh
    hm = HybridMesh.build(dp=2, tp=2, devices=jax.devices()[:4])
    groups = A.mesh_axis_groups(hm)
    assert groups["tp"] == frozenset({(0, 1), (2, 3)})
    assert groups["dp"] == frozenset({(0, 2), (1, 3)})


# -- trace_lint --------------------------------------------------------------

def _lint(src):
    return trace_lint.lint_source(src)


def test_trace_lint_host_sync_in_traced_fn():
    src = (
        "import jax\n"
        "def body(x, y):\n"
        "    v = float(x.sum())\n"
        "    return v\n"
        "out = jax.jit(body)\n")
    v = _lint(src)
    assert [x.rule for x in v] == ["host-sync"] and v[0].line == 3


def test_trace_lint_item_and_time_and_rng():
    src = (
        "import jax, time, numpy as np\n"
        "def step(c, x):\n"
        "    t = time.time()\n"
        "    r = np.random.rand()\n"
        "    k = jax.random.PRNGKey(0)\n"
        "    q = x.item()\n"
        "    return c, x\n"
        "jax.lax.scan(step, 0, None)\n")
    rules = sorted(x.rule for x in _lint(src))
    assert rules == ["host-rng", "host-rng", "host-sync", "host-time"]


def test_trace_lint_nonstatic_branch_and_static_ok():
    src = (
        "import jax\n"
        "def body(x, n):\n"
        "    if x:\n"
        "        return x\n"
        "    m = int(x.shape[0])\n"     # static shape math: NOT flagged
        "    if n is None:\n"           # identity dispatch: NOT flagged
        "        return x\n"
        "    return x\n"
        "jax.jit(body)\n")
    v = _lint(src)
    assert [x.rule for x in v] == ["nonstatic-branch"] and v[0].line == 3


def test_trace_lint_waiver_and_jit_in_loop():
    src = (
        "import jax\n"
        "for k in range(3):\n"
        "    f = jax.jit(lambda x: x)  "
        "# trace-lint: waive(jit-in-loop) bench sweep\n"
        "for k in range(3):\n"
        "    g = jax.jit(lambda x: x)\n")
    v = _lint(src)
    assert len(v) == 2
    assert v[0].waived and v[0].waiver_reason == "bench sweep"
    assert not v[1].waived


def test_trace_lint_false_positive_guards():
    # tree.map's fn arg is NOT traced; `def run(self)` methods are not
    # the jitted local `run`; nested defs inside traced code ARE traced
    src = (
        "import jax\n"
        "clean = jax.tree.map(lambda x: float(x), tree)\n"
        "class Engine:\n"
        "    def run(self):\n"
        "        return float(self.x)\n"
        "def outer(a):\n"
        "    def inner(c, i):\n"
        "        return c, float(c.sum())\n"
        "    return jax.lax.scan(inner, a, None)\n"
        "out = jax.jit(outer)\n"
        "run = jax.jit(lambda p: p)\n")
    v = _lint(src)
    assert [x.line for x in v] == [8]   # only inner's float()


def test_repo_hot_paths_lint_clean():
    """Satellite: trainer/, inference/, ops/ (and analysis/ itself) ship
    with zero unwaived trace-lint violations."""
    repo = os.path.dirname(TOOLS)
    paths = [os.path.join(repo, "paddle_tpu", p)
             for p in ("trainer", "inference", "ops", "analysis")]
    viols = [v for v in trace_lint.lint_paths(paths) if not v.waived]
    assert viols == [], "\n".join(v.render() for v in viols)


# -- fingerprint "why" -------------------------------------------------------

def test_explain_fingerprint_change_paths():
    from paddle_tpu.core import compile_cache as cc
    old = {"static": {"env": {"PT_NAIVE_LOSS_HEAD": False}, "donate": True},
           "kind": "step"}
    new = {"static": {"env": {"PT_NAIVE_LOSS_HEAD": True}, "donate": True},
           "kind": "superstep"}
    diff = cc.explain_fingerprint_change(old, new)
    assert any("static.env.PT_NAIVE_LOSS_HEAD: False -> True" in d
               for d in diff)
    assert any(d.startswith("kind:") for d in diff)
    assert cc.explain_fingerprint_change(old, old) == []


def test_stale_aot_artifact_explained(tmp_path, monkeypatch):
    """End to end: precompile writes the labeled parts sidecar; a restart
    under PT_NAIVE_LOSS_HEAD=1 rejects the artifact WITH the env key named
    in the warning and in stats()['last_stale']."""
    import paddle_tpu as pt
    from paddle_tpu.analysis.graphs import _micro_model
    from paddle_tpu.core import compile_cache as cc
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.trainer import Trainer

    cache_dir = str(tmp_path / "aot")
    batch = {"input_ids": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    model = _micro_model()
    tr = Trainer(model, AdamW(learning_rate=1e-4, parameters=model))
    out = tr.precompile(batch, cache_dir=cache_dir)
    assert out["outcome"] in ("miss", "hit")
    meta = [f for f in os.listdir(cache_dir) if f.endswith(".meta.json")]
    assert meta, "precompile must write the AOT sidecar"
    import json
    with open(os.path.join(cache_dir, meta[0])) as f:
        assert "parts" in json.load(f)

    cc.clear()                       # simulate a process restart
    monkeypatch.setenv("PT_NAIVE_LOSS_HEAD", "1")
    model2 = _micro_model()
    tr2 = Trainer(model2, AdamW(learning_rate=1e-4, parameters=model2))
    with pytest.warns(UserWarning, match="PT_NAIVE_LOSS_HEAD"):
        out2 = tr2.precompile(batch, cache_dir=cache_dir)
    assert out2["outcome"] == "miss"        # stale artifact NOT loaded
    stale = cc.stats()["last_stale"]
    assert stale is not None
    assert any("PT_NAIVE_LOSS_HEAD" in d for d in stale["diff"])


# -- the tier-1 gate ---------------------------------------------------------

def test_graph_lint_tool_in_process():
    """tools/graph_lint.py (the CI gate): all canonical graphs green
    against the checked-in budgets, trace_lint clean, >= 4 canonical
    entrypoints covered (ISSUE 8 acceptance)."""
    sys.path.insert(0, TOOLS)
    try:
        import graph_lint
        out = graph_lint.main(verbose=False)
    finally:
        sys.path.remove(TOOLS)
    assert out["ok"], "\n".join(out["violations"])
    assert len(out["snapshots"]) >= 4
    for required in ("train_step_k1", "serving_tick", "prefix_admit",
                     "fused_ce"):
        assert required in out["snapshots"]
    assert out["trace_lint"]["violations"] == 0
    assert out["skipped"] == []      # 8-device conftest: census graph runs
