"""The harness end to end at tiny presets on the CPU, through run.py's own
entry: the run line's keys, resolution by name, no chip means failure, a
broken timed path means ``correct: false``, a new configuration, traffic
mix, per-layer metric and cell are each one new file, and so is a new
FAMILY: its leaves, reference and counts, a kernel's roofline, a ratio of
the program's counters and a share of one of its spans."""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from benchmarks import reduce, run, serve, traffic
from conftest import ROOT, TINY

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
TINY_CELLS = ["tiny.closed", "tiny.open", "tiny.moe-train", "tiny.dense-train"]


@pytest.mark.parametrize("cell", TINY_CELLS)
def test_run_line_has_exactly_the_contracts_keys(tiny_runs, cell):
    out = tiny_runs(cell)
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    json.dumps(out)


@pytest.mark.parametrize("cell", TINY_CELLS[:1] + TINY_CELLS[2:3])
def test_a_traced_run_reports_per_layer_metrics_only(tiny_runs, cell):
    out = tiny_runs(cell, trace=True)
    assert "setup_s" not in out["metrics"]     # tiny cells list no per-layer metric
    assert out["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_every_cells_files_resolve_by_name(cell):
    c, config, mix, metrics, e2e = run.resolve(cell, os.path.join(ROOT, "BENCHMARK.json"))
    assert config["kind"] in ("serve", "train") and config["chips"] == c["chips"]
    assert ("loop" in mix) == (config["kind"] == "serve")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert metrics, "a cell reports at least one per-layer metric"
    listed = {m["name"] for m in BENCH["per_layer"] if cell in m["workloads"]}
    assert {m["name"] for m in metrics} == listed
    moved = {m["name"] for m in e2e}
    assert all(m["moves"] in moved for m in metrics)


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = 24      # later PRs add cells and may not change run_seconds
    assert (2 + 14 * cells) * (BENCH["run_seconds"] + 60) + cells * 180 + 1200 <= 43200
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmarks/") and os.path.exists(os.path.join(ROOT, c["file"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for root, _, files in os.walk(os.path.join(ROOT, "benchmarks")):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_layer_metric_files_match_benchmark_json():
    for m in BENCH["per_layer"]:
        spec = json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                           m["name"] + ".json")))
        assert {k: spec[k] for k in m} == m
        from benchmarks import reduce
        assert spec["reducer"] in reduce.REDUCERS


def test_without_a_chip_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert not any(l.startswith('{"correct"') for l in p.stdout.splitlines())
    assert "needs 1 TPU chip" in p.stderr


def test_a_served_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.inference import ContinuousBatchingEngine as Engine
    real = Engine.step
    seen = [0]

    def step(self):
        out = []
        for rid, tok in real(self):
            seen[0] += 1
            out.append((rid, (tok + 1) % 256 if seen[0] % 7 == 0 else tok))
        return out

    monkeypatch.setattr(Engine, "step", step)
    out = run.run_cell("tiny.closed", 31, 1.0, False, benchmark_file=TINY,
                       require_chip=False)
    assert out["correct"] is False


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from paddle_tpu.trainer import Trainer
    real = Trainer.train_step

    # a trainer that computes the loss but throws the update away
    def frozen(self, batch):
        import jax
        snap_p = jax.tree.map(lambda x: x.copy(), self.params)
        snap_s = jax.tree.map(lambda x: x.copy(), self.opt_state)
        loss = real(self, batch)
        self.params, self.opt_state = snap_p, snap_s
        self.sync_model()
        return loss

    monkeypatch.setattr(Trainer, "train_step", frozen)
    out = run.run_cell("tiny.dense-train", 32, 0.5, False, benchmark_file=TINY,
                       require_chip=False)
    assert out["correct"] is False


def test_the_lower_precision_fails_the_comparison(tmp_path):
    """The system's matmuls in bfloat16 where the configuration states
    float32: the same comparison, the same limits, not correct."""
    bench = json.load(open(TINY))
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        cfg["dtype"] = "bfloat16"    # weights and activations; limits stay float32's
        p = tmp_path / (c["name"] + ".json")
        p.write_text(json.dumps(cfg))
        c["file"] = str(p)
    bf = tmp_path / "BENCHMARK.json"
    bf.write_text(json.dumps(bench))
    for cell in ("tiny.moe-train", "tiny.dense-train"):
        out = run.run_cell(cell, 33, 0.5, False, benchmark_file=str(bf),
                           require_chip=False)
        assert out["correct"] is False, cell
    # which requests a window finishes depends on the host's speed, and at
    # this size few greedy tokens sit close enough to a tie for bfloat16 to
    # flip them: one seed of three has to show it
    served = [run.run_cell("tiny.closed", seed, 1.0, False, benchmark_file=str(bf),
                           require_chip=False)["correct"] for seed in (33, 34, 35)]
    assert not all(served)


class _StallingEngine:
    """Answers every request with its tokens one step after admission, and
    stalls once for half a second: what a compile or a long prefill does."""
    max_batch, page_size, max_len, preemptions = 4, 16, 128, 0

    def __init__(self):
        self.q, self.active, self.attn_path_ticks = [], {}, {"dense": 0, "paged": 0}
        self.n, self.stalled = 0, False

    def submit(self, ids, max_new_tokens=None):
        self.q.append((self.n, max_new_tokens))
        self.n += 1
        return self.n - 1

    def has_work(self):
        return bool(self.q or self.active)

    def step(self):
        if self.n >= 3 and not self.stalled:
            self.stalled = True
            time.sleep(0.5)
        out = []
        for rid in list(self.active):
            out.append((rid, 1))
            self.active[rid] -= 1
            if not self.active[rid]:
                del self.active[rid]
        while self.q and len(self.active) < self.max_batch:
            rid, n = self.q.pop(0)
            self.active[rid] = n
        self.attn_path_ticks["dense"] += 1
        time.sleep(0.001)
        return out

    def run(self):
        while self.has_work():
            self.step()

    def stats(self):
        return {"queued": len(self.q), "active": len(self.active)}

    def take_finished(self):
        return {}


def test_ttft_is_timed_from_when_the_request_was_due():
    """A stalled loop sends late; the wait it imposed counts, because the
    clock starts when the request was DUE, and lateness is reported."""
    mix = traffic.load("test-open")
    config = {"vocab_size": 256, "engine": {"max_len": 128}}
    ctx = run.Context(config, mix, 5, 1.5, False, "/nonexistent")
    sched = traffic.serving_schedule(mix, 5, 1.5, 256, 128)
    e2e, attempted, failed, _ = serve.measure(ctx, _StallingEngine(), sched, 1.5)
    assert failed == 0 and attempted >= 8
    late = max(ctx.samples["gen_late_ms"])
    assert 300 < late < 520                      # sent late by the stall
    assert max(ctx.samples["ttft_s"]) > late / 1e3   # and TTFT counts it
    from benchmarks import reduce
    assert e2e["ttft_p90_s"] == reduce.percentile(ctx.samples["ttft_s"], 90)


def test_window_accounting_on_a_synthetic_log():
    """Tokens delivered inside the window count; a gap counts where its
    later token was delivered inside; requests are counted where due."""
    mix = traffic.load("test-closed")
    config = {"vocab_size": 256, "engine": {"max_len": 128}}
    ctx = run.Context(config, mix, 6, 0.6, False, "/nonexistent")
    sched = traffic.serving_schedule(mix, 6, 0.6, 256, 128)
    eng = _StallingEngine()
    eng.stalled = True
    e2e, attempted, failed, served = serve.measure(ctx, eng, sched, 0.6)
    assert 0.6 <= ctx.window_s < 0.65       # ends with the step the time ran out in
    assert e2e["serve_tok_s"] == pytest.approx(
        ctx.counters["tokens_in_window"] / ctx.window_s)
    # every stream gets one token a step, so a gap is one step's time
    assert len(ctx.samples["itl_ms"]) <= ctx.counters["tokens_in_window"]
    assert reduce.percentile(ctx.samples["itl_ms"], 90) < 50
    assert served and all(len(p) > 0 and len(t) > 0 for p, t in served)
    assert ctx.counters["decode_ticks"] == len(ctx.samples["step_ms"])


def test_a_configuration_a_mix_a_metric_and_a_cell_are_each_one_new_file(tmp_path):
    """In a temporary copy: add one of each, edit nothing that exists."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"), tmp_path / "paddle_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*") if p.is_file()}
    bench = json.load(open(TINY))
    cfg = json.load(open(os.path.join(ROOT, bench["configs"][0]["file"])))
    cfg.update(name="throwaway", num_hidden_layers=1)
    (tmp_path / "benchmarks/configs/throwaway.json").write_text(json.dumps(cfg))
    mix = dict(traffic.load("test-closed"), clients=2, requests=16)
    (tmp_path / "benchmarks/traffic/throwaway-mix.json").write_text(json.dumps(mix))
    (tmp_path / "benchmarks/layer_metrics/throwaway_ticks_s.json").write_text(json.dumps({
        "name": "throwaway_ticks_s", "unit": "ticks/s", "better": "higher",
        "source": "program_counter", "layer": "engine", "moves": "serve_tok_s",
        "workloads": ["throwaway.cell"], "reducer": "counter_rate",
        "counter": "decode_ticks"}))
    bench["configs"].append({"name": "throwaway", "source": "https://example.invalid",
                             "file": "benchmarks/configs/throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway.cell", "config": "throwaway",
                               "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "tiny.closed" in m["workloads"]:
            m["workloads"].append("throwaway.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = ("import json, sys; sys.path.insert(0, '.'); from benchmarks import run; "
            "print(json.dumps([run.run_cell('throwaway.cell', 3, 1.0, t, "
            "require_chip=False) for t in (False, True)]))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, text=True,
                       capture_output=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert p.returncode == 0, p.stderr[-2000:]
    plain, traced = json.loads(p.stdout.strip().splitlines()[-1])
    assert plain["correct"] and set(plain["metrics"]) == {"serve_tok_s", "setup_s"}
    assert set(traced["metrics"]) == {"throwaway_ticks_s"}
    assert traced["metrics"]["throwaway_ticks_s"]["value"] > 0
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} was edited"


NEW_FAMILY = os.path.join(ROOT, "benchmarks", "tests", "data", "new_family")

# The CPU has no device plane and no entry in peaks.json, so the run below
# is given both: a v5e's peaks, and over the span of the host's real events a
# step program with a grouped matmul in each run. Everything else is the
# harness's own: the family found by name, its leaves in the program, its
# reference deciding ``correct``, the trainer's counters and spans.
_DRIVE_NEW_FAMILY = """
import json, sys
sys.path.insert(0, '.')
from benchmarks import reduce, run

load, events = run.load_json, run.Context.events

def load_json(path):
    out = load(path)
    if path.endswith('peaks.json'):
        out['cpu'] = out['TPU v5 lite']
    return out

def with_a_device(self):
    ev = events(self)
    host = [e for e in ev if e.plane == reduce.HOST_PLANE]
    if host:
        lo, hi = min(e.start_ns for e in host), max(e.end_ns for e in host)
        run_ns = (hi - lo) / 4
        for i in range(4):
            ev.append(reduce.Event('/device:TPU:0', reduce.MODULES_LINE,
                                   'jit_one_step(1)', lo + i * run_ns, run_ns))
            ev.append(reduce.Event('/device:TPU:0', reduce.OPS_LINE,
                                   '%jvp_grouped_matmul_.2 = f32[64,64]{1,0} custom-call()',
                                   lo + i * run_ns, run_ns / 2))
    return ev

run.load_json, run.Context.events = load_json, with_a_device
print(json.dumps([run.run_cell('tiny.shared-moe-train', 3, 1.0, t, require_chip=False)
                  for t in (False, True)]))
"""


def test_a_family_is_new_files_only(tmp_path):
    """In a temporary copy: a family the harness has never seen (a dense
    first layer and a shared expert beside the routed ones, which
    ``gqa_decoder`` cannot say and ``MoEForCausalLM`` builds), with its
    reference, its counts, a configuration, a cell and one metric of each
    new kind, every one a new file; nothing that exists is edited."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"), tmp_path / "paddle_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*") if p.is_file()}
    added = []
    for root, _, files in os.walk(NEW_FAMILY):
        for f in files:
            if f.endswith((".py", ".json")):
                rel = os.path.relpath(os.path.join(root, f), NEW_FAMILY)
                dest = tmp_path / "benchmarks" / rel
                assert not dest.exists(), rel
                shutil.copy(os.path.join(root, f), dest)
                added.append(rel)
    assert sorted(added) == [
        "configs/tiny-shared-moe-train.json", "families/shared_expert_moe.py",
        "layer_metrics/tiny_dispatch_span_share.json",
        "layer_metrics/tiny_dispatches_per_step.json",
        "layer_metrics/tiny_expert_gemm_roofline.json", "refs/shared_expert_moe.py"]
    bench = json.load(open(TINY))
    bench["configs"].append({"name": "tiny-shared-moe-train",
                             "source": "https://example.invalid/tiny",
                             "file": "benchmarks/configs/tiny-shared-moe-train.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.shared-moe-train",
                               "config": "tiny-shared-moe-train",
                               "traffic": "test-train", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "tiny.moe-train" in m["workloads"]:
            m["workloads"].append("tiny.shared-moe-train")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p = subprocess.run([sys.executable, "-c", _DRIVE_NEW_FAMILY], cwd=tmp_path,
                       text=True, capture_output=True, timeout=900,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert p.returncode == 0, p.stderr[-3000:]
    plain, traced = json.loads(p.stdout.strip().splitlines()[-1])
    assert plain["correct"] is True and traced["correct"] is True, p.stdout[-3000:]
    assert set(plain["metrics"]) == {"train_tok_s_chip", "setup_s"}
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(got) == {"tiny_expert_gemm_roofline", "tiny_dispatches_per_step",
                        "tiny_dispatch_span_share"}
    assert all(v == v and abs(v) < float("inf") for v in got.values())
    assert 0 < got["tiny_expert_gemm_roofline"] < 100
    assert got["tiny_dispatches_per_step"] == 1.0     # one program a step
    assert 0 < got["tiny_dispatch_span_share"] <= 100
    assert "trainer::dispatch" in {k for k, _ in traced["breakdown"]["idle_gaps"]}
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} was edited"


def test_a_configuration_without_a_family_or_with_an_unknown_one_fails_loudly(tmp_path):
    from benchmarks import families
    bench = json.load(open(TINY))
    cfg = json.load(open(os.path.join(ROOT, bench["configs"][0]["file"])))
    assert families.of(cfg).__name__ == "benchmarks.families.gqa_decoder"
    with pytest.raises(KeyError, match="names no \"family\""):
        families.of({k: v for k, v in cfg.items() if k != "family"})
    with pytest.raises(ModuleNotFoundError):
        families.of(dict(cfg, family="benchmarks.families.no_such_family"))
    with pytest.raises(AttributeError, match="lacks leaf_shapes"):
        families.of(dict(cfg, family="benchmarks.counts"))
    with pytest.raises(AttributeError, match="no count function"):
        families.kernel_work(cfg, "no_such_kernel", {})
    # and through run.py's own resolution, before anything is built
    cfg.pop("family")
    (tmp_path / "c.json").write_text(json.dumps(cfg))
    bench["configs"][0]["file"] = str(tmp_path / "c.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(KeyError, match="family"):
        run.resolve("tiny.closed", str(tmp_path / "BENCHMARK.json"))
