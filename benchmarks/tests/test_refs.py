"""The two plain references against the system at tiny widths on the CPU,
and the control of "How correct is decided" kept at a size a test can hold:
the reference computed in float8 must come out as NOT correct under the
same comparison and the same limits that the system passes."""

import json

import pytest

from benchmarks.tools import control
from conftest import TINY


def test_prefill_then_decode_through_the_paged_cache_matches_the_reference(tiny_runs, capsys):
    # greedy tokens of a float32 engine are the reference's own best tokens
    assert tiny_runs("tiny.closed")["correct"] is True
    assert tiny_runs("tiny.open")["correct"] is True


@pytest.mark.parametrize("cell", ["tiny.moe-train", "tiny.dense-train"])
def test_first_training_steps_match_the_reference(cell, capsys):
    from benchmarks import run
    out = run.run_cell(cell, 41, 0.3, False, benchmark_file=TINY,
                       require_chip=False)
    assert out["correct"] is True
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{"compared"')]
    got = {l["compared"]: l["value"] for l in lines}
    assert got["loss_step0_abs_diff"] < 1e-5 and got["loss_step2_abs_diff"] < 1e-5
    assert got["grad_norm_worst_leaf"] < 1e-4
    assert got["param_change_worst_leaf"] < 1e-4


@pytest.mark.parametrize("cell,seeds", [("tiny.closed", "5,6,7"),
                                        ("tiny.moe-train", "3"),
                                        ("tiny.dense-train", "3")])
def test_the_control_comes_out_not_correct(cell, seeds, tmp_path):
    out = tmp_path / "control.jsonl"
    rc = control.main(["--workload", cell, "--seeds", seeds, "--seconds", "1",
                       "--allow-cpu", "--benchmark-file", TINY,
                       "--out", str(out)])
    assert rc == 0
    recs = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(recs) == len(seeds.split(","))
    failed = sum(not r["control_correct"] for r in recs)
    # a serving window's sample depends on the host's speed: two of three
    assert failed == len(recs) or (cell == "tiny.closed" and failed >= 2)
