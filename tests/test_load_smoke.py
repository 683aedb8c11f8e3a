"""Tier-1 leg for tools/load_test.py --smoke (ISSUE 16 satellite,
modeled on the obs_smoke leg): the goodput-vs-offered-load harness runs
in-process and its acceptance gates all hold — overload sheds typed,
the hung replica trips and is readmitted, and the slow-loris stream is
evicted. The harness's p99 TTFT ceiling is set out of reach here: a
CPU's p99 under six test workers is not evidence of anything."""

import os
import sys

import pytest

pytestmark = pytest.mark.chaos


def test_load_test_smoke_in_process():
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        import load_test
        out = load_test.main(["--smoke", "--ttft-ceiling", "1e9"])
    finally:
        sys.path.remove(tools)
    assert out["errors"] == []
    assert out["ok"]
    # under 2x-capacity offered load, work still completed AND the
    # shed ladder refused typed (nothing silently dropped)
    assert out["completed"] >= 1
    assert out["rejects"] >= 1
    assert out["shed"]["shed"]
    # the hung replica tripped its breaker and was readmitted closed
    assert out["breaker_trips"] >= 1
    assert out["hang"]["tripped"] and out["hang"]["readmitted"]
    assert out["hang"]["breaker"] == "closed"
