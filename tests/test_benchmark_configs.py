"""Every configuration of the benchmark resolves against the program.

A configuration is data that names code: a family module, a model class and
its config class, the fields it fills, and the keyword arguments the harness
hands the engine (``**config["engine"]``, ``benchmarks/program.py``) or the
optimizer. A PR that renames or removes one of those in ``paddle_tpu/`` breaks
a cell that only the chip runs; this file says so on the CPU, building
nothing. It reads the benchmark's files (the tiny configurations of the
benchmark's own tests too: they pass through the same calls) and edits none.
"""

import dataclasses
import glob
import importlib
import inspect
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(
    glob.glob(os.path.join(REPO, "benchmarks", "configs", "*.json"))
    + glob.glob(os.path.join(REPO, "benchmarks", "tests", "data", "configs",
                             "*.json")))


def _resolve(path):
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def _keywords(fn):
    return set(inspect.signature(fn).parameters) - {"self"}


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_configuration_resolves_against_the_program(path):
    sys.path.insert(0, REPO)
    try:
        from benchmarks import families
        with open(path) as f:
            cfg = json.load(f)
        family = families.of(cfg)     # imports; raises on a missing function
    finally:
        sys.path.remove(REPO)
    assert family.__name__ == cfg["family"]

    prog = cfg["program"]
    assert callable(_resolve(prog["model_class"]))
    config_class = _resolve(prog["config_class"])
    fields = {f.name for f in dataclasses.fields(config_class)}
    assert set(prog["config_fields"]) <= fields, (
        sorted(set(prog["config_fields"]) - fields))
    for value in prog["config_fields"].values():
        if isinstance(value, str) and value.startswith("@"):
            assert value[1:] in cfg, f"{value} names no key of the file"

    assert ("engine" in cfg) != ("optimizer" in cfg), "serves or trains"
    if "engine" in cfg:
        from paddle_tpu.inference import ContinuousBatchingEngine
        known = _keywords(ContinuousBatchingEngine.__init__)
        assert set(cfg["engine"]) <= known, sorted(set(cfg["engine"]) - known)
    else:
        from paddle_tpu import optimizer
        opt = dict(cfg["optimizer"])
        known = _keywords(getattr(optimizer, opt.pop("class")).__init__)
        assert set(opt) <= known, sorted(set(opt) - known)
        assert cfg["trainer"]["rows_per_chip"] > 0
