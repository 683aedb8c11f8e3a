"""A configuration names its family, and the harness finds by that name
everything that depends on the architecture.

``"family": "<module path>"`` in a configuration's file is resolved the way
``program._resolve`` resolves a model class: an import, no registry, no
default. A family module gives three groups of functions, and the harness
takes a leaf shape, a reference or a count from nowhere else:

* leaves: ``leaf_shapes(config) -> {canonical name: (shape, kind)}``
  (``kind``: "norm", "router" or "matrix"; ``weights.py`` makes them);
* the plain reference: ``logits_at``, ``loss_and_grads``, ``loss0_expected``
  (signatures as in ``refs/decoder.py``);
* required work: ``train_flops_per_token(config, seq_len)``,
  ``decode_tick_bytes(config, live_tokens)`` and, for every kernel that has
  a ``work: "kernel"`` roofline metric, a function
  ``<kernel>(config, shapes) -> {pass: {"flops": .., "bytes": ..}}`` for ONE
  run of the module the metric names (every layer that runs the kernel).

So a new architecture is new files only: its family module with its
reference, a configuration, a cell in BENCHMARK.json, metric specs.
"""

from __future__ import annotations

import importlib

REQUIRED = ("leaf_shapes",
            "logits_at", "loss_and_grads", "loss0_expected",
            "train_flops_per_token", "decode_tick_bytes")


def of(config: dict):
    """The family module a configuration names. A configuration without a
    family, a module that cannot be imported or one that lacks a required
    function fails here, as an unknown device kind does in peaks.json."""
    path = config.get("family")
    if not path:
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"\"family\" (a module path, e.g. "
                       f"benchmarks.families.gqa_decoder)")
    family = importlib.import_module(path)
    missing = [f for f in REQUIRED if not callable(getattr(family, f, None))]
    if missing:
        raise AttributeError(f"family {path} lacks {', '.join(missing)}")
    return family


def kernel_work(config: dict, name: str, shapes: dict) -> dict:
    """What one run of a module requires of the kernel ``name``, by the
    configuration's family: {pass: {"flops", "bytes"}}."""
    fn = getattr(of(config), name, None)
    if not callable(fn):
        raise AttributeError(f"family {config['family']} has no count "
                             f"function {name!r}")
    return fn(config, shapes)
