"""The only module of the benchmark that imports the system under test.

From the program the benchmark takes the system (model classes, the serving
engine, the trainer, the mesh) and nothing that decides a number: weights
come from ``weights.py`` (which leaves: the configuration's family), traffic
from ``traffic.py``, time from the harness's clock, correctness from the
family's reference. Which class to build and how
its parameters are called is data in the configuration's ``program`` group.
"""

from __future__ import annotations

import importlib
import re

from . import weights


def _resolve(path: str):
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def canonical(rules, program_name: str) -> str:
    """A program parameter's canonical leaf name (weights.py), by the
    configuration's ``param_names`` rules: [[regex, replacement], ...]."""
    for pattern, repl in rules:
        if re.fullmatch(pattern, program_name):
            return re.sub(pattern, repl, program_name)
    raise KeyError(f"no param_names rule matches {program_name!r}")


def build_model(config: dict):
    """The program's model, built abstractly (no weight is made by the
    program), and {program parameter name: canonical leaf name}."""
    from paddle_tpu.base import LazyGuard
    prog = config["program"]
    fields = {k: (config[v[1:]] if isinstance(v, str) and v.startswith("@")
                  else v) for k, v in prog["config_fields"].items()}
    with LazyGuard():
        model = _resolve(prog["model_class"])(
            _resolve(prog["config_class"])(**fields))
    names = {n: canonical(prog["param_names"], n)
             for n, _ in model.named_parameters()}
    shapes = weights.leaf_shapes(config)
    for n, p in model.named_parameters():
        want = shapes[names[n]][0]
        if tuple(p.value.shape) != tuple(want):
            raise ValueError(f"{n}: program has {tuple(p.value.shape)}, "
                             f"the benchmark's leaf {names[n]} is {want}")
    return model, names


def install(model, names: dict, leaves: dict) -> None:
    for n, p in model.named_parameters():
        p.value = leaves[names[n]]


def build_engine(config: dict, seed: int):
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.generation import GenerationConfig
    model, names = build_model(config)
    install(model, names, weights.make_all(seed, config))
    model.eval()
    eng = ContinuousBatchingEngine(
        model, generation_config=GenerationConfig(do_sample=False),
        **config["engine"])
    return model, eng


def build_trainer(config: dict, seed: int):
    """(trainer, names, model) on one chip. A mesh cell (PERF.md, Open
    questions) will need the leaves born sharded here."""
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.trainer import Trainer
    model, names = build_model(config)
    install(model, names, weights.make_all(seed, config))
    o = dict(config["optimizer"])
    opt = getattr(opt_mod, o.pop("class"))(parameters=model, **o)
    return Trainer(model, opt), names, model


def trainer_counters(tr) -> dict:
    """The counters the trainer keeps: its ``stats()`` where it has one,
    else its public ``dispatch_stats`` (steps, dispatches, the host's
    seconds spent enqueueing) and the programs its ``build_log`` lists."""
    if callable(getattr(tr, "stats", None)):
        return dict(tr.stats())
    return dict(tr.dispatch_stats, programs_built=len(tr.build_log))


def make_loader(rows_fn, batch: int):
    """The program's input pipeline over the benchmark's rows: worker
    threads, collation and device prefetch."""
    from paddle_tpu.io import DataLoader, Dataset

    class Rows(Dataset):
        def __len__(self):
            return 1 << 30

        def __getitem__(self, i):
            return {k: v[0] for k, v in rows_fn(i, 1).items()}

    return iter(DataLoader(Rows(), batch_size=batch, num_workers=2,
                           prefetch_factor=4, prefetch_to_device=True,
                           drop_last=True, shuffle=False))
