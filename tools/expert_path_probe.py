#!/usr/bin/env python
"""Both forms of a prompt's routed experts, timed from a device trace.

``MoELayer.forward_inference`` sorts the rows of a call of more than
``DENSE_ROWS`` rows to their experts and runs them through a loop over steps
of one expert's rows (``blocked_expert_rows``) whose step
``parallel.moe.expert_step_rows`` chooses from the call's shapes. XLA's
``ragged_dot`` over the same sorted rows is the other form such a layer can
take, and was the slower one at every point read on a v5e (PERF.md, section
3). Run this again when XLA, libtpu or the chip changes::

    python tools/expert_path_probe.py --t 1536 --k 6 --e 128 --held 64 \\
        --d 2688 --f 1856 --act relu2 --skew 0.06 --steps 64,128,192,256

builds ONE routed layer of those shapes (sigmoid scores, a seeded selection
bias of spread ``--skew`` so that the experts' loads are as uneven as a
served model's: 0.06 / 0.1 / 0.2 gave the loads the Nemotron, GLM and ZAYA
cells' prompts read on seeded weights; weights and rows drawn from
``--seed``), and times the layer's whole ``forward_inference`` (router,
sort, gather, experts, gathers back, weighted sum) as the rule has it
(``rule``), with ``ragged_dot`` in the loop's place (``ragged``) and with
the loop at each step of ``--steps`` (``loop_<rows>``). Every variant is its
own jitted program, called ``--iters`` times under ``jax.profiler`` with the
weights as ARGUMENTS (nothing of a call is loop-invariant); the time is the
device's, read by program name off the trace's ``XLA Modules`` line, the
operations inside it off ``XLA Ops``. Prints one JSON line a length of
``--t``: the shapes, the loads and the steps they need, what the rule chose
(what the engine writes into a prefill program's ``build_log`` row), and per
variant the median milliseconds a call and its longest operations (``null``
where a variant compiled to the very program of an earlier one: the rule's
own step).

The tool steers the layer from outside (it replaces
``moe.expert_step_rows`` / ``moe.blocked_expert_rows`` while a variant is
traced): the layer has no option for it. A tree without those (an older
commit) is timed as it is (``rule`` alone). A trace of the CPU backend has
no device plane: the line then carries the loads and the rule's choice, and
null for every time.
"""

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def build_layer(a):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.parallel.moe import MoELayer
    pt.seed(a.seed)
    layer = MoELayer(a.d, a.f, a.e, top_k=a.k, capacity_factor=None,
                     dtype=a.dtype, scoring="sigmoid", select_bias=True,
                     norm_topk_prob=a.k > 1,
                     experts_held=(0, a.held) if a.held < a.e else None,
                     expert_act=a.act).eval()
    dict(layer.named_parameters())["gate_bias"].value = \
        a.skew * jax.random.normal(jax.random.key(a.seed), (a.e,),
                                   jnp.float32)
    return layer


def device_times(trace_dir, programs):
    """{program: ([ms a run], {operation: ms a run})} off the newest
    ``.xplane.pb`` under ``trace_dir``: runs by name on the first device's
    ``XLA Modules`` line, operations on ``XLA Ops`` inside each run."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    plane = next((p for p in ProfileData.from_file(path).planes
                  if p.name.startswith("/device:")), None)
    if plane is None:       # the CPU backend: no device time to read
        return {name: ([], {}) for name in programs}
    lines = {l.name: list(l.events) for l in plane.lines}
    ops = sorted(lines.get("XLA Ops", []), key=lambda e: e.start_ns)
    out = {}
    for name in programs:
        runs = [e for e in lines.get("XLA Modules", [])
                if e.name.startswith(f"jit_{name}(")]
        inside = {}
        for r in runs:
            for o in ops:
                if r.start_ns <= o.start_ns < r.start_ns + r.duration_ns:
                    key = o.name.split(" = ")[0].lstrip("%")
                    inside[key] = inside.get(key, 0.0) + o.duration_ns
        n = max(len(runs), 1)
        out[name] = ([r.duration_ns / 1e6 for r in runs],
                     {k: v / 1e6 / n for k, v in inside.items()})
    return out


def probe(a, t):
    import jax
    import numpy as np
    from paddle_tpu.parallel import moe

    layer = build_layer(a)
    x = jax.random.normal(jax.random.key(a.seed + t), (1, t, a.d)).astype(
        a.dtype)
    hooks = ("expert_step_rows", "blocked_expert_rows")
    saved = {h: getattr(moe, h) for h in hooks if hasattr(moe, h)}
    path = getattr(layer, "inference_path", None)
    chosen = path(t, x.dtype) if path else (None, None)
    variants = {"rule": {}}
    if len(saved) == len(hooks):

        def ragged(xs, w_in, w_dn, act, load, block):
            gmm = lambda a, w: moe.xla_grouped_matmul(a, w, load)
            return moe.expert_ffn(xs, w_in, w_dn, act,
                                  lambda a, w: gmm(a, w).astype(xs.dtype),
                                  gmm)
        variants["ragged"] = {"blocked_expert_rows": ragged}
        for s in a.steps:
            variants[f"loop_{s}"] = {"expert_step_rows": lambda *_, s=s: s}

    def program(name):
        def fn(leaves, x):
            with layer._bind(leaves):
                return layer.forward_inference(x)
        fn.__name__ = f"probe_{name}"
        return jax.jit(fn)

    leaves = layer.raw_state()
    fns, load = {}, None
    try:
        for name, patch in variants.items():
            for h, v in {**saved, **patch}.items():
                setattr(moe, h, v)
            fns[name] = program(name)
            out, load = fns[name](leaves, x)        # compiled, once
            jax.block_until_ready(out)
    finally:
        for h, v in saved.items():
            setattr(moe, h, v)
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(a.iters):
            for fn in fns.values():
                jax.block_until_ready(fn(leaves, x)[0])
        jax.profiler.stop_trace()
        times = device_times(tmp, [f"probe_{n}" for n in fns])
    load = np.sort(np.asarray(load))[::-1]
    line = dict(
        t=t, k=a.k, e=a.e, held=a.held, d=a.d, f=a.f, act=a.act,
        dtype=a.dtype, seed=a.seed, skew=a.skew, iters=a.iters,
        device=jax.devices()[0].device_kind,
        expert_path=chosen[0], expert_step_rows=chosen[1],
        load=dict(sum=int(load.sum()), max=int(load[0]),
                  median=float(np.median(load)), min=int(load[-1]),
                  steps={s: int(np.sum(-(-load // s))) for s in a.steps}),
        ms={}, ops={})
    for name in fns:
        runs, ops = times[f"probe_{name}"]
        line["ms"][name] = (round(statistics.median(runs), 4) if runs
                            else None)
        line["ops"][name] = {k: round(v, 4) for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:a.top]}
    return line


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--t", required=True,
                   help="rows of the call (a prompt's tokens), a list")
    p.add_argument("--k", type=int, required=True, help="choices a row")
    p.add_argument("--e", type=int, required=True, help="the router's width")
    p.add_argument("--held", type=int, default=None,
                   help="experts held here (default: all)")
    p.add_argument("--d", type=int, required=True)
    p.add_argument("--f", type=int, required=True)
    p.add_argument("--act", default="swiglu", choices=("swiglu", "relu2"))
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--steps", default="32,64,128,256")
    p.add_argument("--skew", type=float, default=0.0,
                   help="spread of the seeded selection bias (scores are "
                        "sigmoids: 0 sends every expert the same share)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--top", type=int, default=6,
                   help="operations listed a variant")
    a = p.parse_args(argv)
    a.held = a.held or a.e
    a.steps = [int(s) for s in a.steps.split(",")]
    for t in (int(x) for x in a.t.split(",")):
        print(json.dumps(probe(a, t)), flush=True)


if __name__ == "__main__":
    main()
