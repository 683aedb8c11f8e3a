"""Write the lowered text of the serving engine's tick (``jit_run``) and of
one prefill program for the tiny configuration of each servable core, so
that two trees can be compared byte for byte (ISSUE 44: the contract between
core and engine changes how a program is CALLED, never what it computes).

    cd <tree> && JAX_PLATFORMS=cpu PYTHONPATH=. python \
        <this file> --out /root/scratch/text/<name>
    diff -r /root/scratch/text/parent /root/scratch/text/change

Run from the root of the tree it is to describe: it imports that tree's
``paddle_tpu`` and calls the engine exactly as ``_admit`` and
``_dispatch_block`` do, through entry points both trees have.

``--cells <cell> ...`` writes instead the same two programs (the widest
prefill) of benchmark cells at their REAL size, lowered for a described v5e
chip with abstract weights (Mosaic kernel bodies included), a few seconds a
cell. Tracebacks are kept out of the locations: a kernel's serialized body
holds them, and they name the tree's path and line numbers.
"""

import argparse
import os
import sys

sys.path.insert(0, os.getcwd())

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models.hybrid_lm import HybridConfig, HybridForCausalLM  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models.moe_lm import MoEConfig, MoEForCausalLM  # noqa: E402

MOE = dict(capacity_factor=None, dtype="float32")
CASES = {
    # pages only, no counter
    "llama": lambda: LlamaForCausalLM(LlamaConfig.tiny(dtype="float32")),
    # pages and counters
    "moe-gqa": lambda: MoEForCausalLM(MoEConfig.tiny(**MOE)),
    "moe-mla": lambda: MoEForCausalLM(MoEConfig.tiny(
        attention="mla", q_lora_rank=32, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16, **MOE)),
    # pages, slot state, counters with a skip choice
    "moe-cca": lambda: MoEForCausalLM(MoEConfig.tiny(
        attention="cca", head_dim=16, router="mlp", router_hidden_size=32,
        router_skip_choice=True, residual_scaling=True, rms_norm_eps=1e-5,
        **MOE)),
    # pages for one layer, slot state for two, counters
    "hybrid-pages": lambda: HybridForCausalLM(HybridConfig.tiny()),
    # no page at all, slot state, no counter
    "hybrid-no-page": lambda: HybridForCausalLM(
        HybridConfig.tiny(pattern="p-m-")),
}
PAGE, BUCKET = 16, 32


def programs(model):
    eng = ContinuousBatchingEngine(model.eval(), max_batch=2, max_len=64,
                                   page_size=PAGE)
    eng._init_state(jnp.zeros((model.cfg.vocab_size,), jnp.float32))
    eng._tables_dev = jnp.asarray(eng.tables)
    tick = eng._build_decode(1, False, "paged").lower(*eng._decode_args(False))
    prefill = eng._prefill_fn(BUCKET).lower(
        eng._params, jnp.zeros((1, BUCKET), jnp.int32), eng.pools,
        jnp.asarray(eng.tables[:1]), jnp.int32(BUCKET - 3), eng.slot_state,
        np.int32(1))
    return {"tick": tick.as_text(), "prefill": prefill.as_text()}


def cell_programs(cell):
    """{program: lowered text} of ``cell`` at its real size, for one chip of
    a described v5e:2x2 (``tests/test_aot_tpu_compile.py``'s recipe)."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import program, run as bench
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.pallas import autotune
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_traceback_in_locations_limit", 0)
    autotune._device_kind = lambda default="cpu": "TPU v5 lite"
    registry.backend_kind = lambda: "tpu"
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    abstract = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype,
        sharding=dev), tree)
    config = bench.resolve(cell, os.path.join(os.getcwd(),
                                              "BENCHMARK.json"))[1]
    eng = ContinuousBatchingEngine(
        program.build_model(config)[0].eval(),
        generation_config=GenerationConfig(do_sample=False),
        **config["engine"])
    eng._init_state(jax.ShapeDtypeStruct((config["vocab_size"],),
                                         jnp.bfloat16))
    eng._tables_dev = jnp.asarray(eng.tables)
    bucket = eng._bucket(eng.max_len)
    return {"tick": eng._build_decode(1, False, "paged").lower(
                *abstract(eng._decode_args(False))).as_text(),
            f"prefill_{bucket}": eng._prefill_fn(bucket).lower(*abstract((
                eng._params, jnp.zeros((1, bucket), jnp.int32), eng.pools,
                jnp.asarray(eng.tables[:1]), jnp.int32(0), eng.slot_state,
                np.int32(0)))).as_text()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--cells", nargs="*", default=[])
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    todo = ({cell: (lambda cell=cell: cell_programs(cell))
             for cell in args.cells}
            or {name: (lambda build=build: programs(build()))
                for name, build in CASES.items()})
    for name, texts in todo.items():
        pt.seed(0)
        for program, text in texts().items():
            path = os.path.join(args.out, f"{name}.{program}.txt")
            with open(path, "w") as f:
                f.write(text)
            print(f"{name}.{program}: {len(text)} bytes")


if __name__ == "__main__":
    main()
