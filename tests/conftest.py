"""Test configuration: virtual 8-device CPU mesh.

Mirrors the reference's "multi-node without a cluster" strategy (SURVEY.md §4:
fake_cpu_device / single-host multi-process) using XLA's host-platform device
partitioning — the idiomatic JAX way to test sharding without TPU hardware.

Tests run on the CPU backend whatever the machine holds: the CPU platform
is forced BEFORE any backend is initialized, both via env (fresh interpreter,
and every worker a test spawns inherits it) and jax.config (already-imported
jax). The chip is reached through chip_smoke.py, never through pytest.
"""

import functools
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

assert jax.device_count() == 8, f"need 8 virtual cpu devices, got {jax.device_count()}"


@pytest.fixture(autouse=True)
def _seed_rng():
    import paddle_tpu
    paddle_tpu.seed(42)
    yield


@pytest.fixture(scope="session")
def tiny_llama():
    """ONE tiny LlamaForCausalLM shared by the serving-fabric test
    files (each module-scoped copy costs ~2.5s of tier-1 budget; the
    engines under test never mutate parameters)."""
    import paddle_tpu
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    paddle_tpu.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


@pytest.fixture(autouse=True, scope="session")
def _shared_engine_executables():
    """Tier-1 compile dedup: every ContinuousBatchingEngine instance
    re-jits its decode/prefill executables, but those fns are
    argument-pure by design (params/pools/tables/state/knobs are call
    arguments — that's what lets the graph contracts lower them), and
    the per-engine cache keys (`fkey`) already encode every knob that
    changes the trace (spec_k, sampling, attn_impl, kv_quant; prefill
    is keyed by page bucket). So engines over the same model with the
    same pool geometry can share one cache. Dozens of tier-1 tests
    build identically-shaped engines over the session ``tiny_llama``;
    on a 1-core CI host the duplicate compiles are minutes of wall
    time. A fresh key still compiles from scratch — the only
    observable difference is wall time."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    orig = ContinuousBatchingEngine.__init__
    cache = {}

    @functools.wraps(orig)       # inspect.signature still sees the knobs
    def patched(self, model, *args, **kwargs):
        orig(self, model, *args, **kwargs)
        key = (id(model), repr(getattr(model, "cfg", None)),
               self.max_batch, self.page_size, self.max_len,
               self._total_pages, self.decode_block)
        dec, pre = cache.setdefault(key, ({}, {}))
        self._decode_fns = dec
        self._prefill_cache = pre

    ContinuousBatchingEngine.__init__ = patched
    yield
    ContinuousBatchingEngine.__init__ = orig


@pytest.fixture
def mesh8():
    """2x4 (dp, tp) mesh over the 8 virtual CPU devices."""
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    with Mesh(devs, ("dp", "tp")) as m:
        yield m
