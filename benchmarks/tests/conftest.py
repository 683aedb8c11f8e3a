"""The benchmark's own tests run on the CPU at tiny presets: they check the
harness and the yardstick, never a speed."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest

TINY = os.path.join(ROOT, "benchmarks", "tests", "data", "BENCHMARK.tiny.json")


@pytest.fixture(scope="session", autouse=True)
def _own_compile_cache(tmp_path_factory):
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    yield
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old


@pytest.fixture(scope="session")
def tiny_runs():
    """One run of each tiny cell through run.py's own entry, shared."""
    from benchmarks import run
    cache = {}

    def get(cell, seed=7, trace=False, seconds=1.5):
        key = (cell, seed, trace)
        if key not in cache:
            cache[key] = run.run_cell(cell, seed, seconds, trace,
                                      benchmark_file=TINY, require_chip=False)
        return cache[key]
    return get
