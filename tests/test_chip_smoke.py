"""chip_smoke.py's contract, as far as a machine without the chip can show it.

The script itself is the proof that the system starts on the chip; what this
file pins is everything around that: a rehearsal runs end to end and never
claims the chip, a run without a TPU fails before building anything, the
compile cache goes where it is told, and an accelerator the peaks table does
not hold is an error.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from paddle_tpu.core import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)         # conftest's 8 devices are not ours
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_runs_and_never_claims_the_chip(tmp_path, chips):
    r = _run(["--rehearse", "--chips", str(chips)], tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()]   # all parse
    assert all(ln.get("rehearsal") is True for ln in lines)
    last = lines[-1]
    assert last["ok"] is True and last["device"]["platform"] != "tpu"
    assert last["device"]["count"] == chips
    phases = {ln.get("phase") for ln in lines}
    assert phases >= ({"train4"} if chips == 4 else {"train", "serve"})
    # the cache went where the environment said, and nowhere else
    assert lines[0]["cache_dir"] == str(tmp_path / "jax_cache")
    assert lines[0]["cache_dir_from_env"] is True


def test_no_tpu_fails_before_building_a_model(tmp_path):
    r = _run([], tmp_path, timeout=120)
    assert r.returncode not in (0, None)
    assert r.stdout == "", "a run without the chip must print no result"
    assert "no TPU" in r.stderr


def test_cache_dir_from_env_is_left_to_jax(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> jax reads it itself and the code
    sets NO directory of its own."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert compile_cache.stats()["persistent_dir"] == str(tmp_path)


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.configure_compilation_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        # fixed: no temp name, pid or time in it — a second call agrees
        assert compile_cache.configure_compilation_cache() == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    src = open(compile_cache.__file__).read()
    assert "PT_COMPILE_CACHE_DIR" not in src, "the private variable is gone"


def test_peak_flops_raises_on_unknown_accelerator(monkeypatch):
    from paddle_tpu import trainer

    class Unknown:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Unknown()])
    with pytest.raises(LookupError, match="not in the peaks table"):
        trainer.device_peak_flops()

    class V5e:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    assert trainer.device_peak_flops() == 197e12
    assert trainer.peak_lookup(trainer.PEAK_HBM, "TPU v5 lite") == {
        "bytes": 16e9, "bytes_per_s": 819e9}


def test_kernel_census_reads_the_mosaic_module():
    import base64
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    body = base64.b64encode(
        b"...paddle_tpu/ops/pallas/flash_attention.py...").decode()
    hlo = ('%a = custom-call(%x), custom_call_target="tpu_custom_call", '
           'backend_config={"custom_call_config":{"body":"' + body + '"}}\n'
           '%b = f32[4] all-reduce-start(%a)\n%c = all-gather(%b)\n')
    assert chip_smoke.kernel_census(hlo) == {"tpu_custom_call": 1,
                                             "flash_attention": 1}
    assert chip_smoke.collective_census(hlo) == {"all-reduce": 1,
                                                 "all-gather": 1}
