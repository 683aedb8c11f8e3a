"""Python-free C++ PJRT deploy runner (round-4 verdict missing #4).

Reference analogue: the C++ inference API
(paddle/fluid/inference/api/analysis_predictor.cc) running exported models
without Python. Here: jit.save_deploy_bundle exports portable StableHLO +
raw params; csrc/pt_deploy_runner.cc (plain C++17 + dlopen, no Python/
protobuf/framework deps) compiles and runs it through the PJRT C API
against any plugin .so. The numeric-parity test runs it against the
installed libtpu on a machine with a chip and compares with the Python
forward.
"""

import glob
import importlib.util
import os
import subprocess

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import jit as pjit
from paddle_tpu import nn

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO, "csrc", "pt_deploy_runner.cc")
_PJRT_INC = "/opt/venv/lib/python3.12/site-packages/tensorflow/include"
_LIBTPU = os.path.join(
    os.path.dirname(importlib.util.find_spec("libtpu").origin), "libtpu.so")
# the test process is pinned to the CPU (conftest), so a chip on this
# machine is free for the runner child; found by its device node, not by jax
_HAS_CHIP = bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


@pytest.fixture(scope="module")
def runner(tmp_path_factory):
    """The runner binary, built from csrc/ on demand (git holds no binary)."""
    out = str(tmp_path_factory.mktemp("runner") / "pt_deploy_runner")
    r = subprocess.run(
        ["g++", "-std=c++17", "-O2", f"-I{_PJRT_INC}", _SRC,
         "-o", out, "-ldl"], capture_output=True, text=True, timeout=300)
    if r.returncode != 0:
        pytest.skip(f"runner build failed: {r.stderr[-400:]}")
    return out


class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 4)

    def forward(self, x):
        import jax.numpy as jnp
        return self.fc2(jnp.tanh(self.fc1(x)))


@pytest.fixture(scope="module")
def bundle(tmp_path_factory):
    pt.seed(0)
    m = _MLP()
    d = tmp_path_factory.mktemp("deploy") / "mlp_bundle"
    pjit.save_deploy_bundle(m, str(d),
                            input_spec=[pjit.InputSpec([2, 16], "float32")])
    rs = np.random.RandomState(0)
    x = rs.normal(0, 1, (2, 16)).astype(np.float32)
    expect = np.asarray(m(x))
    return str(d), x, expect


def test_bundle_layout(bundle):
    d, _, _ = bundle
    names = sorted(os.listdir(d))
    assert "manifest.txt" in names
    assert "module.stablehlo" in names
    assert "compile_options.pb" in names
    mf = open(os.path.join(d, "manifest.txt")).read()
    # Linear has 2 weights + 2 biases; one runtime input; one output
    assert mf.count("param ") == 4
    assert mf.count("input ") == 1
    assert "output f32 2 4" in mf
    # params are raw binaries matching their manifest sizes
    for line in mf.splitlines():
        if line.startswith("param "):
            _, fn, _, *dims = line.split()
            n = 4 * int(np.prod([int(x) for x in dims]))
            assert os.path.getsize(os.path.join(d, fn)) == n


def test_runner_binary_builds_and_validates_args(bundle, runner):
    r = subprocess.run([runner], capture_output=True, text=True)
    assert r.returncode != 0 and "usage" in r.stderr
    d, x, _ = bundle
    xin = os.path.join(d, "..", "x_args.bin")
    open(xin, "wb").write(x.tobytes())
    r = subprocess.run([runner, d, "--plugin", "/nonexistent.so",
                        "--input", xin],
                       capture_output=True, text=True)
    assert r.returncode != 0 and "dlopen" in r.stderr


@pytest.mark.skipif(not _HAS_CHIP, reason="no TPU on this machine")
def test_runner_matches_python_forward(bundle, runner, tmp_path):
    """The C++ binary executes the bundle on the chip through the PJRT C
    API of the installed libtpu, and its output matches the Python
    forward numerically."""
    d, x, expect = bundle
    xin = tmp_path / "x.bin"
    xin.write_bytes(x.tobytes())
    out_prefix = str(tmp_path / "out")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)   # the runner doesn't use jax at all
    r = subprocess.run(
        [runner, d, "--plugin", _LIBTPU, "--input", str(xin),
         "--out", out_prefix],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-800:]
    assert "OK" in r.stdout
    got = np.frombuffer(open(out_prefix + "0.bin", "rb").read(),
                        np.float32).reshape(2, 4)
    np.testing.assert_allclose(got, expect, rtol=2e-2, atol=2e-2)
