"""Regression tests for the round-3 advisor findings (ADVICE.md)."""
import os

import numpy as np
import jax.numpy as jnp
import pytest

RS = np.random.RandomState(0)


class TestLuPivots:
    def test_lu_unpack_round_trip(self):
        # ADVICE #1: lu() must return 1-based pivots so lu -> lu_unpack
        # reconstructs P @ L @ U == x.
        import paddle_tpu.linalg as L
        a = RS.randn(5, 5).astype("float32")
        lu, piv = L.lu(jnp.asarray(a))
        assert int(np.asarray(piv).min()) >= 1
        P, Lm, U = L.lu_unpack(np.asarray(lu), np.asarray(piv))
        rec = np.asarray(P) @ np.asarray(Lm) @ np.asarray(U)
        assert np.allclose(rec, a, atol=1e-5)

    def test_lu_get_infos(self):
        import paddle_tpu.linalg as L
        a = RS.randn(3, 3).astype("float32")
        lu, piv, info = L.lu(jnp.asarray(a), get_infos=True)
        assert int(info) == 0


class TestPsroiPool:
    def test_output_channels_gt_1(self):
        # ADVICE #2: channel layout is (co, ph, pw) — output channel
        # outermost (reference psroi_pool kernel:
        # input_channel = (c*ph_ + iy)*pw_ + ix).
        from paddle_tpu.vision.ops import psroi_pool
        ph = pw = 2
        co = 3
        c = co * ph * pw
        h = w = 8
        x = RS.randn(1, c, h, w).astype("float32")
        boxes = np.array([[0.0, 0.0, 8.0, 8.0]], np.float32)
        out = psroi_pool(jnp.asarray(x), boxes, np.array([1]), (ph, pw))
        assert out.shape == (1, co, ph, pw)
        # numpy oracle with the reference layout
        feat = x[0].reshape(co, ph, pw, h, w)
        want = np.zeros((co, ph, pw), np.float32)
        for iy in range(ph):
            for ix in range(pw):
                ys, ye = int(np.floor(8.0 * iy / ph)), int(np.ceil(8.0 * (iy + 1) / ph))
                xs, xe = int(np.floor(8.0 * ix / pw)), int(np.ceil(8.0 * (ix + 1) / pw))
                want[:, iy, ix] = feat[:, iy, ix, ys:ye, xs:xe].mean(axis=(1, 2))
        assert np.allclose(np.asarray(out[0]), want, atol=1e-5)


class TestRoiAlignAdaptive:
    def test_adaptive_matches_explicit_ratio(self):
        # ADVICE #4: sampling_ratio=-1 uses adaptive ceil(roi_size/bin)
        # per ROI. For a ROI of size 8 with 2x2 bins that's ratio 4.
        from paddle_tpu.vision.ops import roi_align
        x = RS.randn(1, 2, 16, 16).astype("float32")
        boxes = np.array([[2.0, 2.0, 10.0, 10.0]], np.float32)
        auto = roi_align(jnp.asarray(x), boxes, np.array([1]), 2,
                         sampling_ratio=-1)
        explicit = roi_align(jnp.asarray(x), boxes, np.array([1]), 2,
                             sampling_ratio=4)
        assert np.allclose(np.asarray(auto), np.asarray(explicit), atol=1e-6)

    def test_per_roi_ratio_differs(self):
        # Large and small ROIs get different grids but both stay finite.
        from paddle_tpu.vision.ops import roi_align
        x = RS.randn(1, 2, 32, 32).astype("float32")
        boxes = np.array([[0.0, 0.0, 30.0, 30.0],
                          [4.0, 4.0, 6.0, 6.0]], np.float32)
        out = roi_align(jnp.asarray(x), boxes, np.array([2]), 2,
                        sampling_ratio=-1)
        assert out.shape == (2, 2, 2, 2)
        assert np.isfinite(np.asarray(out)).all()


class TestStrategyNestedConfig:
    def test_dict_config_merges_into_cfg(self):
        # ADVICE #3: Strategy(config={'sharding': {...}}) must merge into
        # the _Cfg sub-object, not replace it.
        from paddle_tpu.distributed.compat import Strategy
        s = Strategy(config={"sharding": {"enable": True}})
        assert s.sharding.enable is True
        assert s.sharding.degree == 8  # default preserved
        s2 = Strategy(config={"pipeline": {"accumulate_steps": 4}})
        assert s2.pipeline.accumulate_steps == 4
        assert s2.pipeline.schedule_mode == "1F1B"


class TestReferenceImportIdioms:
    def test_vision_transforms_functional_path(self):
        # reference doctests do `import paddle.vision.transforms.functional`
        import importlib
        import paddle_tpu
        m = importlib.import_module("paddle_tpu.vision.transforms.functional")
        assert hasattr(m, "to_tensor") and hasattr(m, "normalize")
        from paddle_tpu.vision import transforms as T
        assert T.functional is m


class TestTensorMethods:
    def test_paddle_method_surface(self):
        import jax.numpy as jnp
        x = jnp.asarray([[1.0, -2.0], [3.0, 4.0]])
        assert x.numpy().shape == (2, 2)
        assert str(x.cast("int32").dtype) == "int32"
        assert x.unsqueeze(0).shape == (1, 2, 2)
        assert x.t().shape == (2, 2)
        assert float(x.add(1.0)[0, 0]) == 2.0
        assert x.stop_gradient is True
        x.stop_gradient = False        # accepted, inert

    def test_backward_raises_migration_error(self):
        import jax.numpy as jnp
        with pytest.raises(RuntimeError, match="layer_grad"):
            jnp.asarray([1.0]).backward()

    def test_jax_semantics_not_shadowed(self):
        import jax.numpy as jnp
        x = jnp.arange(4.0)
        assert x.reshape(2, 2).shape == (2, 2)   # numpy-style kept
        assert float(x.sum()) == 6.0

    def test_methods_on_tracers(self):
        import jax, jax.numpy as jnp
        out = jax.jit(lambda a: a.unsqueeze(0).sigmoid())(jnp.zeros((3,)))
        assert out.shape == (1, 3)

    def test_import_does_not_initialize_backend(self):
        # multi-host workers import paddle_tpu BEFORE
        # jax.distributed.initialize — the import must not touch XLA
        import subprocess, sys
        code = (
            "import os; os.environ['JAX_PLATFORMS']='cpu';"
            "import paddle_tpu;"
            "from jax._src import xla_bridge;"
            "assert not xla_bridge._backends, xla_bridge._backends;"
            "print('CLEAN')")
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True)
        assert "CLEAN" in r.stdout, r.stderr[-500:]

    def test_method_batch2_selection_structural(self):
        import jax, jax.numpy as jnp
        x = jnp.asarray([[3.0, 1.0, 2.0], [6.0, 5.0, 4.0]])
        v, i = x.topk(2)
        np.testing.assert_array_equal(np.asarray(i), [[0, 2], [0, 1]])
        assert x.tile([2, 1]).shape == (4, 3)
        assert x.expand([2, 2, 3]).shape == (2, 2, 3)
        assert x.gather(jnp.asarray([1]), axis=0).shape == (1, 3)
        assert float(x.masked_fill(x > 4, 0.0).max()) <= 4.0
        assert len(x.unbind(0)) == 2
        np.testing.assert_allclose(np.asarray(x.softmax(-1).sum(-1)), 1.0,
                                   rtol=1e-6)
        out = jax.jit(lambda a: a.index_select(jnp.asarray([0]), 1))(x)
        assert out.shape == (2, 1)


@pytest.mark.skipif(
    not os.path.isdir("/root/reference/python/paddle"),
    reason="reference doctest corpus not present in this container")
def test_reference_doctests_subset(tmp_path):
    """Fast regression: a 3-module slice of the reference-doctest sweep
    must stay green (full matrix: tools/run_reference_doctests.py,
    docs/DOCTEST_PARITY.md)."""
    import subprocess, sys, os, json
    out = str(tmp_path / "doctest_subset.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "tools/run_reference_doctests.py",
         "--modules", "tensor/logic.py", "tensor/attribute.py",
         "metric/metrics.py", "--json", out],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-500:]
    d = json.load(open(out))
    assert d["totals"]["fail"] == 0 and d["totals"]["timeout"] == 0, d["totals"]
    assert d["totals"]["pass"] >= 30, d["totals"]
