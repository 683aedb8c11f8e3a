"""Optimizer + LR scheduler tests (reference: test/legacy_test/test_adamw_op.py
et al. — compare against hand-rolled numpy update rules)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.optimizer import lr as lr_mod


def quad_loss_setup():
    m = nn.Linear(4, 1, bias_attr=False)
    x = jnp.ones((8, 4))
    y = jnp.zeros((8, 1))

    def loss_fn(p):
        pred = m.functional_call(p, x)
        return jnp.mean((pred - y) ** 2)

    return m, loss_fn


@pytest.mark.parametrize("cls,kw", [
    (opt.SGD, {}),
    (opt.Momentum, {"momentum": 0.9}),
    (opt.Adam, {}),
    (opt.AdamW, {"weight_decay": 0.01}),
    (opt.Lamb, {}),
    (opt.RMSProp, {}),
    (opt.Adagrad, {}),
    (opt.Adadelta, {"learning_rate": 1.0}),
    (opt.Adamax, {}),
])
def test_optimizer_decreases_loss(cls, kw):
    m, loss_fn = quad_loss_setup()
    o = cls(learning_rate=kw.pop("learning_rate", 0.05), parameters=m, **kw)
    params = m.raw_parameters()
    state = o.init_state(params)
    l0 = float(loss_fn(params))
    for _ in range(20):
        g = jax.grad(loss_fn)(params)
        params, state = o.apply_gradients(params, g, state)
    assert float(loss_fn(params)) < l0 * 0.9


def test_adamw_matches_reference_update():
    """One AdamW step vs hand-computed numpy (paddle adamw semantics:
    decoupled decay applied with lr)."""
    p0 = np.array([1.0, -2.0], np.float32)
    g0 = np.array([0.1, 0.2], np.float32)
    lr, b1, b2, eps, wd = 0.01, 0.9, 0.999, 1e-8, 0.1
    m = (1 - b1) * g0
    v = (1 - b2) * g0 ** 2
    mhat = m / (1 - b1)
    vhat = v / (1 - b2)
    expected = p0 - lr * (mhat / (np.sqrt(vhat) + eps) + wd * p0)

    o = opt.AdamW(learning_rate=lr, beta1=b1, beta2=b2, epsilon=eps, weight_decay=wd)
    params = {"w": jnp.asarray(p0)}
    state = o.init_state(params)
    new_params, _ = o.apply_gradients(params, {"w": jnp.asarray(g0)}, state)
    np.testing.assert_allclose(np.asarray(new_params["w"]), expected, rtol=1e-6)


def test_master_weights_bf16():
    o = opt.AdamW(learning_rate=0.1)
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    state = o.init_state(params)
    assert "w" in state["master"]
    assert state["master"]["w"].dtype == jnp.float32
    g = {"w": jnp.full((4,), 1e-3, jnp.bfloat16)}
    # many tiny steps: master accumulates below bf16 resolution
    for _ in range(10):
        params, state = o.apply_gradients(params, g, state)
    assert params["w"].dtype == jnp.bfloat16
    assert float(state["master"]["w"][0]) != 1.0


def test_grad_clip_global_norm():
    clip = opt.ClipGradByGlobalNorm(1.0)
    g = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped = clip(g)
    total = np.sqrt(sum(float(jnp.sum(jnp.square(v))) for v in clipped.values()))
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)
    # direction preserved
    np.testing.assert_allclose(float(clipped["b"][0] / clipped["a"][0]), 4 / 3, rtol=1e-5)


def test_imperative_step_api():
    m, loss_fn = quad_loss_setup()
    o = opt.SGD(learning_rate=0.1, parameters=m)
    params = m.raw_parameters()
    g = jax.grad(loss_fn)(params)
    before = np.asarray(m.weight).copy()
    o.step(g)
    after = np.asarray(m.weight)
    assert not np.allclose(before, after)


def test_lr_schedulers():
    s = lr_mod.CosineAnnealingDecay(0.1, T_max=10)
    assert s.get_last_lr() == pytest.approx(0.1)
    for _ in range(10):
        s.step()
    assert s.get_last_lr() == pytest.approx(0.0, abs=1e-6)

    w = lr_mod.LinearWarmup(0.1, warmup_steps=10, start_lr=0.0, end_lr=0.1)
    vals = [w.get_last_lr()]
    for _ in range(10):
        w.step()
        vals.append(w.get_last_lr())
    np.testing.assert_allclose(vals[5], 0.05, rtol=1e-6)
    np.testing.assert_allclose(vals[10], 0.1, rtol=1e-6)

    st = lr_mod.StepDecay(0.1, step_size=3, gamma=0.5)
    for _ in range(3):
        st.step()
    assert st.get_last_lr() == pytest.approx(0.05)

    n = lr_mod.NoamDecay(d_model=512, warmup_steps=100)
    n.step(50)
    n.step(100)
    peak = n.get_last_lr()
    n.step(400)
    assert n.get_last_lr() < peak


def test_scheduler_with_optimizer():
    sched = lr_mod.StepDecay(0.1, step_size=1, gamma=0.5)
    o = opt.SGD(learning_rate=sched)
    assert o.get_lr() == pytest.approx(0.1)
    sched.step()
    assert o.get_lr() == pytest.approx(0.05)


def test_grad_scaler_fp16_dynamics():
    from paddle_tpu.amp import GradScaler
    s = GradScaler(init_loss_scaling=1024.0, incr_every_n_steps=2,
                   decr_every_n_nan_or_inf=1)
    # finite grads: unscale divides by scale
    g = {"w": jnp.asarray([2048.0])}
    out = s.unscale_(g)
    np.testing.assert_allclose(np.asarray(out["w"]), [2.0])
    assert not s._found_inf
    s.update()
    # inf grads: skip + scale down
    g = {"w": jnp.asarray([jnp.inf])}
    s.unscale_(g)
    assert s._found_inf
    s.update()
    assert s.get_loss_scaling() == pytest.approx(512.0)


@pytest.mark.parametrize("cls", ["AdamW", "Momentum", "RMSProp", "Adagrad"])
def test_slots_are_placed_like_their_parameter(cls):
    """On a mesh every optimizer slot must be born sharded like its
    parameter. Created whole on the default device and sharded afterwards,
    the two AdamW moments of a 1.9 B-parameter model are 15 GB on the first
    chip — the four-chip run died there (PR 21)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu import optimizer as opt_mod
    mesh = Mesh(np.array(jax.devices()[:4]), ("fsdp",))
    sh = NamedSharding(mesh, P("fsdp", None))
    params = {"w": jax.device_put(jnp.ones((8, 4), jnp.bfloat16), sh)}
    opt = getattr(opt_mod, cls)(learning_rate=0.1)
    state = opt.init_state(params)
    leaves = jax.tree.leaves(state["slots"])
    assert leaves, "no slots created"
    for leaf in leaves:
        assert leaf.dtype == jnp.float32 and leaf.sharding == sh
        assert {s.data.shape for s in leaf.addressable_shards} == {(2, 4)}
