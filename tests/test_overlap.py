"""Comm/compute overlap controls (round-3 verdict item 4).

Reference analogues: mp_async_allreduce (mp_layers.py:458-477),
allreduce_matmul_grad_overlapping pass, sharding comm overlap. Under XLA
the overlap is scheduler-driven; these tests prove the PRECONDITIONS on
compiled HLO (CPU mesh): the TP backward's collective is independent of
the weight-grad matmul, and grad sync in the accumulation loop happens
per-microbatch inside the loop body (overlappable), plus flag plumbing.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.distributed import overlap


def _mesh(shape, names):
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


class TestBackwardIndependence:
    def test_tp_backward_allreduce_independent_of_weight_grad(self):
        """Column-parallel backward: dx needs a tp psum, dW does not — the
        HLO must keep them independent so the latency-hiding scheduler can
        overlap them (mp_async_allreduce's effect)."""
        mesh = _mesh((8,), ("tp",))
        d = 32
        W = jnp.ones((d, 4 * d))
        x = jnp.ones((16, d))

        def loss(w, xx):
            y = xx @ w                      # col-parallel matmul
            y = jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P(None, "tp")))
            return jnp.sum(jnp.tanh(y))

        f = jax.jit(jax.grad(loss, argnums=(0, 1)),
                    in_shardings=(NamedSharding(mesh, P(None, "tp")),
                                  NamedSharding(mesh, P())),
                    out_shardings=(NamedSharding(mesh, P(None, "tp")),
                                   NamedSharding(mesh, P())))
        txt = f.lower(W, x).compile().as_text()
        assert "all-reduce" in txt or "reduce-scatter" in txt
        assert overlap.backward_overlap_independent(txt), (
            "collective and weight-grad dot are not independent")


class TestGradSyncPlacement:
    def test_accum_loop_syncs_per_microbatch(self):
        """The dp grad all-reduce must sit INSIDE the microbatch loop body
        — one sync per microbatch, overlappable with the next microbatch's
        compute — not a single deferred sync (the reference's
        comm-overlap-in-backward structure)."""
        mesh = _mesh((8,), ("dp",))
        W = jnp.ones((64, 64))
        xs = jnp.ones((32, 8, 64))

        def loss_of(p, mb):
            return jnp.mean((mb @ p) ** 2)

        def step(p, batches):
            def body(gacc, mb):
                l, gg = jax.value_and_grad(loss_of)(p, mb)
                return jax.tree.map(jnp.add, gacc, gg), l
            g, _ = jax.lax.scan(body, jnp.zeros_like(p), batches)
            return p - 0.1 * g

        f = jax.jit(step,
                    in_shardings=(NamedSharding(mesh, P()),
                                  NamedSharding(mesh, P(None, "dp"))),
                    out_shardings=NamedSharding(mesh, P()))
        txt = f.lower(W, xs).compile().as_text()
        total, in_body = overlap.collectives_in_loop(txt)
        assert total >= 1
        assert in_body >= 1, "grad sync was deferred out of the loop"


class TestFlagPlumbing:
    def test_apply_overlap_flags_requires_uninit_backend(self, monkeypatch):
        # backend IS initialized in the test process → must refuse + warn
        monkeypatch.setenv(overlap.FLAGS_ENV, "")
        out = overlap.apply_overlap_flags(True, target="tpu")
        assert "--xla_tpu_enable_async_collective_fusion" not in out

    def test_flags_travel_in_libtpu_init_args(self, monkeypatch):
        # the route the installed libtpu accepts; XLA_FLAGS (where unknown
        # names abort the process) is never touched
        monkeypatch.setattr(overlap, "_backend_initialized", lambda: False)
        monkeypatch.setenv(overlap.FLAGS_ENV, "--user_flag=1")
        monkeypatch.setenv("XLA_FLAGS", "--keep=1")
        out = overlap.apply_overlap_flags(True, target="tpu")
        assert os.environ["XLA_FLAGS"] == "--keep=1"
        assert os.environ["LIBTPU_INIT_ARGS"] == out
        assert out.startswith("--user_flag=1 ")
        assert all(f in out.split() for f in
                   overlap.OVERLAP_XLA_FLAGS.split())
        # idempotent, and an explicit user "=false" is respected
        assert overlap.apply_overlap_flags(True, target="tpu") == out
        monkeypatch.setenv(
            overlap.FLAGS_ENV, "--xla_enable_async_all_gather=false")
        out = overlap.apply_overlap_flags(True, target="tpu")
        assert "--xla_enable_async_all_gather=true" not in out

    def test_pt_no_overlap_disables(self, monkeypatch):
        monkeypatch.setenv("PT_NO_OVERLAP", "1")
        monkeypatch.setenv(overlap.FLAGS_ENV, "")
        out = overlap.apply_overlap_flags(True, target="tpu")
        assert "async_collective" not in out

    def test_cpu_target_is_noop(self, monkeypatch):
        monkeypatch.setenv(overlap.FLAGS_ENV, "--foo")
        out = overlap.apply_overlap_flags(True, target="cpu")
        assert out == "--foo"


class TestStrategyWiring:
    def test_summary_reads_reference_knobs(self):
        from paddle_tpu.distributed.strategy import DistributedStrategy
        s = DistributedStrategy()
        s.tensor_parallel.mp_async_allreduce = True
        s.allreduce_matmul_grad_overlapping = True  # lands in extras
        got = overlap.strategy_overlap_summary(s)
        assert got["mp_async_allreduce"]
        assert got["allreduce_matmul_grad_overlapping"]
        assert not got["sharding_comm_overlap"]
        s.sharding.comm_overlap = True
        assert overlap.strategy_overlap_summary(s)["sharding_comm_overlap"]

    def test_fleet_init_applies_overlap(self):
        import paddle_tpu.distributed.fleet as fleet
        from paddle_tpu.distributed.strategy import DistributedStrategy
        s = DistributedStrategy()
        s.hybrid_configs = {"dp_degree": 8}
        s.tensor_parallel.mp_async_allreduce = True
        # backend is initialized in tests → flags are refused with a
        # warning, but init must not crash and strategy must be recorded
        fleet.init(strategy=s)
        try:
            assert fleet._strategy is s
        finally:
            fleet.stop()


_HLO_DEFERRED = """
HloModule m
%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %dot.1 = f32[4] dot(%gte1, %gte2), lhs_contracting_dims={0}
  ROOT %tuple.1 = (s32[], f32[4]) tuple(%c, %dot.1)
}
ENTRY %main.2 (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  %while.1 = (s32[], f32[4]) while(%t), condition=%cond.1, body=%body.1
  %gte.9 = f32[4] get-tuple-element(%while.1), index=1
  ROOT %all-reduce.1 = f32[4] all-reduce(%gte.9), to_apply=%add.1
}
"""

_HLO_INDEP = """
HloModule m
ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  %dot.1 = f32[4,4] dot(%a, %a), lhs_contracting_dims={}
  %all-reduce.2 = f32[4] all-reduce(%a), to_apply=%add.1
  ROOT %t = f32[4] add(%all-reduce.2, %a)
}
"""


class TestHloAnalysisSoundness:
    """Synthetic-HLO regressions for the analysis helpers."""

    def test_deferred_collective_not_counted_in_body(self):
        assert overlap.collectives_in_loop(_HLO_DEFERRED) == (1, 0)

    def test_async_start_forms_counted_once(self):
        h = _HLO_DEFERRED.replace("all-reduce(", "all-reduce-start(")
        assert overlap.collectives_in_loop(h) == (1, 0)

    def test_dependence_through_while_body_detected(self):
        # the all-reduce consumes the while output whose body computes the
        # dot: NOT independent — the claim must stay sound across
        # computation boundaries
        assert not overlap.backward_overlap_independent(_HLO_DEFERRED)

    def test_true_independence_detected(self):
        assert overlap.backward_overlap_independent(_HLO_INDEP)

    def test_detect_target_follows_jax_platforms(self):
        # jax's own platform choice decides, nothing else
        before = jax.config.jax_platforms
        try:
            jax.config.update("jax_platforms", "cpu")
            assert overlap._detect_target() == "cpu"
            jax.config.update("jax_platforms", "tpu,cpu")
            assert overlap._detect_target() == "tpu"
            jax.config.update("jax_platforms", None)   # jax picks: TPU first
            assert overlap._detect_target() == "tpu"
        finally:
            jax.config.update("jax_platforms", before)


class TestWarnOnce:
    def test_backend_initialized_warns_once_per_process(self, monkeypatch,
                                                        capsys):
        # fresh warn-set: earlier tests in this process may have tripped it
        monkeypatch.setattr(overlap, "_WARNED", set())
        monkeypatch.setenv(overlap.FLAGS_ENV, "")
        overlap.apply_overlap_flags(True, target="tpu")
        assert "backend already initialized" in capsys.readouterr().err
        overlap.apply_overlap_flags(True, target="tpu")
        assert capsys.readouterr().err == "", \
            "second refusal must not warn again (per-Trainer noise)"


class TestEnableOverlap:
    """enable_overlap(): the applied policy entrypoint (ISSUE 14)."""

    def test_disabled_is_strict_noop(self, monkeypatch):
        monkeypatch.setenv(overlap.FLAGS_ENV, "--sentinel=1")
        monkeypatch.delenv("PT_NO_OVERLAP", raising=False)
        res = overlap.enable_overlap(False)
        assert res == {"enabled": False, "applied": [],
                       "reason": "disabled", "flags": "--sentinel=1",
                       "fingerprint": ""}
        assert os.environ[overlap.FLAGS_ENV] == "--sentinel=1"

    def test_pt_no_overlap_wins_and_keys_fingerprint(self, monkeypatch):
        monkeypatch.setenv(overlap.FLAGS_ENV, "")
        monkeypatch.setenv("PT_NO_OVERLAP", "1")
        res = overlap.enable_overlap(True, target="tpu")
        assert res["enabled"] is False
        assert res["reason"] == "PT_NO_OVERLAP"
        # the A/B lever itself is part of the compile-cache key
        assert res["fingerprint"].startswith("PT_NO_OVERLAP;")

    def test_cpu_target_is_noop_with_reason(self, monkeypatch):
        monkeypatch.setenv(overlap.FLAGS_ENV, "--keep=1")
        monkeypatch.delenv("PT_NO_OVERLAP", raising=False)
        res = overlap.enable_overlap(True, target="cpu")
        assert res["enabled"] is False and res["reason"] == "target=cpu"
        assert os.environ[overlap.FLAGS_ENV] == "--keep=1"

    def test_initialized_backend_reports_reason(self, monkeypatch):
        # this test process HAS a live backend: the tpu path must refuse
        # (warn-once) and say why, leaving the variable untouched
        monkeypatch.setattr(overlap, "_WARNED", set())
        monkeypatch.setenv(overlap.FLAGS_ENV, "")
        monkeypatch.delenv("PT_NO_OVERLAP", raising=False)
        res = overlap.enable_overlap(True, target="tpu")
        assert res["enabled"] is False
        assert res["reason"] == "backend-initialized"
        assert os.environ[overlap.FLAGS_ENV] == ""

    def test_fingerprint_tracks_installed_flags(self, monkeypatch):
        monkeypatch.setenv(overlap.FLAGS_ENV, "")
        monkeypatch.delenv("PT_NO_OVERLAP", raising=False)
        assert overlap.overlap_fingerprint() == ""
        # foreign flags don't key the fingerprint...
        monkeypatch.setenv(overlap.FLAGS_ENV, "--xla_something_else=1")
        assert overlap.overlap_fingerprint() == ""
        # ...ours do, with their values (an explicit =false differs from
        # installed), in stable sorted order
        monkeypatch.setenv(
            overlap.FLAGS_ENV,
            "--xla_enable_async_all_gather=true "
            "--xla_tpu_overlap_compute_collective_tc=false")
        fp = overlap.overlap_fingerprint()
        assert fp == ("--xla_enable_async_all_gather=true "
                      "--xla_tpu_overlap_compute_collective_tc=false")


class TestTrainerFingerprint:
    def test_compile_cache_keys_on_overlap_env(self, monkeypatch):
        """A flag flip between runs must never aot-hit an executable
        compiled under the other schedule: the overlap fingerprint is
        part of Trainer._fp_parts (ISSUE 14)."""
        from paddle_tpu import nn
        from paddle_tpu.nn.layer import Layer
        from paddle_tpu.optimizer import SGD
        from paddle_tpu.trainer import Trainer

        class M(Layer):
            def __init__(self):
                super().__init__()
                self.l = nn.Linear(4, 1)

            def forward(self, x):
                return jnp.mean(self.l(x) ** 2)

        def fp_env():
            m = M()
            tr = Trainer(m, SGD(learning_rate=0.1, parameters=m))
            return tr._fp_parts()["env"]["overlap"]

        monkeypatch.delenv("PT_NO_OVERLAP", raising=False)
        monkeypatch.setenv(overlap.FLAGS_ENV, "")
        base = fp_env()
        monkeypatch.setenv(
            overlap.FLAGS_ENV,
            "--xla_tpu_overlap_compute_collective_tc=true")
        flagged = fp_env()
        assert flagged != base
        monkeypatch.setenv("PT_NO_OVERLAP", "1")
        assert fp_env() not in (base, flagged)
