"""SLO sentry (ISSUE 10): declarative rules over the metrics plane,
correlated incident capture.

Contract under test:

* every rule kind (Threshold ceiling/floor/delta, EwmaSpike, RatioBand,
  Staleness) breaches on the right synthetic-gauge shapes, honors
  ``breach_for`` hysteresis (no incident before N consecutive breached
  windows) and ``cooldown_s`` (no duplicate-incident storm while the
  breach persists), and resets its streak on recovery;
* incidents carry the correlated context — the ``pt_step_time_breakdown``
  buckets and the goodput snapshot at breach time — plus the rule's
  windowed stats, and append to a crash-safe JSONL the tolerant loader
  reads back (torn tail included);
* the disabled path costs one branch: a tick with the plane off never
  snapshots the registry; ``maybe_tick`` with no sentry installed is a
  no-op;
* ``Trainer.fit`` ticks the installed sentry at log boundaries (the real
  wiring, not a hand call).
"""

import json
import warnings

import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.observability import sentry as sn
from paddle_tpu.observability.metrics import REGISTRY


@pytest.fixture(autouse=True)
def _clean():
    REGISTRY.reset()
    REGISTRY.enable()
    yield
    sn.uninstall()
    obs.disable()
    REGISTRY.reset()
    obs.ledger().reset()


def _gauge(name="pt_test_signal"):
    return REGISTRY.gauge(name, "synthetic")


# ---------------------------------------------------------------------------
# rule kinds: breach / hysteresis / cooldown
# ---------------------------------------------------------------------------

def test_threshold_ceiling_hysteresis_and_cooldown():
    g = _gauge()
    rule = sn.Threshold("r", "pt_test_signal", ceiling=1.0, breach_for=3,
                        cooldown_s=10.0)
    s = sn.SloSentry([rule])
    g.set(5.0)
    assert s.tick(now=1.0) == []          # window 1: breached, held
    assert s.tick(now=2.0) == []          # window 2: breached, held
    fired = s.tick(now=3.0)               # window 3 == breach_for: fire
    assert [i.rule for i in fired] == ["r"]
    assert fired[0].breach_windows == 3
    assert fired[0].stats["ceiling"] == 1.0
    # still breaching inside cooldown: no storm
    assert s.tick(now=4.0) == []
    assert s.tick(now=12.9) == []
    # cooldown expired, breach persists: re-fires once
    assert len(s.tick(now=13.1)) == 1
    # recovery resets the streak — next breach needs breach_for again
    g.set(0.5)
    assert s.tick(now=14.0) == []
    assert s.stats()["rules"]["r"]["streak"] == 0
    g.set(5.0)
    assert s.tick(now=30.0) == []         # streak 1 of 3, no incident
    counter = REGISTRY.counter("pt_slo_incidents_total")
    assert counter.value(rule="r") == 2.0


def test_rules_generator_not_silently_exhausted():
    """A generator of rules must yield a sentry that watches them all —
    not one whose name scan consumed the iterator into an empty list."""
    g = _gauge()
    s = sn.SloSentry(r for r in [
        sn.Threshold("a", "pt_test_signal", ceiling=1.0, breach_for=1,
                     cooldown_s=0.0),
        sn.Threshold("b", "pt_test_signal", floor=0.1, breach_for=1,
                     cooldown_s=0.0)])
    assert [r.name for r in s.rules] == ["a", "b"]
    g.set(5.0)
    assert [i.rule for i in s.tick(now=1.0)] == ["a"]


def test_faulty_rule_skipped_not_fatal():
    """One rule whose evaluation raises must not disable the sentry:
    it is skipped (warned once), the remaining rules keep firing."""
    g = _gauge()

    class Broken(sn.Threshold):
        def check(self, value, state, now):
            raise ZeroDivisionError("bad rule math")

    rules = [Broken("broken", "pt_test_signal", ceiling=1.0),
             sn.Threshold("good", "pt_test_signal", ceiling=1.0,
                          breach_for=1, cooldown_s=0.0)]
    s = sn.SloSentry(rules)
    g.set(5.0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert [i.rule for i in s.tick(now=1.0)] == ["good"]
        assert [i.rule for i in s.tick(now=2.0)] == ["good"]
    warns = [w for w in caught if "broken" in str(w.message)]
    assert len(warns) == 1                   # warned ONCE, not per tick


def test_unwritable_incident_log_warns_once_keeps_ring(tmp_path):
    """A bad incident_log path loses the file, not the incidents — and
    says so once instead of silently dropping every append."""
    g = _gauge()
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("")                   # dirname exists as a FILE
    rule = sn.Threshold("r", "pt_test_signal", ceiling=1.0,
                        breach_for=1, cooldown_s=0.0)
    s = sn.SloSentry([rule], incident_log=str(blocker / "inc.jsonl"))
    g.set(5.0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert len(s.tick(now=1.0)) == 1
        assert len(s.tick(now=2.0)) == 1
    warns = [w for w in caught if "incidents stay" in str(w.message)]
    assert len(warns) == 1                   # warned ONCE
    assert len(s.incidents) == 2             # ring still has them


def test_threshold_floor_breach():
    g = _gauge()
    rule = sn.Threshold("floor", "pt_test_signal", floor=0.4,
                        breach_for=1, cooldown_s=0.0)
    s = sn.SloSentry([rule])
    g.set(0.9)
    assert s.tick(now=1.0) == []
    g.set(0.1)
    fired = s.tick(now=2.0)
    assert len(fired) == 1 and fired[0].value == 0.1


def test_threshold_delta_rate_form():
    c = REGISTRY.counter("pt_test_drains_total", "synthetic")
    rule = sn.Threshold("rate", "pt_test_drains_total", ceiling=4.0,
                        delta=True, breach_for=1, cooldown_s=0.0)
    s = sn.SloSentry([rule])
    c.inc(100.0)
    # first window only anchors the delta — a huge absolute level is
    # not a rate breach
    assert s.tick(now=1.0) == []
    c.inc(2.0)
    assert s.tick(now=2.0) == []          # delta 2 <= 4
    c.inc(50.0)
    fired = s.tick(now=3.0)               # delta 50 > 4
    assert len(fired) == 1
    assert fired[0].stats["value"] == 50.0


def test_ewma_spike_warmup_breach_and_absorb():
    g = _gauge()
    rule = sn.EwmaSpike("spike", "pt_test_signal", spike_ratio=2.0,
                        alpha=0.5, warmup=3, breach_for=1, cooldown_s=0.0)
    s = sn.SloSentry([rule])
    for i, now in enumerate((1.0, 2.0, 3.0)):
        g.set(1.0)
        assert s.tick(now=now) == [], f"warmup window {i} must not fire"
    g.set(10.0)                            # 10 > 2 x ewma(=1.0): spike
    fired = s.tick(now=4.0)
    assert len(fired) == 1
    assert fired[0].stats["ewma"] == pytest.approx(1.0)
    # sustained level: the EWMA catches up and the spike rule goes
    # quiet (a persistent shift is Threshold/RatioBand territory)
    for now in (5.0, 6.0, 7.0, 8.0):
        g.set(10.0)
        s.tick(now=now)
    g.set(10.0)
    assert s.tick(now=9.0) == []


def test_ewma_spike_hysteresis():
    g = _gauge()
    rule = sn.EwmaSpike("spike2", "pt_test_signal", spike_ratio=2.0,
                        alpha=0.01, warmup=2, breach_for=2, cooldown_s=0.0)
    s = sn.SloSentry([rule])
    for now in (1.0, 2.0):
        g.set(1.0)
        s.tick(now=now)
    g.set(10.0)
    assert s.tick(now=3.0) == []          # breached once, held
    g.set(10.0)
    assert len(s.tick(now=4.0)) == 1      # second consecutive: fires


def test_ewma_spike_fires_at_shipped_defaults():
    """The trainer pack's exact combination (spike_ratio=3, alpha=0.3,
    breach_for=2): a sustained 10x jump must fire. Absorbing the first
    breached sample into the EWMA would demand a ~21x jump for the
    second consecutive breach — a dead detector (the EWMA is frozen
    during the pre-fire streak instead), while after the fire the new
    level IS absorbed, so a persistent shift raises one incident, not a
    storm."""
    g = _gauge()
    rule = sn.EwmaSpike("spike3", "pt_test_signal", spike_ratio=3.0,
                        alpha=0.3, warmup=3, breach_for=2, cooldown_s=0.0)
    s = sn.SloSentry([rule])
    for now in (1.0, 2.0, 3.0, 4.0):
        g.set(0.1)
        assert s.tick(now=now) == []
    g.set(1.0)                            # 10x the warmed-up average
    assert s.tick(now=5.0) == []          # streak 1, EWMA frozen at 0.1
    fired = s.tick(now=6.0)               # judged against PRE-spike avg
    assert [i.rule for i in fired] == ["spike3"]
    assert fired[0].stats["ewma"] == pytest.approx(0.1)
    # absorption resumed at the fire: the sustained level becomes the
    # new normal and goes quiet (no incident storm past cooldown=0)
    assert sum(len(s.tick(now=t)) for t in (7.0, 8.0, 9.0, 10.0)) == 0


def test_maybe_tick_systemic_failure_warns_once(monkeypatch):
    """collect() itself raising must not break the hosting loop — but
    the watcher dying must be SAID once, not swallowed forever while
    stats() keeps looking healthy."""
    g = _gauge()
    sn.install(sn.SloSentry([sn.Threshold(
        "r", "pt_test_signal", ceiling=1.0, breach_for=1)]))
    g.set(5.0)
    monkeypatch.setattr(REGISTRY, "collect",
                        lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert sn.maybe_tick() == []
        assert sn.maybe_tick() == []
    warns = [w for w in caught if "tick() failed" in str(w.message)]
    assert len(warns) == 1


def test_gauge_clear_is_noop_on_disabled_registry():
    """clear() follows the same contract as every other mutator:
    disable() disarms without destroying state — a flush racing the
    teardown must not delete series reset() is supposed to own."""
    g = _gauge()
    g.set(1.0)
    REGISTRY.disable()
    g.clear()
    REGISTRY.enable()
    assert any(e["name"] == "pt_test_signal" for e in REGISTRY.collect())
    g.clear()                                # enabled: clears for real
    assert not any(e["name"] == "pt_test_signal"
                   for e in REGISTRY.collect())


def test_skipped_window_freezes_streak_instead_of_resetting():
    """A missing series is 'stay quiet', not 'recovered': this plane
    legitimately drops series (serving clears percentile gauges when the
    latency window empties between bursts), so a workload breaching on
    every window the series EXISTS must still accumulate to breach_for."""
    g = _gauge()
    rule = sn.Threshold("r", "pt_test_signal", ceiling=1.0, breach_for=3,
                        cooldown_s=0.0)
    s = sn.SloSentry([rule])
    g.set(5.0)
    assert s.tick(now=1.0) == []                 # streak 1
    assert s.tick(now=2.0) == []                 # streak 2
    g.clear()                                    # series vanishes
    assert s.tick(now=3.0) == []                 # skipped: streak HELD
    assert s.stats()["rules"]["r"]["streak"] == 2
    g.set(5.0)                                   # burst resumes, breached
    fired = s.tick(now=4.0)
    assert [i.rule for i in fired] == ["r"]
    assert fired[0].breach_windows == 3
    # a genuine recovery still resets
    g.set(0.5)
    s.tick(now=5.0)
    assert s.stats()["rules"]["r"]["streak"] == 0


def test_ratio_band_both_directions_and_cooldown():
    g = _gauge()
    rule = sn.RatioBand("band", "pt_test_signal", baseline=2.0,
                        low=0.5, high=1.5, breach_for=1, cooldown_s=100.0)
    s = sn.SloSentry([rule])
    g.set(2.2)                             # ratio 1.1: inside
    assert s.tick(now=1.0) == []
    g.set(4.0)                             # ratio 2.0 > high
    fired = s.tick(now=2.0)
    assert len(fired) == 1 and fired[0].stats["ratio"] == 2.0
    g.set(0.5)                             # ratio 0.25 < low, cooldown on
    assert s.tick(now=3.0) == []
    # recovery then re-breach after cooldown fires again
    g.set(2.0)
    s.tick(now=4.0)
    g.set(0.5)
    assert len(s.tick(now=200.0)) == 1


def test_staleness_missing_and_frozen():
    rule = sn.Staleness("stale", "pt_never_published", breach_for=2,
                        cooldown_s=0.0)
    s = sn.SloSentry([rule])
    assert s.tick(now=1.0) == []          # one quiet window tolerated
    fired = s.tick(now=2.0)
    assert len(fired) == 1
    assert fired[0].stats["reason"] == "series missing"
    assert fired[0].value is None

    # require_change: a present-but-frozen counter is stale too
    c = REGISTRY.counter("pt_test_should_move", "synthetic")
    c.inc()
    frozen = sn.Staleness("frozen", "pt_test_should_move",
                          require_change=True, breach_for=2,
                          cooldown_s=0.0)
    s2 = sn.SloSentry([frozen])
    assert s2.tick(now=1.0) == []         # first sighting: no prev
    assert s2.tick(now=2.0) == []         # frozen window 1, held
    fired = s2.tick(now=3.0)              # frozen window 2: fires
    assert len(fired) == 1
    assert fired[0].stats["reason"] == "series frozen"
    c.inc()                               # it moved: streak resets
    assert s2.tick(now=4.0) == []
    assert s2.stats()["rules"]["frozen"]["streak"] == 0


def test_missing_series_skips_non_staleness_rules():
    rules = [sn.Threshold("t", "pt_absent", ceiling=1.0, breach_for=1),
             sn.EwmaSpike("e", "pt_absent", breach_for=1),
             sn.RatioBand("b", "pt_absent", baseline=1.0, breach_for=1)]
    s = sn.SloSentry(rules)
    assert s.tick(now=1.0) == []
    assert all(v["streak"] == 0 for v in s.stats()["rules"].values())


def test_label_subset_match_prefers_exact():
    g = _gauge("pt_test_labeled")
    g.set(1.0, component="train", bucket="stall")
    g.set(9.0, component="serving")
    rule = sn.Threshold("lab", "pt_test_labeled",
                        labels={"component": "serving"}, ceiling=5.0,
                        breach_for=1, cooldown_s=0.0)
    s = sn.SloSentry([rule])
    fired = s.tick(now=1.0)
    assert len(fired) == 1 and fired[0].value == 9.0


def test_histogram_field_resolution_skips_empty():
    h = REGISTRY.histogram("pt_test_hist", "synthetic")
    rule = sn.Threshold("h99", "pt_test_hist", field="p99", ceiling=0.5,
                        breach_for=1, cooldown_s=0.0)
    s = sn.SloSentry([rule])
    # registered-but-empty histogram exposes no p99: the rule must read
    # MISSING, never a stale zero (the percentile-publishing contract)
    assert s.tick(now=1.0) == []
    h.observe(2.0)
    assert len(s.tick(now=2.0)) == 1


# ---------------------------------------------------------------------------
# incidents: context, JSONL, counter
# ---------------------------------------------------------------------------

def test_incident_carries_correlated_context(tmp_path):
    bd = REGISTRY.gauge("pt_step_time_breakdown", "breakdown")
    for bucket, v in (("compute", 0.7), ("collective", 0.1),
                      ("host", 0.05), ("stall", 0.15)):
        bd.set(v, component="train", bucket=bucket)
    led = obs.ledger()
    led.reset()
    led.run_start()
    g = _gauge()
    g.set(9.0)
    rule = sn.Threshold("ctx", "pt_test_signal", ceiling=1.0,
                        breach_for=1, cooldown_s=0.0)
    path = str(tmp_path / "incidents.jsonl")
    s = sn.SloSentry([rule], incident_log=path)
    fired = s.tick(now=1.0)
    led.run_end()
    assert len(fired) == 1
    ctx = fired[0].context
    assert ctx["step_time_breakdown"]["train"]["compute"] == 0.7
    assert ctx["step_time_breakdown"]["train"]["stall"] == 0.15
    assert ctx["goodput"]["total_s"] >= 0.0
    assert "goodput_fraction" in ctx["goodput"]
    # the JSONL record round-trips the same context, strict JSON
    recs = sn.SloSentry.load_incidents(path)
    assert len(recs) == 1
    assert recs[0]["rule"] == "ctx"
    assert recs[0]["context"]["step_time_breakdown"]["train"][
        "collective"] == 0.1
    json.loads(json.dumps(recs[0], allow_nan=False))


def test_incident_jsonl_tolerates_torn_tail(tmp_path):
    g = _gauge()
    g.set(9.0)
    path = str(tmp_path / "inc.jsonl")
    rule = sn.Threshold("torn", "pt_test_signal", ceiling=1.0,
                        breach_for=1, cooldown_s=0.0)
    s = sn.SloSentry([rule], incident_log=path)
    s.tick(now=1.0)
    s.tick(now=2.0)
    with open(path, "a") as f:
        f.write('{"rule": "half-written')   # the crash
    recs = sn.SloSentry.load_incidents(path)
    assert len(recs) == 2
    assert all(r["rule"] == "torn" for r in recs)


def test_incident_counter_labels_per_rule():
    g = _gauge()
    g.set(9.0)
    rules = [sn.Threshold("a", "pt_test_signal", ceiling=1.0,
                          breach_for=1, cooldown_s=0.0),
             sn.Threshold("b", "pt_test_signal", ceiling=2.0,
                          breach_for=1, cooldown_s=0.0)]
    s = sn.SloSentry(rules)
    s.tick(now=1.0)
    c = REGISTRY.counter("pt_slo_incidents_total")
    assert c.value(rule="a") == 1.0
    assert c.value(rule="b") == 1.0


def test_flight_dump_fires_through_recorder(tmp_path):
    rec = obs.flight_recorder.recorder()
    rec.dir = str(tmp_path)
    rec.start()
    try:
        g = _gauge()
        g.set(9.0)
        rule = sn.Threshold("fd", "pt_test_signal", ceiling=1.0,
                            breach_for=1, cooldown_s=0.0)
        s = sn.SloSentry([rule], flight_dump=True)
        assert len(s.tick(now=1.0)) == 1
        assert rec.last_dump_path is not None
        with open(rec.last_dump_path) as f:
            dump = json.load(f)
        assert dump["reason"] == "slo_incident:fd"
        assert dump["extra"]["rule"] == "fd"
    finally:
        rec.stop()


# ---------------------------------------------------------------------------
# disabled path / installation / rate limit
# ---------------------------------------------------------------------------

def test_disabled_plane_never_snapshots(monkeypatch):
    g = _gauge()
    g.set(9.0)
    s = sn.SloSentry([sn.Threshold("d", "pt_test_signal", ceiling=1.0,
                                   breach_for=1)])
    REGISTRY.disable()

    def boom():
        raise AssertionError("collect() on the disabled path")

    monkeypatch.setattr(REGISTRY, "collect", boom)
    assert s.tick() == []
    assert s.ticks == 0
    # ISSUE 14: the full trainer pack (now incl. the exposed_comm ratio
    # band) must keep the plane-off path one attr-load + branch — no
    # rule may force a collect() just by existing in the list
    full = sn.SloSentry(sn.trainer_rules())
    assert full.tick() == []
    assert full.ticks == 0


def test_exposed_comm_rule_breaches_over_ceiling_and_skips_when_absent():
    """ISSUE 14 trainer pack: the exposed_comm RatioBand fires when the
    fraction gauge exceeds the ceiling, stays quiet inside the band, and
    — crucially — SKIPS when the series is absent (sync-lowered CPU runs
    never publish it, so they must never page)."""
    rules = [r for r in sn.trainer_rules(breach_for=1)
             if r.name == "exposed_comm"]
    assert len(rules) == 1
    s = sn.SloSentry(rules)
    assert s.tick(now=1.0) == []          # series absent: skipped
    g = REGISTRY.gauge("pt_exposed_comm_fraction", "t")
    g.set(0.9, component="train")
    fired = s.tick(now=2.0)
    assert [i.rule for i in fired] == ["exposed_comm"]
    g.set(0.2, component="train")         # healthy: mostly hidden
    assert s.tick(now=1000.0) == []


def test_maybe_tick_without_sentry_is_noop():
    assert sn.active() is None
    assert sn.maybe_tick() == []


def test_install_replaces_and_uninstall_clears():
    a = sn.SloSentry([])
    b = sn.SloSentry([])
    sn.install(a)
    assert sn.active() is a
    sn.install(b)
    assert sn.active() is b
    sn.uninstall()
    assert sn.active() is None


def test_min_interval_rate_limits_evaluation():
    g = _gauge()
    g.set(9.0)
    s = sn.SloSentry([sn.Threshold("rl", "pt_test_signal", ceiling=1.0,
                                   breach_for=1, cooldown_s=0.0)],
                     min_interval_s=10.0)
    assert len(s.tick(now=100.0)) == 1
    assert s.tick(now=105.0) == []        # inside the interval: skipped
    assert s.ticks == 1
    assert len(s.tick(now=111.0)) == 1


def test_duplicate_rule_names_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        sn.SloSentry([sn.Threshold("x", "m", ceiling=1.0),
                      sn.Staleness("x", "m")])


# ---------------------------------------------------------------------------
# default packs
# ---------------------------------------------------------------------------

def test_default_packs_cover_rule_kinds_and_stay_quiet_when_missing():
    rules = sn.trainer_rules() + sn.serving_rules()
    kinds = {r.kind for r in rules}
    assert kinds == {"threshold", "ewma_spike", "ratio_band"}
    names = [r.name for r in rules]
    assert len(set(names)) == len(names)
    # empty registry: every rule skips, nothing fires, no exceptions
    s = sn.SloSentry(rules)
    assert s.tick(now=1.0) == []


def test_serving_pack_fires_on_breached_itl():
    REGISTRY.gauge("pt_serving_itl_seconds", "itl").set(5.0, q="p99")
    rules = sn.serving_rules(itl_p99_ceiling_s=0.25, breach_for=2,
                             cooldown_s=0.0)
    s = sn.SloSentry(rules)
    assert s.tick(now=1.0) == []
    fired = s.tick(now=2.0)
    assert [i.rule for i in fired] == ["itl_p99_ceiling"]
    assert fired[0].severity == "critical"


def test_trainer_pack_goodput_floor():
    REGISTRY.gauge("pt_goodput_fraction", "gf").set(0.1)
    rules = sn.trainer_rules(goodput_floor=0.5, breach_for=2,
                             cooldown_s=0.0)
    # refresh_derived would overwrite the synthetic gauge from the real
    # (idle) ledger — disable it for this synthetic-gauge test
    s = sn.SloSentry(rules, refresh_derived=False)
    s.tick(now=1.0)
    fired = s.tick(now=2.0)
    assert "goodput_floor" in [i.rule for i in fired]


# ---------------------------------------------------------------------------
# trainer wiring: fit ticks the installed sentry at log boundaries
# ---------------------------------------------------------------------------

def test_trainer_fit_ticks_sentry_at_log_boundaries(tmp_path):
    from paddle_tpu import nn
    from paddle_tpu.nn.layer import Layer
    from paddle_tpu.optimizer import SGD
    from paddle_tpu.trainer import Trainer
    import jax.numpy as jnp
    import paddle_tpu as pt

    class TinyReg(Layer):
        def __init__(self):
            super().__init__()
            self.l1 = nn.Linear(4, 4)

        def forward(self, x, y):
            return jnp.mean((self.l1(x) - y) ** 2)

    pt.seed(0)
    model = TinyReg()
    tr = Trainer(model, SGD(learning_rate=0.01, parameters=model),
                 donate=False)
    rs = np.random.RandomState(0)

    def batches(n):
        return [{"x": jnp.asarray(rs.randn(2, 4), jnp.float32),
                 "y": jnp.asarray(rs.randn(2, 4), jnp.float32)}
                for _ in range(n)]

    path = str(tmp_path / "inc.jsonl")
    rule = sn.Threshold("train_loss_always", "pt_train_loss",
                        ceiling=-1e9, breach_for=2, cooldown_s=3600.0,
                        severity="critical")
    sentry = sn.install(sn.SloSentry([rule], incident_log=path))
    tr.fit(iter(batches(12)), steps=12, log_every=4)
    # 3 log boundaries -> 3 ticks; fires at the 2nd (hysteresis), the
    # 3rd suppressed by cooldown — exactly one incident
    assert sentry.ticks == 3
    assert len(sentry.incidents) == 1
    assert sentry.incidents[0].rule == "train_loss_always"
    recs = sn.SloSentry.load_incidents(path)
    assert len(recs) == 1


def test_window_mean_spike_fires_on_transient():
    """The step-time spike rule reads the per-window histogram mean
    (delta sum / delta count) — a single spiked window fires even
    though the 1024-sample reservoir p50 has barely moved."""
    h = REGISTRY.histogram("pt_test_step_seconds", "synthetic")
    rule = sn.EwmaSpike("spike", "pt_test_step_seconds",
                        field="window_mean", spike_ratio=3.0, alpha=0.3,
                        warmup=2, breach_for=1, cooldown_s=0.0)
    s = sn.SloSentry([rule])
    # long steady history: the reservoir median is pinned at 0.1
    for _ in range(50):
        h.observe(0.1)
    assert s.tick(now=1.0) == []          # anchors the window delta
    for now in (2.0, 3.0, 4.0):           # steady windows warm the EWMA
        for _ in range(5):
            h.observe(0.1)
        assert s.tick(now=now) == []
    for _ in range(5):                    # ONE tripled window
        h.observe(0.33)
    fired = s.tick(now=5.0)
    assert len(fired) == 1
    assert fired[0].value == pytest.approx(0.33)
    # no new observations since: the rule reads MISSING, not stale
    assert s.tick(now=6.0) == []


def test_default_rules_rejects_threshold_kwargs():
    """Tuned thresholds go to trainer_rules()/serving_rules();
    default_rules() silently ignoring them would watch the wrong SLO."""
    with pytest.raises(TypeError):
        sn.default_rules(itl_p99_ceiling_s=0.5)
