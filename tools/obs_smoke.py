"""Observability smoke: train + serve with exporters on, then validate.

CI gate for the metrics plane (ISSUE 4 satellite): runs a short CPU
training leg (Trainer.fit with checkpointing, so the goodput ledger sees
compile/save buckets) and a short serving leg (ContinuousBatchingEngine),
both with the JSONL + Prometheus exporters attached, then checks:

* the JSONL time-series parses line-by-line (crash-safety contract);
* the Prometheus text exposition round-trips the minimal parser and
  carries the headline series (goodput buckets, compile cache, serving
  telemetry);
* the goodput buckets sum to the run's accounted wall-time;
* a forced flight-recorder dump is strict JSON;
* the cost-observatory leg (ISSUE 9): OpCostDB calibration on two micro
  canonical graphs reload-hits through a fresh instance, the live
  ``pt_model_flops_utilization`` gauge is finite, and the breakdown/MFU
  series round-trip the exporters.

Usage::

    JAX_PLATFORMS=cpu python tools/obs_smoke.py [out_dir]

Prints one JSON summary line; exit 0 = pass. ``main(out_dir)`` is
importable — tests/test_observability.py runs it in-process.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _train_leg(steps: int = 12):
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.io import DataLoader, TensorDataset
    from paddle_tpu.nn.layer import Layer
    from paddle_tpu.optimizer import SGD
    from paddle_tpu.trainer import Trainer

    class TinyReg(Layer):
        def __init__(self):
            super().__init__()
            self.l1 = nn.Linear(8, 16)
            self.l2 = nn.Linear(16, 1)

        def forward(self, x, y):
            import jax.numpy as jnp
            h = jnp.tanh(self.l1(x))
            return jnp.mean((self.l2(h) - y) ** 2)

    pt.seed(0)
    rs = np.random.RandomState(1234)
    xs = rs.randn(16 * (steps + 2), 8).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) * 0.1).astype(np.float32)
    loader = DataLoader(
        TensorDataset([xs, ys]), batch_size=16, shuffle=False,
        drop_last=True,
        collate_fn=lambda items: {"x": np.stack([i[0] for i in items]),
                                  "y": np.stack([i[1] for i in items])})
    model = TinyReg()
    tr = Trainer(model, SGD(learning_rate=0.05, parameters=model),
                 donate=False)
    hist = tr.fit(loader, steps=steps, log_every=4)
    return len(hist)


def _serving_leg():
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    eng = ContinuousBatchingEngine(
        model, max_batch=2, page_size=8, max_len=32,
        generation_config=GenerationConfig(max_new_tokens=8,
                                           do_sample=False),
        decode_block=4)
    rs = np.random.RandomState(0)
    for L in (6, 8, 5):
        eng.submit(rs.randint(0, 32, (L,)).astype(np.int32))
    out = eng.run()
    served = sum(len(v) for v in out.values())

    # speculative batch (ISSUE 6 satellite): the acceptance counters
    # must MOVE deterministically, so the drafts come from an ORACLE
    # provider that replays the precomputed greedy continuation — the
    # engine's parity contract (spec stream == generate_scan stream)
    # guarantees every draft matches its target, independent of what the
    # random-weight model happens to generate on any jax/platform
    import jax.numpy as jnp

    from paddle_tpu.inference import DraftProvider
    from paddle_tpu.inference.generation import generate_scan

    prompt = rs.randint(0, 32, (8,)).astype(np.int32)
    full = np.asarray(generate_scan(
        model, jnp.asarray(prompt)[None, :],
        GenerationConfig(max_new_tokens=10, do_sample=False)))[0]

    class Oracle(DraftProvider):
        """history[:hist_len] == full[:hist_len] by the parity contract,
        so the stream's next tokens are full[hist_len:]."""

        def propose(self, history, hist_len, k):
            ref = jnp.asarray(full, jnp.int32)
            idx = hist_len[:, None] + jnp.arange(k, dtype=jnp.int32)
            return ref[jnp.clip(idx, 0, ref.shape[0] - 1)]

    spec = ContinuousBatchingEngine(
        model, max_batch=1, page_size=8, max_len=48,
        generation_config=GenerationConfig(max_new_tokens=10,
                                           do_sample=False),
        spec_k=3, draft_provider=Oracle())
    spec.submit(prompt)
    out = spec.run()
    served += sum(len(v) for v in out.values())
    assert spec.spec_tokens_proposed > 0, "spec verify never ran"
    assert spec.spec_tokens_accepted > 0, \
        "oracle drafts not accepted: spec parity contract broken"

    # prefix-sharing leg (ISSUE 7 satellite): two requests over one
    # shared prompt through a prefix-enabled engine — the second admit
    # must HIT (two full shared pages + the COW fast path on the exact
    # repeat), moving the shared-page gauge and the hit/COW counters
    # the exporters round-trip below
    from paddle_tpu.observability.metrics import REGISTRY
    shared = rs.randint(0, 32, (17,)).astype(np.int32)
    px = ContinuousBatchingEngine(
        model, max_batch=2, page_size=8, max_len=48,
        generation_config=GenerationConfig(max_new_tokens=6,
                                           do_sample=False),
        prefix_cache=True)
    px.submit(shared)
    px.submit(np.concatenate([shared,
                              rs.randint(0, 32, (4,)).astype(np.int32)]))
    out = px.run()                        # seeds the tree
    px.submit(shared)                     # exact repeat: COW fast path
    out2 = px.run()
    served += sum(len(v) for v in out.values())
    served += sum(len(v) for v in out2.values())
    px._check_page_invariants()
    assert px.prefix_hit_tokens > 0, "prefix admit never hit"
    assert px.prefix_cow_copies > 0, "full-prompt hit skipped COW path"
    gauge = REGISTRY.gauge("pt_serving_prefix_shared_pages").value()
    assert gauge > 0, "shared-page gauge never moved"
    return served, spec.spec_stats(), px.prefix_stats(), model


def _quant_leg(errors: list, model) -> dict:
    """Quantized-serving leg (ISSUE 17 satellite): an int8-weight,
    int8-KV engine serves two requests; the ``pt_serving_kv_quant_*``
    series must move and round-trip the exporters like every other
    serving counter (main() checks the names below)."""
    import numpy as np

    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.observability.metrics import REGISTRY
    from paddle_tpu.quantization import quantize_model

    qmodel = quantize_model(model, kv_dtype="int8")
    eng = ContinuousBatchingEngine(
        qmodel, max_batch=2, page_size=8, max_len=32,
        generation_config=GenerationConfig(max_new_tokens=8,
                                           do_sample=False))
    rs = np.random.RandomState(9)
    for L in (6, 9):
        eng.submit(rs.randint(0, 32, (L,)).astype(np.int32))
    out = eng.run()
    served = sum(len(v) for v in out.values())
    if not eng.kv_quant:
        errors.append("quant leg: engine did not detect int8 KV pool")
    if eng.kv_quant_ticks <= 0:
        errors.append("quant leg: kv_quant_ticks never moved")
    ticks = REGISTRY.counter("pt_serving_kv_quant_ticks_total").value()
    if ticks <= 0:
        errors.append("quant leg: pt_serving_kv_quant_ticks_total "
                      "never incremented")
    pool_b = REGISTRY.gauge("pt_serving_kv_quant_pool_bytes").value()
    if not pool_b or pool_b <= 0:
        errors.append("quant leg: pt_serving_kv_quant_pool_bytes "
                      "gauge empty")
    return {"served": served, "kv_quant_ticks": int(eng.kv_quant_ticks),
            "pool_bytes": int(pool_b or 0)}


def _fabric_leg(out_dir: str, errors: list, model=None) -> dict:
    """Serving-fabric leg (ISSUE 12 satellite): route 4 requests across
    2 NAMED replicas — their engine series must land under distinct
    ``engine=`` labels — then kill one replica with a request mid-
    stream: the router re-admits on the survivor and a fabric sentry
    pack fires EXACTLY one replicas-alive incident (breach_for=1 fires
    the first tick, cooldown suppresses the storm)."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability.metrics import REGISTRY
    from paddle_tpu.observability.sentry import SloSentry, fabric_rules
    from paddle_tpu.serving_fabric import (InProcTransport, ServingFabric,
                                           build_replicas)
    from paddle_tpu.testing.chaos import kill_replica

    if model is None:
        pt.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
    reps = build_replicas(
        model, 2, names=["fab0", "fab1"], page_size=8, max_len=32,
        max_batch=2,
        generation_config=GenerationConfig(max_new_tokens=3,
                                           do_sample=False))
    tr = InProcTransport(reps)
    fab = ServingFabric(tr, policy="affinity")
    sentry = SloSentry(
        fabric_rules(replicas=["fab0", "fab1"]),
        incident_log=os.path.join(out_dir, "fabric_incidents.jsonl"))
    rs = np.random.RandomState(7)
    shorts = [fab.submit(rs.randint(0, 32, (6,)).astype(np.int32), 3)
              for _ in range(3)]
    flong = fab.submit(rs.randint(0, 32, (6,)).astype(np.int32), 8)
    # drive until the shorts retired (both replicas publish their
    # engine= series) while the long one is still mid-stream
    while any(fab._reqs[f].state != "done" for f in shorts):
        fab.step()
    tok = REGISTRY.counter("pt_serving_tokens_total")
    for n in ("fab0", "fab1"):
        if fab.routed.get(n, 0) and tok.value(engine=n) <= 0:
            errors.append(f"per-replica token series never moved for "
                          f"engine={n}")
    routed = REGISTRY.counter("pt_fabric_routed_total")
    if sum(routed.value(replica=n, how=h) for n in ("fab0", "fab1")
           for h in ("affinity", "rr", "ll", "cold", "spill",
                     "prefill", "disagg")) < 4:
        errors.append("pt_fabric_routed_total never moved")
    victim = fab._reqs[flong].replica
    kill_replica(tr, victim)
    out = fab.run()                       # survivor completes it
    if len(out) != 4:
        errors.append(f"fabric served {len(out)}/4 requests")
    if len(out.get(flong, ())) != 8:
        errors.append("killed replica's request did not complete on "
                      "the survivor")
    for _ in range(3):
        sentry.tick()
    alive = [i for i in sentry.incidents
             if i.rule == "fabric_replicas_alive_floor"]
    if len(alive) != 1:
        errors.append(f"replica kill fired {len(alive)} alive-floor "
                      f"incidents, expected exactly 1")
    return {"served": len(out),
            "routed": dict(fab.routed),
            "killed": victim,
            "readmitted": fab.readmitted,
            "fabric_incidents": len(alive)}


def _cost_leg(out_dir: str, errors: list) -> dict:
    """Cost-observatory leg (ISSUE 9): calibrate the OpCostDB on two
    micro canonical graphs, prove the DB round-trips through a fresh
    instance (reload hits), and check the live analytical-MFU gauge the
    train leg published is finite — the exporters round-trip the new
    series in the main body below."""
    import math

    from paddle_tpu.observability.costs import OpCostDB
    from paddle_tpu.observability.metrics import REGISTRY

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    from op_cost_probe import CI_GRAPHS, calibrate

    db_path = os.path.join(out_dir, "op_cost_db.json")
    cal = calibrate(graphs=list(CI_GRAPHS), rounds=2, iters=2,
                    db_path=db_path)
    if not cal["recorded"]:
        errors.append("op_cost_probe recorded nothing")
    fresh = OpCostDB(db_path)
    for key in cal["recorded"]:
        if fresh.lookup(key) is None:
            errors.append(f"OpCostDB reload missed {key}")
    mfu = REGISTRY.gauge("pt_model_flops_utilization").value(
        component="train")
    if not (math.isfinite(mfu) and mfu > 0):
        errors.append(f"pt_model_flops_utilization not finite-positive: "
                      f"{mfu}")
    return {"recorded_keys": len(cal["recorded"]),
            "mfu_gauge": round(mfu, 6),
            "graphs": sorted(k for k in cal["graphs"]
                             if k != "_skipped")}


def _sentry_checks(out_dir: str, errors: list, sentry) -> dict:
    """Sentry leg (ISSUE 10 satellite): the synthetic rule installed
    before the train leg is breached by construction (any published
    train loss exceeds its ceiling), so the REAL wiring — Trainer.fit
    log-boundary ticks, engine drain ticks — must have fired exactly one
    incident: hysteresis holds the first breached window, cooldown
    suppresses the storm afterwards."""
    from paddle_tpu.observability.metrics import REGISTRY
    from paddle_tpu.observability.sentry import SloSentry

    n = len(sentry.incidents)
    if n != 1:
        errors.append(f"synthetic sentry rule fired {n} incidents, "
                      f"expected exactly 1 (hysteresis+cooldown)")
    moved = REGISTRY.counter("pt_slo_incidents_total").value(
        rule="smoke_synthetic_breach")
    if moved < 1:
        errors.append("pt_slo_incidents_total{rule=...} never moved")
    inc_path = os.path.join(out_dir, "incidents.jsonl")
    recs = SloSentry.load_incidents(inc_path) if os.path.exists(
        inc_path) else []
    if not recs:
        errors.append("no incident landed in the incident JSONL")
    else:
        inc = recs[-1]
        if inc.get("rule") != "smoke_synthetic_breach":
            errors.append(f"unexpected incident rule: {inc.get('rule')}")
        ctx = inc.get("context", {})
        if not ctx.get("goodput", {}).get("total_s", 0) > 0:
            errors.append("incident missing correlated goodput snapshot")
        if not ctx.get("step_time_breakdown"):
            errors.append("incident missing correlated step-time "
                          "breakdown buckets")
    return {"incidents": n, "ticks": sentry.ticks,
            "jsonl_incidents": len(recs)}


def main(out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import sentry as sn
    from paddle_tpu.observability.exporters import (JSONLExporter,
                                                    parse_prometheus)

    jsonl_path = os.path.join(out_dir, "metrics.jsonl")
    prom_path = os.path.join(out_dir, "metrics.prom")
    flight_dir = os.path.join(out_dir, "flight")
    obs.ledger().reset()
    obs.enable(jsonl_path=jsonl_path, prom_path=prom_path,
               flight_dir=flight_dir)
    # deliberately-breached synthetic rule: every published train loss
    # exceeds the ceiling, so breach/hysteresis/cooldown ride the real
    # log-boundary ticks (12 steps / log_every=4 = 3 windows)
    sentry = sn.install(sn.SloSentry(
        [sn.Threshold("smoke_synthetic_breach", "pt_train_loss",
                      ceiling=-1e9, breach_for=2, cooldown_s=3600.0,
                      severity="critical",
                      description="obs_smoke synthetic always-breached "
                                  "rule")],
        incident_log=os.path.join(out_dir, "incidents.jsonl")))
    errors = []
    try:
        emissions = _train_leg()
        served, spec_stats, prefix_stats, smodel = _serving_leg()
        quant = _quant_leg(errors, smodel)
        served += quant["served"]
        fabric = _fabric_leg(out_dir, errors, model=smodel)
        cost = _cost_leg(out_dir, errors)
        sentry_out = _sentry_checks(out_dir, errors, sentry)
        obs.publish()

        # goodput invariant: buckets sum to accounted wall-time
        t = obs.ledger().totals()
        bucket_sum = sum(t[b] for b in obs.goodput.BUCKETS)
        if t["total_s"] > 0 and abs(bucket_sum - t["total_s"]) > \
                0.01 * t["total_s"]:
            errors.append(f"goodput buckets sum {bucket_sum} != "
                          f"total {t['total_s']}")

        # JSONL parses line-by-line
        records = JSONLExporter.load_jsonl(jsonl_path)
        if not records:
            errors.append("JSONL exporter wrote no records")
        names = {r["name"] for r in records}

        # Prometheus text round-trips the minimal parser
        with open(prom_path) as f:
            text = f.read()
        parsed = parse_prometheus(text)
        for want in ("pt_goodput_seconds", "pt_goodput_fraction",
                     "pt_train_loss", "pt_compile_cache",
                     "pt_serving_tokens_total",
                     "pt_spec_tokens_proposed_total",
                     "pt_spec_tokens_accepted_total",
                     "pt_serving_prefix_hit_tokens_total",
                     "pt_serving_cow_copies_total",
                     "pt_serving_prefix_shared_pages",
                     "pt_serving_prefix_hit_rate",
                     "pt_serving_kv_quant_ticks_total",
                     "pt_serving_kv_quant_enabled",
                     "pt_serving_kv_quant_pool_bytes",
                     "pt_fabric_routed_total",
                     "pt_fabric_replicas_alive",
                     "pt_fabric_readmitted_total",
                     "pt_fabric_replica_deaths_total",
                     "pt_fabric_ttft_seconds",
                     "pt_model_flops_utilization",
                     "pt_hbm_bw_utilization",
                     "pt_step_time_breakdown",
                     "pt_step_time_predicted_over_measured",
                     "pt_slo_incidents_total"):
            if want not in names:
                errors.append(f"{want} missing from JSONL series")
            if not any(k.startswith(want) for k in parsed):
                errors.append(f"{want} missing from Prometheus text")
        # (counter records only exist once they increment, so the
        # missing-name check above already proves the spec counters
        # moved)
        buckets = {lb[0][1] for lb in parsed.get("pt_goodput_seconds", {})}
        missing = set(obs.goodput.BUCKETS) - buckets
        if missing:
            errors.append(f"goodput buckets missing from exposition: "
                          f"{sorted(missing)}")

        # flight dump is strict JSON
        path = obs.flight_recorder.recorder().dump("smoke")
        with open(path) as f:
            dump = json.load(f)          # json.load tolerates NaN...
        json.loads(f'{{"x": {json.dumps(dump, allow_nan=False)}}}')
        # ...so re-serialize with allow_nan=False to PROVE strictness
        summary = {
            "ok": not errors,
            "train_metric_emissions": emissions,
            "served_tokens": served,
            "spec_accept_rate": round(
                spec_stats.get("spec_accept_rate", 0.0), 3),
            "prefix_hit_rate": round(
                prefix_stats.get("prefix_hit_rate", 0.0), 3),
            "prefix_cow_copies": int(
                prefix_stats.get("prefix_cow_copies", 0)),
            "cost": cost,
            "quant": quant,
            "fabric": fabric,
            "sentry": sentry_out,
            "jsonl_records": len(records),
            "prom_metrics": len(parsed),
            "goodput_fraction": t["goodput_fraction"],
            "flight_dump": os.path.basename(path),
            "errors": errors,
        }
    finally:
        sn.uninstall()
        obs.disable()
    return summary


if __name__ == "__main__":
    out = main(sys.argv[1] if len(sys.argv) > 1 else "./obs_smoke_out")
    print(json.dumps(out))
    sys.exit(0 if out["ok"] else 1)
