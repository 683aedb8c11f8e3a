"""Serving-fabric tests: digest routing signal, weighted fair
admission, router policies + hysteresis (stub transport, host-only),
and the 1-replica pass-through parity anchor against a bare engine
(ISSUE 12: the fabric adds routing, never changes decoding)."""

import itertools

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.inference.prefix_cache import RadixPrefixCache
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving_fabric import (FabricTransport, InProcTransport,
                                       PrefixDigest, ServingFabric,
                                       TenantFairPolicy, TenantSpec,
                                       build_replicas)

PAGE = 8


@pytest.fixture(scope="module")
def model(tiny_llama):
    return tiny_llama


def _mk(rs, n, vocab=256):
    return rs.randint(0, vocab, (n,)).astype(np.int32)


def _tree_with(tokens_list, page_size=PAGE):
    """Host-only radix tree holding the given runs (fake page ids)."""
    tree = RadixPrefixCache(page_size)
    next_page = itertools.count(1)
    for toks in tokens_list:
        toks = np.asarray(toks, np.int32)
        n = len(toks) // page_size
        tree.insert(toks[:n * page_size], [next(next_page)
                                           for _ in range(n)])
    return tree


# ---------------------------------------------------------------------------
# digest
# ---------------------------------------------------------------------------

class TestPrefixDigest:
    def test_match_counts_whole_matched_pages(self):
        rs = np.random.RandomState(0)
        run = _mk(rs, 4 * PAGE)
        d = PrefixDigest.from_cache(_tree_with([run]))
        assert d.match_pages(run) == 4
        assert d.match_pages(run[:2 * PAGE + 3]) == 2
        # divergence in page 2 stops the chain there
        fork = run.copy()
        fork[2 * PAGE] += 1
        assert d.match_pages(fork) == 2
        assert d.match_pages(_mk(rs, 4 * PAGE)) == 0

    def test_chain_structure_prevents_positional_aliasing(self):
        """A tree holding pages [A, B] must not match a prompt [C, B]:
        the fingerprint commits to the whole history before it."""
        rs = np.random.RandomState(1)
        a, b, c = (_mk(rs, PAGE) for _ in range(3))
        d = PrefixDigest.from_cache(
            _tree_with([np.concatenate([a, b])]))
        assert d.match_pages(np.concatenate([a, b])) == 2
        assert d.match_pages(np.concatenate([c, b])) == 0

    def test_wire_round_trip(self):
        rs = np.random.RandomState(2)
        run = _mk(rs, 3 * PAGE)
        d = PrefixDigest.from_cache(_tree_with([run]), hit_rate=0.5)
        back = PrefixDigest.from_dict(d.to_dict())
        assert back.fps == d.fps
        assert back.page_size == d.page_size
        assert back.hit_rate == 0.5
        assert back.match_pages(run) == 3

    def test_entry_cap_keeps_top_of_tree(self):
        """BFS build: under a tight cap the SHALLOW boundaries (shared
        system prompts) survive, deep leaves are dropped."""
        rs = np.random.RandomState(3)
        shared = _mk(rs, PAGE)
        runs = [np.concatenate([shared, _mk(rs, 6 * PAGE)])
                for _ in range(4)]
        d = PrefixDigest.from_cache(_tree_with(runs), max_entries=3)
        assert len(d) == 3
        assert d.match_pages(runs[0]) >= 1          # shared page kept
        full = PrefixDigest.from_cache(_tree_with(runs))
        assert full.match_pages(runs[0]) == 7


# ---------------------------------------------------------------------------
# weighted fair admission
# ---------------------------------------------------------------------------

class _Req:
    def __init__(self, tenant):
        self.tenant = tenant


class TestTenantFairPolicy:
    def test_weighted_share_converges_to_weights(self):
        pol = TenantFairPolicy({"a": TenantSpec(weight=3.0),
                                "b": TenantSpec(weight=1.0)})
        queue = [_Req("a") for _ in range(40)] + \
                [_Req("b") for _ in range(40)]
        order = []
        for _ in range(40):
            pol.tick()
            i = pol.select(queue, lambda r: 10)
            order.append(queue[i].tenant)
            pol.note_admitted(queue, i, 10)
            del queue[i]
        # 3:1 weights → first 40 admits split ~30/10
        assert order.count("a") == 30 and order.count("b") == 10

    def test_token_bucket_defers_then_refills(self):
        pol = TenantFairPolicy(
            {"a": TenantSpec(weight=1.0, rate_per_tick=5.0, burst=10.0)})
        queue = [_Req("a"), _Req("a")]
        pol.tick()
        i = pol.select(queue, lambda r: 10)      # full bucket covers 10
        pol.note_admitted(queue, i, 10)          # bucket -> 0
        del queue[i]
        assert pol.select(queue, lambda r: 10) is None   # deferred
        assert pol.deferred["a"] == 1
        pol.tick()                                # +5 -> 5, still short
        assert pol.select(queue, lambda r: 10) is None
        pol.tick()                                # +5 -> 10
        assert pol.select(queue, lambda r: 10) == 0

    def test_oversized_request_overdraws_at_full_bucket(self):
        """A request pricier than the whole burst must still run once
        the bucket is full (then repays the debt in refills)."""
        pol = TenantFairPolicy(
            {"a": TenantSpec(weight=1.0, rate_per_tick=4.0, burst=8.0)})
        queue = [_Req("a")]
        pol.tick()
        assert pol.select(queue, lambda r: 100) == 0
        pol.note_admitted(queue, 0, 100)
        assert pol._bucket["a"] < 0               # debt

    def test_starvation_bound_forces_through(self):
        pol = TenantFairPolicy(
            {"b": TenantSpec(weight=1.0, rate_per_tick=0.0, burst=0.0)},
            starvation_ticks=3)
        queue = [_Req("b")]
        for _ in range(3):
            assert pol.select(queue, lambda r: 10) is None
        assert pol.select(queue, lambda r: 10) == 0   # forced

    def test_idle_tenant_cannot_bank_credit(self):
        pol = TenantFairPolicy({"a": TenantSpec(weight=1.0),
                                "b": TenantSpec(weight=1.0)})
        # a admits alone for a while
        for _ in range(10):
            q = [_Req("a")]
            pol.note_admitted(q, 0, 10)
        # b arrives: it may win ONCE on vtime 0, but the clamp stops a
        # long catch-up burst — strict alternation from here
        queue = [_Req("a"), _Req("b")] * 4
        order = []
        for _ in range(8):
            i = pol.select(queue, lambda r: 10)
            order.append(queue[i].tenant)
            pol.note_admitted(queue, i, 10)
            del queue[i]
        assert order.count("b") <= 5


# ---------------------------------------------------------------------------
# router policies over a stub transport (no engines, no device work)
# ---------------------------------------------------------------------------

class _StubTransport(FabricTransport):
    """Scripted replicas: canned statuses, instant completion."""

    def __init__(self, statuses):
        self.statuses = {s["name"]: dict(s) for s in statuses}
        for s in self.statuses.values():
            s.setdefault("role", "both")
            s.setdefault("max_batch", 8)
            s.setdefault("free_slots", 8)
            s.setdefault("queued", 0)
            s.setdefault("free_pages", 100)
            s.setdefault("itl_p99_s", None)
            s.setdefault("digest", None)
        self.submitted = {n: [] for n in self.statuses}
        self._pending = {n: [] for n in self.statuses}
        self._rid = itertools.count()

    def replica_names(self):
        return list(self.statuses)

    def submit(self, name, req):
        rid = next(self._rid)
        self.submitted[name].append(req)
        self._pending[name].append((rid, req))
        return rid

    def poll(self, name):
        fin = {rid: [7] * req["max_new_tokens"]
               for rid, req in self._pending[name]}
        self._pending[name] = []
        return {"emitted": [], "finished": fin}

    def status(self, name):
        return dict(self.statuses[name])

    def extract(self, name, tokens):
        return None

    def adopt(self, name, payload):
        return 0


def _digest_dict(tokens_list, epoch=1):
    d = PrefixDigest.from_cache(_tree_with(tokens_list))
    out = d.to_dict()
    out["epoch"] = epoch
    return out


class TestRoutingPolicies:
    def test_round_robin_cycles(self):
        tr = _StubTransport([{"name": "a"}, {"name": "b"}])
        fab = ServingFabric(tr, policy="round-robin")
        for i in range(4):
            fab.submit([1, 2, 3], 2)
        fab.run()
        assert len(tr.submitted["a"]) == 2
        assert len(tr.submitted["b"]) == 2

    def test_least_loaded_prefers_free_capacity(self):
        tr = _StubTransport([
            {"name": "a", "free_pages": 2},
            {"name": "b", "free_pages": 50}])
        fab = ServingFabric(tr, policy="least-loaded")
        fab.submit([1, 2, 3], 2)
        fab.run()
        assert len(tr.submitted["b"]) == 1

    def test_affinity_routes_to_digest_match(self):
        rs = np.random.RandomState(5)
        shared = _mk(rs, 2 * PAGE)
        tr = _StubTransport([
            {"name": "a", "free_pages": 999},    # more free: LL would pick a
            {"name": "b", "digest": _digest_dict([shared])}])
        fab = ServingFabric(tr, policy="affinity")
        prompt = np.concatenate([shared, _mk(rs, 3)])
        fab.submit(prompt, 2)
        fab.run()
        assert len(tr.submitted["b"]) == 1 and not tr.submitted["a"]
        assert fab.affinity_hits == 1

    def test_cold_prompt_falls_back_least_loaded(self):
        rs = np.random.RandomState(6)
        tr = _StubTransport([
            {"name": "a", "free_pages": 1},
            {"name": "b", "free_pages": 50,
             "digest": _digest_dict([_mk(rs, 2 * PAGE)])}])
        fab = ServingFabric(tr, policy="affinity")
        fab.submit(_mk(rs, 12), 2)               # matches nobody
        fab.run()
        assert len(tr.submitted["b"]) == 1
        assert fab.cold_routes == 1 and fab.affinity_hits == 0

    def test_hysteresis_spills_hot_affine_replica(self):
        rs = np.random.RandomState(7)
        shared = _mk(rs, 2 * PAGE)
        hot = {"name": "a", "digest": _digest_dict([shared]),
               "itl_p99_s": 0.5}
        tr = _StubTransport([hot, {"name": "b", "itl_p99_s": 0.01}])
        fab = ServingFabric(tr, policy="affinity", itl_p99_target_s=0.1,
                            hysteresis_band=0.5)
        prompt = np.concatenate([shared, _mk(rs, 3)])
        fab.submit(prompt, 2)
        fab.run()
        # a matched but is past its ITL SLO: spilled to b, counted as
        # a misroute
        assert len(tr.submitted["b"]) == 1 and not tr.submitted["a"]
        assert fab.misrouted == 1
        # recovery below target*(1-band) cools it again
        tr.statuses["a"]["itl_p99_s"] = 0.04
        fab.submit(prompt, 2)
        fab.run()
        assert len(tr.submitted["a"]) == 1
        assert fab.affinity_hits == 1

    def test_hysteresis_band_holds_hot_between_thresholds(self):
        rs = np.random.RandomState(8)
        shared = _mk(rs, 2 * PAGE)
        tr = _StubTransport([
            {"name": "a", "digest": _digest_dict([shared]),
             "itl_p99_s": 0.5},
            {"name": "b", "itl_p99_s": 0.01}])
        fab = ServingFabric(tr, policy="affinity", itl_p99_target_s=0.1,
                            hysteresis_band=0.5)
        prompt = np.concatenate([shared, _mk(rs, 3)])
        fab.submit(prompt, 2)
        fab.run()
        assert fab.stats()["hot"] == ["a"]
        # inside the band (0.05 < itl < 0.1): still hot, no flapping
        tr.statuses["a"]["itl_p99_s"] = 0.08
        fab.submit(prompt, 2)
        fab.run()
        assert fab.stats()["hot"] == ["a"]
        assert not tr.submitted["a"]

    def test_capacity_gating_backpressures_queue(self):
        tr = _StubTransport([{"name": "a", "max_batch": 2}])
        fab = ServingFabric(tr, policy="least-loaded")
        for _ in range(5):
            fab.submit([1, 2], 2)
        fab._refresh_status()
        fab._dispatch_queue()
        assert len(tr.submitted["a"]) == 2       # capacity, not queue
        assert fab.stats()["queued"] == 3
        fab.run()
        assert len(tr.submitted["a"]) == 5

    def test_named_fabrics_keep_series_distinct(self):
        """Two routers in one process (a bench A/B) publish under
        their own fabric= label instead of merging pt_fabric_*."""
        from paddle_tpu.observability.metrics import REGISTRY
        REGISTRY.reset()
        REGISTRY.enable()
        try:
            fa = ServingFabric(_StubTransport([{"name": "a"}]),
                               policy="round-robin", name="legA")
            fb = ServingFabric(_StubTransport([{"name": "a"}]),
                               policy="round-robin", name="legB")
            fa.submit([1, 2], 2)
            fa.submit([1, 2], 2)
            fb.submit([1, 2], 2)
            fa.run()
            fb.run()
            routed = REGISTRY.counter("pt_fabric_routed_total")
            assert routed.value(replica="a", how="rr", fabric="legA") == 2
            assert routed.value(replica="a", how="rr", fabric="legB") == 1
        finally:
            REGISTRY.disable()
            REGISTRY.reset()

    def test_unknown_policy_rejected(self):
        tr = _StubTransport([{"name": "a"}])
        with pytest.raises(ValueError):
            ServingFabric(tr, policy="random")

    def test_replica_rejection_fails_request_not_fabric(self):
        """A deterministic submit rejection (e.g. a prompt no pool can
        hold) fails THAT request terminally — other requests still
        serve, run() maps the failed one to None with the error kept."""
        class _Rejecting(_StubTransport):
            def submit(self, name, req):
                if len(req["prompt"]) > 100:
                    raise ValueError("prompt needs more pages than "
                                     "the pool holds")
                return super().submit(name, req)

        tr = _Rejecting([{"name": "a"}])
        fab = ServingFabric(tr, policy="least-loaded")
        bad = fab.submit(np.zeros(200, np.int32), 2)
        ok = fab.submit([1, 2, 3], 2)
        out = fab.run()
        assert out[ok] is not None and len(out[ok]) == 2
        assert out[bad] is None
        assert "more pages" in fab.failed[bad]
        assert fab.stats()["failed"] == {bad: fab.failed[bad]}


# ---------------------------------------------------------------------------
# parity anchor: fabric(1 replica, pass-through) ≡ bare engine
# ---------------------------------------------------------------------------

def _bare_streams(model, prompts, gc, max_new, spec_k=0,
                  prefix_cache=False):
    eng = ContinuousBatchingEngine(
        model, max_batch=2, page_size=PAGE, max_len=96,
        generation_config=gc, spec_k=spec_k, prefix_cache=prefix_cache)
    rids = [eng.submit(p) for p in prompts]
    out = eng.run()
    return [out[r] for r in rids]


def _fabric_streams(model, prompts, gc, max_new, spec_k=0,
                    prefix_cache=False):
    reps = build_replicas(model, 1, page_size=PAGE, max_len=96,
                          max_batch=2, generation_config=gc,
                          spec_k=spec_k, prefix_cache=prefix_cache)
    fab = ServingFabric(InProcTransport(reps), policy="round-robin")
    fids = [fab.submit(p, max_new) for p in prompts]
    out = fab.run()
    return [out[f] for f in fids]


def test_parity_single_replica_passthrough(model):
    """Tier-1 anchor: greedy, spec off, prefix off (the slow full
    matrix covers sampled × spec × prefix)."""
    rs = np.random.RandomState(10)
    prompts = [_mk(rs, n) for n in (5, 9)]
    gc = GenerationConfig(max_new_tokens=6, do_sample=False, seed=3)
    bare = _bare_streams(model, prompts, gc, 6)
    fab = _fabric_streams(model, prompts, gc, 6)
    for b, f in zip(bare, fab):
        np.testing.assert_array_equal(b, f)


@pytest.mark.slow
@pytest.mark.parametrize("do_sample", [False, True])
@pytest.mark.parametrize("spec_k", [0, 3])
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_parity_full_matrix(model, do_sample, spec_k, prefix_cache):
    """Full acceptance matrix: greedy/sampled × spec_k {0,3} × prefix
    on/off — the fabric adds routing, never changes decoding."""
    rs = np.random.RandomState(11)
    shared = _mk(rs, PAGE * 2)
    prompts = [np.concatenate([shared, _mk(rs, 4)]),
               _mk(rs, 9),
               np.concatenate([shared, _mk(rs, 7)])]
    gc = GenerationConfig(max_new_tokens=10, do_sample=do_sample, seed=5)
    bare = _bare_streams(model, prompts, gc, 10, spec_k=spec_k,
                         prefix_cache=prefix_cache)
    fab = _fabric_streams(model, prompts, gc, 10, spec_k=spec_k,
                          prefix_cache=prefix_cache)
    for b, f in zip(bare, fab):
        np.testing.assert_array_equal(b, f)


# ---------------------------------------------------------------------------
# live-engine integration: affinity actually hits the replica tree
# ---------------------------------------------------------------------------

def test_affinity_pins_prefix_family_and_hits_tree(model):
    rs = np.random.RandomState(12)
    gc = GenerationConfig(max_new_tokens=4, do_sample=False)
    reps = build_replicas(model, 2, page_size=PAGE, max_len=96,
                          max_batch=4, generation_config=gc)
    fab = ServingFabric(InProcTransport(reps), policy="affinity")
    shared = _mk(rs, 3 * PAGE)
    fam = [np.concatenate([shared, _mk(rs, 4)]) for _ in range(5)]
    fab.submit(fam[0], 4)
    fab.run()                                   # seeds ONE tree
    seeded = [n for n, c in fab.stats()["routed"].items() if c][0]
    for p in fam[1:]:
        fab.submit(p, 4)
    fab.run()
    st = fab.stats()
    assert st["routed"][seeded] == 5            # family pinned
    assert fab.affinity_hits == 4
    by_name = {r.name: r for r in reps}
    assert by_name[seeded].engine.prefix_hit_tokens >= 4 * 3 * PAGE


@pytest.mark.slow
def test_tenant_quota_defers_on_live_fabric(model):
    """A zero-rate tenant's requests sit in the GLOBAL queue while the
    unmetered tenant's flow; the starvation bound eventually forces
    them through."""
    rs = np.random.RandomState(13)
    gc = GenerationConfig(max_new_tokens=3, do_sample=False)
    reps = build_replicas(model, 1, page_size=PAGE, max_len=64,
                          max_batch=2, generation_config=gc)
    fair = TenantFairPolicy(
        {"free": TenantSpec(weight=1.0),
         "capped": TenantSpec(weight=1.0, rate_per_tick=0.0,
                              burst=0.0)},
        starvation_ticks=4)
    fab = ServingFabric(InProcTransport(reps), policy="least-loaded",
                        fair=fair)
    fc = fab.submit(_mk(rs, 6), 3, tenant="capped")
    ff = [fab.submit(_mk(rs, 6), 3, tenant="free") for _ in range(3)]
    out = fab.run()
    assert set(out) == {fc, *ff}                # everyone completed
    assert fair.deferred.get("capped", 0) >= 1  # but capped waited
    assert fair.admitted == {"free": 3, "capped": 1}


def test_engine_name_labels_keep_series_distinct(model):
    """ISSUE 12 satellite: two named engines in one process publish
    distinct per-engine registry series instead of merging."""
    from paddle_tpu.observability.metrics import REGISTRY
    rs = np.random.RandomState(14)
    gc = GenerationConfig(max_new_tokens=4, do_sample=False)
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        e1 = ContinuousBatchingEngine(
            model, max_batch=1, page_size=PAGE, max_len=64,
            generation_config=gc, name="left")
        e2 = ContinuousBatchingEngine(
            model, max_batch=1, page_size=PAGE, max_len=64,
            generation_config=gc, name="right")
        e1.submit(_mk(rs, 6))
        e1.run()
        e2.submit(_mk(rs, 6))
        e2.submit(_mk(rs, 7))
        e2.run()
        tok = REGISTRY.counter("pt_serving_tokens_total")
        assert tok.value(engine="left") == 4
        assert tok.value(engine="right") == 8
        req = REGISTRY.counter("pt_serving_requests_total")
        assert req.value(engine="left") == 1
        assert req.value(engine="right") == 2
        # percentile gauges carry the label too
        g = REGISTRY.gauge("pt_serving_ttft_seconds")
        assert g.value(q="p99", engine="left") > 0
        assert g.value(q="p99", engine="right") > 0
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


def _cli():
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    return importlib.import_module("serve_fabric")


def test_serve_fabric_cli_smoke():
    """tools/serve_fabric.py tier-1 smoke: ONE small invocation that
    exercises routing + two tenants + disaggregated prefill/handoff."""
    sf = _cli()
    out = sf.main(["--replicas", "2", "--prefill-replicas", "1",
                   "--policy", "affinity", "--disagg-threshold", "32",
                   "--families", "2", "--per-family", "2", "--cold", "1",
                   "--fam-pages", "2", "--cold-pages", "6"])
    assert out["ok"] and out["requests"] == 5
    assert out["roles"] == ["prefill", "both"]
    assert out["tenant_admitted"] == {"shared": 4, "cold": 1}
    assert out["handoffs"] == 1 and out["handoff_failures"] == 0
    assert out["handoff_bytes"] > 0
    assert sum(out["routed"].values()) >= 5


@pytest.mark.slow
def test_serve_fabric_cli_full(tmp_path):
    """Full-matrix CLI coverage: default synthetic trace, trace-file
    mode (family-synthesized prompts), and a 3-replica disagg run."""
    import json
    sf = _cli()
    out = sf.main(["--replicas", "2", "--policy", "affinity",
                   "--max-batch", "2"])
    assert out["ok"] and out["requests"] == 11
    assert sum(out["routed"].values()) >= 11
    assert out["tenant_admitted"] == {"shared": 9, "cold": 2}
    # trace-file mode: families share prefixes; same family → affinity
    trace = tmp_path / "trace.jsonl"
    lines = [{"prompt_len": 19, "family": "sys", "tenant": "a"},
             {"prompt_len": 21, "family": "sys", "tenant": "a"},
             {"prompt": list(range(1, 8)), "tenant": "b",
              "max_new_tokens": 3}]
    trace.write_text("\n".join(json.dumps(d) for d in lines))
    out2 = sf.main(["--replicas", "2", "--policy", "round-robin",
                    "--trace", str(trace)])
    assert out2["ok"] and out2["requests"] == 3
    assert set(out2["tenants"]) == {"a", "b"}
    out3 = sf.main(["--replicas", "3", "--prefill-replicas", "1",
                    "--disagg-threshold", "48",
                    "--policy", "least-loaded"])
    assert out3["ok"]
    assert out3["handoffs"] >= 1 and out3["handoff_failures"] == 0
    assert out3["roles"] == ["prefill", "both", "both"]


def test_fabric_rules_pack_shape():
    from paddle_tpu.observability.sentry import fabric_rules
    rules = fabric_rules(replicas=["r0", "r1"])
    names = {r.name for r in rules}
    assert "fabric_ttft_p99_ceiling" in names
    assert "fabric_itl_p99_ceiling" in names
    assert "fabric_handoff_failure_rate" in names
    assert "fabric_replicas_alive_floor" in names
    assert "fabric_replica_r0_prefix_hit_floor" in names
    assert "fabric_replica_r1_itl_p99_ceiling" in names
    assert len({r.name for r in rules}) == len(rules)
    # per-replica rules select the engine label
    per = [r for r in rules if r.name.startswith("fabric_replica_r0")]
    assert all(r.labels.get("engine") == "r0" for r in per)
