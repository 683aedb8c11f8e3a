"""Front-door load test through the full client → FrontDoor →
ServingFabric → replica stack (ISSUE 16).

One leg, the tier-1 CI leg (tests/test_load_smoke.py runs it
in-process): ~20 concurrent streaming FabricClients against a 2-replica
fabric with the shed ladder, tenant weights, and a circuit breaker
armed, plus one slow-loris client and one injected hang-then-recover
mid-run. Asserts the acceptance contract: every rejection is TYPED and
carries ``retry_after_ms``, every admitted stream completes exactly, the
slow client is evicted (and its capacity reused), the hung replica
trips/fails-over/readmits, and admitted p99 TTFT stays under the
``frontdoor_rules()`` ceiling.

Usage::

    JAX_PLATFORMS=cpu python tools/load_test.py --smoke

Prints one JSON summary line; exit 0 = pass. ``main(argv)`` is
importable.
"""

import argparse
import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# -- stack construction ------------------------------------------------------

def _tiny_model():
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pt.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def build_stack(model, *, replicas=2, max_batch=2, max_len=96,
                shedder=None, fair=None, breaker_kwargs=None,
                door_kwargs=None, policy="round-robin", names=None):
    """The full front-door stack on in-process replicas. Returns
    (door, fab, breaker); caller owns door.stop()."""
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.serving_fabric import (BreakerTransport, FrontDoor,
                                           InProcTransport,
                                           ServingFabric,
                                           build_replicas)
    reps = build_replicas(
        model, replicas, page_size=8, max_len=max_len,
        max_batch=max_batch, names=names,
        generation_config=GenerationConfig(max_new_tokens=8,
                                           do_sample=False))
    br = BreakerTransport(InProcTransport(reps), **(breaker_kwargs or {}))
    fab = ServingFabric(br, policy=policy, fair=fair, shedder=shedder)
    door = FrontDoor(fab, **(door_kwargs or {}))
    return door, fab, br


def _prompts(n, length=6, seed=7):
    import numpy as np
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 32, (length,)).astype(np.int32).tolist()
            for _ in range(n)]


def _warmup(door, fab, *, replicas=2, max_batch=2):
    """Compile every shape the waves will hit BEFORE anything is timed:
    the cold prefill buckets (short prompts and the longer
    prompt+replay re-prefill bucket a failover pays) and the
    full-batch decode shape, on EVERY replica."""
    from paddle_tpu.serving_fabric import FabricClient
    n = replicas * max_batch
    shorts = _prompts(n, length=6, seed=1)
    longs = _prompts(replicas, length=14, seed=2)
    errs = []

    def one(i, p):
        try:
            c = FabricClient(door.host, door.port, max_attempts=2,
                             io_timeout_s=300.0)
            c.generate(p, 8, request_id=f"warm-{i}")
        except Exception as e:      # noqa: BLE001 — surfaced below
            errs.append(e)

    for batch in (shorts, longs):
        ts = [threading.Thread(target=one, args=(i, p))
              for i, p in enumerate(batch)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300.0)
        if errs:
            raise RuntimeError(f"warmup failed: {errs[0]}")
    fab.reset_latency_stats()


# -- the smoke leg -----------------------------------------------------------

def _slow_loris(door, *, sid, n_tokens=48):
    """Connect, submit, then never read: the server must evict us (the
    write path stalls against our closed TCP window) without stalling
    anyone else. The long id pads every event so a few dozen tokens
    overflow the shrunken server-side send buffer. Returns the open
    socket (caller keeps it alive for the duration of the wave)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    # tiny receive window BEFORE connect: with the server's shrunken
    # send buffer, a couple of padded events fill both and the writer
    # blocks — the stalled-sendall state a real slow-loris produces
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 2048)
    s.settimeout(5.0)
    s.connect((door.host, door.port))
    msg = {"op": "submit", "id": sid, "prompt": [3, 1, 4, 1, 5, 9],
           "max_new_tokens": n_tokens}
    s.sendall(json.dumps(msg).encode() + b"\n")
    return s


def smoke(ttft_ceiling_s: float = 30.0) -> dict:
    from paddle_tpu.observability.metrics import REGISTRY
    from paddle_tpu.observability.sentry import SloSentry, frontdoor_rules
    from paddle_tpu.observability.tracing import TRACER
    from paddle_tpu.serving_fabric import (FabricClient, LoadShedder,
                                           TenantFairPolicy, TenantSpec)
    from paddle_tpu.testing.chaos import hang_replica, unhang_replica

    was_enabled = REGISTRY.enabled
    REGISTRY.enable()
    TRACER.enable()          # the smoke wave runs traced (ISSUE 19)
    errors = []
    model = _tiny_model()
    fair = TenantFairPolicy({"prod": TenantSpec(weight=2.0),
                             "bulk": TenantSpec(weight=0.5)})
    shedder = LoadShedder(queue_depth_hi=4, queue_depth_lo=1,
                          queue_cap=10, breach_ticks=1, recover_ticks=3,
                          retry_after_ms=200.0)
    door, fab, br = build_stack(
        model, shedder=shedder, fair=fair,
        breaker_kwargs=dict(open_cooldown_s=0.5, probe_successes=2,
                            probe_timeout_s=0.3),
        door_kwargs=dict(outbox_max=64, write_stall_s=0.25,
                         sndbuf=2048),
        names=["ld0", "ld1"])
    door.start()
    summary = {}
    try:
        _warmup(door, fab)

        results, failures = {}, {}
        lock = threading.Lock()
        go = threading.Barrier(19)

        def client(cid, tenant, attempts):
            c = FabricClient(door.host, door.port,
                             max_attempts=attempts, io_timeout_s=300.0)
            go.wait(timeout=60.0)
            try:
                r = c.generate(_prompts(1, seed=100 + cid)[0], 8,
                               tenant=tenant,
                               request_id=f"{tenant}-{cid}")
                with lock:
                    results[f"{tenant}-{cid}"] = r
            except Exception as e:   # noqa: BLE001 — collected
                with lock:
                    failures[f"{tenant}-{cid}"] = e

        threads = [threading.Thread(target=client,
                                    args=(i, "prod", 8), daemon=True)
                   for i in range(13)]
        threads += [threading.Thread(target=client,
                                     args=(i, "bulk", 2), daemon=True)
                    for i in range(5)]

        # the hang controller: wedge one replica mid-wave, tighten the
        # poll budget ONLY for detection (the survivor's failover
        # re-prefill may recompile nothing — warmed — but budgets stay
        # honest), then recover and wait for half-open readmission
        hang_report = {}

        def hangman():
            go.wait(timeout=60.0)
            time.sleep(0.75)
            victim = "ld0"
            hang_replica(br, victim)
            br.op_timeouts["poll"] = 1.2
            t0 = time.monotonic()
            while victim not in fab._dead and \
                    time.monotonic() - t0 < 15.0:
                time.sleep(0.02)
            hang_report["tripped_s"] = round(time.monotonic() - t0, 3)
            hang_report["tripped"] = victim in fab._dead
            br.op_timeouts["poll"] = 30.0
            time.sleep(0.5)
            unhang_replica(br, victim)
            t1 = time.monotonic()
            while (victim in fab._dead or br.state(victim) != "closed") \
                    and time.monotonic() - t1 < 20.0:
                time.sleep(0.05)
            hang_report["readmitted"] = victim not in fab._dead
            hang_report["breaker"] = br.state(victim)

        threads.append(threading.Thread(target=hangman, daemon=True))
        # every event echoes the id: an 8KB id makes each tok event
        # outweigh the shrunken socket buffers on its own
        slow_sid = "slow-" + "x" * 8000
        slow_sock = _slow_loris(door, sid=slow_sid, n_tokens=90)
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        try:
            slow_sock.close()
        except OSError:
            pass

        # -- acceptance checks ---------------------------------------
        for sid, r in results.items():
            if len(r.tokens) != 8:
                errors.append(f"{sid}: {len(r.tokens)}/8 tokens")
        rejects = [ev for r in results.values() for ev in r.rejects]
        for sid, e in failures.items():
            w = getattr(e, "to_wire", None)
            if w is None:
                errors.append(f"{sid}: untyped failure {e!r}")
            else:
                rejects.append(w())
        for ev in rejects:
            if ev.get("kind") not in ("overloaded", "all_down",
                                      "deadline"):
                errors.append(f"untyped rejection: {ev}")
            if ev.get("retry_after_ms") is None:
                errors.append(f"rejection without retry_after_ms: {ev}")
        if not results:
            errors.append("no client completed")
        shed_stats = shedder.stats()
        if not rejects and not shed_stats.get("shed"):
            errors.append("19 clients against 4 slots never shed — "
                          "the overload leg exercised nothing")
        if not hang_report.get("tripped"):
            errors.append(f"hung replica never tripped: {hang_report}")
        if not hang_report.get("readmitted") or \
                hang_report.get("breaker") != "closed":
            errors.append(f"hung replica never readmitted: "
                          f"{hang_report}")
        states = door.stream_states()
        if states.get(slow_sid) not in ("orphaned", None):
            errors.append(f"slow-loris stream not evicted: "
                          f"{states.get(slow_sid)}")
        slow_evicted = REGISTRY.counter(
            "pt_frontdoor_disconnects_total",
            "client connections dropped").value(reason="slow")
        if slow_evicted < 1:
            errors.append("pt_frontdoor_disconnects_total{reason=slow} "
                          "never moved")

        # capacity reusable after the slow client's eviction
        from paddle_tpu.serving_fabric import FabricClient as FC
        after = FC(door.host, door.port, max_attempts=8,
                   io_timeout_s=300.0).generate(
            _prompts(1, seed=999)[0], 8, tenant="prod",
            request_id="post-wave")
        if len(after.tokens) != 8:
            errors.append("post-wave request did not complete: the "
                          "evicted slow client leaked capacity")

        # admitted p99 TTFT under the frontdoor_rules ceiling — the
        # same ceiling wired into the sentry pack
        lat = fab.latency_stats()
        rules = frontdoor_rules(replicas=["ld0", "ld1"],
                                ttft_p99_ceiling_s=ttft_ceiling_s,
                                breach_for=1)
        sentry = SloSentry(rules)
        fab.publish_metrics()
        sentry.tick()
        ttft_inc = [i for i in sentry.incidents
                    if i.rule == "frontdoor_ttft_p99_ceiling"]
        if lat.get("ttft_p99_s", 0.0) > ttft_ceiling_s:
            errors.append(f"admitted p99 TTFT "
                          f"{lat.get('ttft_p99_s'):.3f}s over the "
                          f"{ttft_ceiling_s}s ceiling")
        if ttft_inc:
            errors.append("frontdoor_ttft_p99_ceiling sentry fired")

        # distributed tracing (ISSUE 19): the wave must leave complete
        # stitched traces — frontdoor accept through replica
        # prefill/decode to stream drain — with >=95% of some request's
        # TTFT attributed to NAMED hops (the acceptance bound)
        traces = TRACER.recent_traces()
        trace_report = ""
        named = []
        if not traces:
            errors.append("tracing produced no complete traces")
        else:
            from paddle_tpu.analysis import critical_path as cp
            agg = cp.aggregate(traces)
            for t in traces:
                att = cp.attribute_trace(t)
                if att["ttft_s"]:
                    named.append(
                        1.0 - att["ttft_frac"].get("untracked", 0.0))
            full = max(traces,
                       key=lambda t: len({s["name"].split("::")[0]
                                          for s in t["spans"]}))
            names = {s["name"] for s in full["spans"]}
            for pref in ("frontdoor::request", "frontdoor::submit",
                         "fabric::queue", "replica::queue",
                         "replica::prefill", "replica::decode",
                         "frontdoor::drain"):
                if not any(n.startswith(pref) for n in names):
                    errors.append(f"stitched trace missing {pref} spans")
            if not named or max(named) < 0.95:
                errors.append(
                    f"TTFT attribution never reached 95% named hops "
                    f"(best {max(named) if named else None})")
            worst = max(traces,
                        key=lambda t: t["summary"].get("ttft_s") or 0.0)
            trace_report = (cp.format_table(agg) + "\n\n"
                            + cp.format_span_tree(worst))
            print(trace_report, file=sys.stderr)

        summary = {
            "ok": not errors,
            "completed": len(results),
            "failed_typed": len(failures),
            "rejects": len(rejects),
            "shed": shed_stats,
            "retries": door.retries,
            "breaker_trips": br.trips,
            "hang": hang_report,
            "ttft_p99_s": round(lat.get("ttft_p99_s", 0.0), 4),
            "ttft_ceiling_s": ttft_ceiling_s,
            "traces": len(traces),
            "trace_ttft_named_frac_best": (round(max(named), 4)
                                           if named else None),
            "errors": errors,
        }
    finally:
        door.stop()
        TRACER.disable()
        REGISTRY.enabled = was_enabled
    return summary


# -- CLI ---------------------------------------------------------------------

def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="the acceptance leg (~20 clients, one slow, "
                         "one hang); the only leg, so the flag is "
                         "optional")
    ap.add_argument("--ttft-ceiling", type=float, default=30.0,
                    help="frontdoor_rules p99 TTFT ceiling in seconds")
    args = ap.parse_args(argv)
    return smoke(ttft_ceiling_s=args.ttft_ceiling)


if __name__ == "__main__":
    out = main()
    print(json.dumps(out))
    sys.exit(0 if out.get("ok") else 1)
