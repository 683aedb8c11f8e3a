"""Bench baselines: pin ratio metrics from BENCH artifacts, diff with
noise-aware bands.

The bench-variance policy (BASELINE.md, every round since PR 3): on this
noisy shared host, absolute tok/s is weather — RATIO metrics (MFU,
A/B speedups, efficiency and hit rates, the predicted-over-measured
drift) are the signal. This module turns that policy into a mechanical
gate:

* :data:`RATIO_METRICS` — the census of comparable ratio rows a bench
  record can carry, each with the direction that counts as *worse* and a
  per-metric relative noise band;
* :func:`pin_baseline` — extract those rows from an artifact into a
  small pinned-baseline dict (checked in as ``tools/bench_baseline.json``);
* :func:`diff_records` — compare a candidate record against a baseline
  (or a second artifact): a metric regresses only when it moves past its
  band in the *worse* direction. Ratios are backend-relative, so records
  from different backends (a TPU round vs a CPU fallback round) compare
  NOTHING — every row is skipped with the reason named, and the verdict
  is "incomparable", not a fake pass/fail.

Both the driver's round files (``BENCH_r*.json``, ``{"parsed": {...}}``)
and raw bench output records (``{"metric": ..., "detail": {...}}``) load
through :func:`load_record`.
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["RatioMetric", "RATIO_METRICS", "load_record", "backend_of",
           "ratio_metrics_of", "pin_baseline", "diff_records",
           "BenchDiff", "BASELINE_SCHEMA"]

BASELINE_SCHEMA = "pt-bench-baseline-v1"

_DEFAULT_BAND = 0.25          # relative; generous for a shared noisy host


class RatioMetric:
    """One comparable row: where it lives in the record, which direction
    is worse, and how far it may move before the gate calls regression.

    ``worse`` — "lower" (throughput-like: MFU, speedups, hit rates),
    "higher" (overhead-like: obs_overhead_ratio), or "either" (a
    self-ratio whose healthy value is ~1.0, drifting in any direction is
    bad). ``band`` is relative: candidate ÷ baseline beyond
    ``1 ± band`` in the worse direction regresses.

    ``cpu_band`` widens the band when BOTH records ran the cpu tier:
    MFU and vs_baseline on a fixed config are linear rescalings of
    absolute tok/s, so comparing them ACROSS runs on this shared host
    re-imports the very noise the ratio policy exists to dodge
    (documented swings ~±40%). The wide cpu band keeps the gate able to
    catch catastrophic collapses (a wrong loss head, a dead fast path)
    without paging on weather; within-run A/B ratios (speedups,
    overhead, drift) keep their tight bands on every backend.
    """

    def __init__(self, name: str, worse: str = "lower",
                 band: float = _DEFAULT_BAND, headline: bool = False,
                 cpu_band: Optional[float] = None):
        assert worse in ("lower", "higher", "either")
        self.name = name
        self.worse = worse
        self.band = float(band)
        self.headline = headline        # lives at record top level
        self.cpu_band = cpu_band        # wider band on the cpu tier


RATIO_METRICS: Dict[str, RatioMetric] = {m.name: m for m in [
    RatioMetric("vs_baseline", "lower", headline=True, cpu_band=0.45),
    # MFU family (PaLM closed form + causal + fenced + HLO-attributed):
    # cross-RUN absolute-derived on a fixed config, hence cpu_band
    RatioMetric("mfu", "lower", cpu_band=0.45),
    RatioMetric("mfu_causal", "lower", cpu_band=0.45),
    RatioMetric("mfu_fenced_causal", "lower", cpu_band=0.45),
    RatioMetric("mfu_analytical", "lower", cpu_band=0.45),
    RatioMetric("longctx_mfu", "lower", cpu_band=0.45),
    RatioMetric("longctx_mfu_causal", "lower", cpu_band=0.45),
    # cost-model drift: healthy ~1.0, either direction is drift — wide
    # band, the live RatioBand rule holds the tight one
    RatioMetric("step_time_predicted_over_measured", "either", band=0.5),
    # observability overhead: metrics-on ÷ metrics-off, healthy ~1.0
    RatioMetric("obs_overhead_ratio", "higher", band=0.15),
    # distributed tracing (ISSUE 19): traced ÷ untraced smoke load-test
    # wall time, healthy ~1.0 — the zero-cost contract's bench gate.
    # Same shape as obs_overhead_ratio but the smoke leg is a full
    # serving stack (compiles amortized, still host-noisy): wider band
    RatioMetric("trace_overhead_ratio", "higher", band=0.25),
    # serving efficiency and A/B speedups (interleaved min-of-rounds
    # ratios, but still rider on host noise — keep the wide default)
    RatioMetric("serving_decode_efficiency", "lower", band=0.35),
    RatioMetric("spec_decode_speedup", "lower", band=0.35),
    RatioMetric("spec_decode_speedup_b4", "lower", band=0.35),
    RatioMetric("spec_decode_speedup_vs_block", "lower", band=0.35),
    RatioMetric("spec_decode_speedup_vs_block_b4", "lower", band=0.35),
    RatioMetric("spec_accept_rate", "lower"),
    RatioMetric("spec_accept_rate_b4", "lower"),
    RatioMetric("spec_mean_accepted_len", "lower"),
    RatioMetric("prefix_reuse_ttft_speedup", "lower", band=0.35),
    RatioMetric("prefix_hit_rate", "lower"),
    # serving fabric (ISSUE 12): within-run A/B ratios over interleaved
    # min-of-rounds legs — affinity÷round-robin TTFT and goodput, and
    # the disagg÷no-disagg decode ITL p99 (lower is better there, so
    # HIGHER is worse; generous band, ITL p99 tails ride host noise)
    RatioMetric("fabric_affinity_ttft_speedup", "lower", band=0.35),
    RatioMetric("fabric_goodput_ratio", "lower", band=0.35),
    RatioMetric("fabric_p99_itl_with_disagg_ratio", "higher", band=0.5),
    RatioMetric("loss_head_fused_speedup", "lower", band=0.35),
    # sharding planner (ISSUE 11): rank-order validation vs measured.
    # top1-in-top2 is binary (1.0 healthy) — any drop to 0 must page,
    # hence the tight band; agreement is a 0.5-1.0 concordance score
    # riding measured step times, so it keeps the wide default
    RatioMetric("planner_top1_is_measured_top2", "lower", band=0.01),
    RatioMetric("planner_rank_agreement", "lower", band=0.3),
    RatioMetric("planner_predicted_mfu", "lower", cpu_band=0.45),
    # ZeRO/FSDP axis (ISSUE 18): fsdp4 ÷ dp4 measured step time at
    # equal devices (the gather/reduce-scatter tax — growth means the
    # overlap contract stopped hiding the windows; rides host noise,
    # wide band) and the same pair's closed-form HBM high-water ratio
    # (deterministic arithmetic, tight band — a rise means the ZeRO
    # sharding of params/slots/grads eroded)
    RatioMetric("fsdp_step_overhead_ratio", "higher", band=0.5),
    RatioMetric("fsdp_hbm_ratio", "higher", band=0.1),
    # latency-hiding contract (ISSUE 14): exposed (un-overlapped) comm
    # fraction of the dp2xtp2 canonical step — structural per build, a
    # GROWING fraction means a hiding window collapsed (higher=worse) —
    # and the overlap-flags off÷on step-time ratio (interleaved
    # min-of-rounds subprocess A/B; rides host noise, wide band)
    RatioMetric("overlap_exposed_comm_fraction", "higher", band=0.5),
    RatioMetric("overlap_on_step_speedup", "lower", band=0.35),
    # front-door robustness (ISSUE 16): shed-enabled ÷ shed-disabled
    # admitted goodput at 2x capacity offered load (shedding must BUY
    # throughput for admitted work, lower = the ladder stopped paying
    # for itself), and hung-replica p99 TTFT with breaker ÷ without
    # (tight op budgets ÷ loose ones — the breaker's early trip must
    # keep the tail DOWN, so higher is worse; both ride host noise and
    # thread scheduling, generous bands)
    RatioMetric("frontdoor_goodput_under_overload", "lower", band=0.4),
    RatioMetric("frontdoor_p99_ttft_with_breaker_ratio", "higher",
                band=0.5),
    # quantized serving (ISSUE 17): int8 ÷ bf16 engine tok/s at EQUAL
    # HBM budget (interleaved min-of-rounds; the bf16 leg thrashes by
    # design, so the ratio rides recompute scheduling — wide band), the
    # max-resident-slots capacity ratio (integer slot counts over the
    # engine's own preemption machinery — near-deterministic, tight
    # band), the int8 leg's serving÷raw-kernel efficiency, and the
    # greedy int8-vs-bf16 stream agreement (free-running, one near-tie
    # flip cascades; the hard floor lives in the tests)
    RatioMetric("quant_decode_speedup", "lower", band=0.4),
    RatioMetric("quant_kv_capacity_ratio", "lower", band=0.15),
    RatioMetric("quant_serving_decode_efficiency", "lower", band=0.35),
    RatioMetric("quant_stream_agreement", "lower", band=0.4),
    # expert parallelism (ISSUE 20): replicated ÷ ep2 measured MoE step
    # at equal devices/experts (interleaved min-of-rounds; the a2a tax
    # vs the expert-HBM win — collapse means the dispatch path
    # regressed; rides host noise, wide band), the priced-census
    # per-a2a seconds ÷ a wall-clock shard_map all-to-all (cost-model
    # drift for the NEW collective; CPU constants are nominal, so only
    # the drift-of-the-ratio is gated, either direction)
    RatioMetric("moe_ep_step_speedup", "lower", band=0.35),
    RatioMetric("moe_ep_a2a_pred_over_measured", "either", band=0.5),
]}


# ---------------------------------------------------------------------------
# record loading / extraction
# ---------------------------------------------------------------------------

def load_record(path: str) -> dict:
    """Load a bench record from either shape: a driver round file
    (``BENCH_r*.json``: ``{"parsed": {...}}``) or a raw bench payload /
    pinned baseline."""
    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    if isinstance(d.get("parsed"), dict):
        d = d["parsed"]
    return d


def is_baseline(record: dict) -> bool:
    return record.get("schema") == BASELINE_SCHEMA


def backend_of(record: dict) -> str:
    if is_baseline(record):
        return str(record.get("backend", "unknown"))
    det = record.get("detail") or {}
    return str(det.get("backend", "unknown"))


def ratio_metrics_of(record: dict) -> Dict[str, float]:
    """The finite ratio rows present in ``record`` (baseline dicts pass
    straight through)."""
    if is_baseline(record):
        src = record.get("metrics", {})
        return {k: float(v) for k, v in src.items()
                if k in RATIO_METRICS and _finite_num(v)}
    det = record.get("detail") or {}
    out: Dict[str, float] = {}
    for name, spec in RATIO_METRICS.items():
        v = record.get(name) if spec.headline else det.get(name)
        if _finite_num(v):
            out[name] = float(v)
    return out


def _finite_num(v) -> bool:
    # zero is a VALID candidate value (a collapsed hit rate is the most
    # extreme regression, not a missing row) — only non-numbers and
    # non-finite floats read as absent
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and math.isfinite(v))


def pin_baseline(record: dict, source: str = "") -> dict:
    """Freeze ``record``'s ratio rows into the pinned-baseline shape the
    CI gate diffs against. Deliberately tiny and diff-friendly — this is
    a checked-in file. Zero-valued rows are not pinned: a zero baseline
    can anchor no ratio (and usually means the probe didn't run)."""
    return {"schema": BASELINE_SCHEMA,
            "source": source or record.get("metric", ""),
            "backend": backend_of(record),
            "metrics": {k: round(v, 6)
                        for k, v in sorted(ratio_metrics_of(record)
                                           .items()) if v != 0}}


# ---------------------------------------------------------------------------
# diffing
# ---------------------------------------------------------------------------

class BenchDiff:
    """Result of one baseline-vs-candidate comparison."""

    def __init__(self, rows: List[dict], backend_base: str,
                 backend_cand: str, note: str = ""):
        self.rows = rows
        self.backend_base = backend_base
        self.backend_cand = backend_cand
        self.note = note

    @property
    def regressions(self) -> List[str]:
        return [r["metric"] for r in self.rows
                if r["status"] == "regressed"]

    @property
    def improvements(self) -> List[str]:
        return [r["metric"] for r in self.rows
                if r["status"] == "improved"]

    @property
    def compared(self) -> int:
        return sum(r["status"] != "skipped" for r in self.rows)

    @property
    def ok(self) -> bool:
        return not self.regressions

    def verdict(self) -> str:
        if self.regressions:
            return "regressed"
        if self.compared == 0:
            return "incomparable"
        return "ok"

    def summary(self) -> dict:
        return {"verdict": self.verdict(), "compared": self.compared,
                "skipped": len(self.rows) - self.compared,
                "regressions": self.regressions,
                "improvements": self.improvements,
                "backend": f"{self.backend_base} vs {self.backend_cand}",
                **({"note": self.note} if self.note else {})}

    def format(self) -> str:
        lines = [f"{'metric':<38} {'base':>10} {'cand':>10} "
                 f"{'ratio':>7} {'band':>11}  status"]
        for r in self.rows:
            if r["status"] == "skipped":
                lines.append(f"{r['metric']:<38} {'-':>10} {'-':>10} "
                             f"{'-':>7} {'-':>11}  skipped"
                             f" ({r['reason']})")
                continue
            band = f"±{r['band']:.0%}" if r["worse"] == "either" else (
                f"-{r['band']:.0%}" if r["worse"] == "lower"
                else f"+{r['band']:.0%}")
            lines.append(
                f"{r['metric']:<38} {r['base']:>10.4g} {r['cand']:>10.4g}"
                f" {r['ratio']:>7.3f} {band:>11}  {r['status']}")
        if self.note:
            lines.append(f"note: {self.note}")
        s = self.summary()
        lines.append(f"verdict: {s['verdict']} "
                     f"(compared={s['compared']}, "
                     f"skipped={s['skipped']}"
                     + (f", regressions={','.join(self.regressions)}"
                        if self.regressions else "") + ")")
        return "\n".join(lines)


def diff_records(base: dict, cand: dict,
                 band_override: Optional[float] = None) -> BenchDiff:
    """Compare candidate against baseline over the ratio census.

    Per-metric: ``ratio = cand ÷ base``; worse-direction moves past the
    band regress, better-direction moves past it report "improved",
    inside the band is "ok". Metrics either side lacks are skipped with
    the reason. Backend mismatch skips EVERYTHING — cross-backend ratios
    (a TPU MFU vs a CPU MFU) are not noise, they are different
    quantities."""
    bb, cb = backend_of(base), backend_of(cand)
    bm, cm = ratio_metrics_of(base), ratio_metrics_of(cand)
    rows: List[dict] = []
    note = ""
    if bb != cb or "unknown" in (bb, cb):
        # an UNKNOWN backend (pre-backend-field artifacts) must not
        # bypass the guard: "can't prove same backend" compares nothing,
        # same as a proven mismatch — never a fake pass/fail
        if "unknown" in (bb, cb):
            who = " and ".join(s for s, b in (("base", bb),
                                              ("candidate", cb))
                               if b == "unknown")
            reason = "backend unknown"
            note = (f"backend unknown on {who}: cannot prove both "
                    f"records ran the same backend, nothing is "
                    f"comparable")
        else:
            reason = "backend mismatch"
            note = (f"backend mismatch ({bb} vs {cb}): ratio metrics "
                    f"are backend-relative, nothing is comparable")
        for name in sorted(set(bm) | set(cm)):
            rows.append({"metric": name, "status": "skipped",
                         "reason": reason})
        return BenchDiff(rows, bb, cb, note)
    for name in sorted(set(bm) | set(cm)):
        spec = RATIO_METRICS[name]
        if name not in bm or name not in cm:
            rows.append({"metric": name, "status": "skipped",
                         "reason": ("absent from baseline"
                                    if name not in bm
                                    else "absent from candidate")})
            continue
        b, c = bm[name], cm[name]
        if b == 0:
            # a second-artifact base (pinned baselines never carry
            # zeros) — no ratio can anchor on it
            rows.append({"metric": name, "status": "skipped",
                         "reason": "zero baseline value"})
            continue
        if band_override is not None:
            band = band_override
        elif spec.cpu_band is not None and bb == "cpu":
            band = spec.cpu_band
        else:
            band = spec.band
        ratio = c / b
        if spec.worse == "either":
            status = ("regressed" if abs(ratio - 1.0) > band else "ok")
        elif spec.worse == "lower":
            status = ("regressed" if ratio < 1.0 - band
                      else "improved" if ratio > 1.0 + band else "ok")
        else:  # worse == "higher"
            status = ("regressed" if ratio > 1.0 + band
                      else "improved" if ratio < 1.0 - band else "ok")
        rows.append({"metric": name, "base": b, "cand": c,
                     "ratio": round(ratio, 4), "band": band,
                     "worse": spec.worse, "status": status})
    return BenchDiff(rows, bb, cb, note)


def newest_round_artifact(repo_root: str) -> Optional[str]:
    """Highest-numbered ``BENCH_r*.json`` with a parsed payload (the
    default pin source). Ordered by the NUMERIC round — lexicographic
    sort would pin r99 over r100 (and r9 over r10) forever."""
    pat = re.compile(r"^BENCH_r(\d+)\.json$")
    cands = sorted((p for p in os.listdir(repo_root) if pat.match(p)),
                   key=lambda p: int(pat.match(p).group(1)))
    for p in reversed(cands):
        path = os.path.join(repo_root, p)
        try:
            if ratio_metrics_of(load_record(path)):
                return path
        except Exception:
            continue
    return None
