"""Live cost gauges: the runtime half of the cost observatory.

A :class:`CostWatch` pairs ONE compiled executable's analytical cost
report (:func:`analyzer.attribute_costs`) with measured wall times and
publishes, through the PR 4 registry:

* ``pt_step_time_breakdown{component,bucket}`` — the measured per-step
  wall time split into compute / collective / exposed_comm / host /
  stall seconds. The buckets SUM TO the measured step time by
  construction (same discipline as the goodput ledger): compute and
  comm are the analytical predictions, scaled down proportionally if
  they exceed what the wall clock allows, and stall is the unattributed
  residual (input pipeline, dispatch gaps, overlap the serialized model
  didn't credit). The comm share is further split by the ISSUE 14
  overlap analyzer: ``collective`` is the part start→done windows hide
  behind compute, ``exposed_comm`` the priced census minus that
  overlap-window compute — the serialization actually on the clock.
* ``pt_exposed_comm_fraction{component}`` — exposed ÷ total priced comm
  seconds, published ONLY when the executable has async collective
  windows (a sync-lowered backend is trivially 100% exposed and would
  page a sentry on every CPU run for a structural non-event).
* ``pt_model_flops_utilization{component}`` — HLO-attributed flops ÷
  (measured time × device peak): the MFU definition shared with
  graph_lint's flop floor.
* ``pt_hbm_bw_utilization{component}`` — attributed HBM bytes ÷
  (measured time × HBM bandwidth).
* ``pt_step_time_predicted_over_measured{component}`` — the cost model
  watching itself: drift between prediction and reality is a monitored
  signal, not a silent assumption.

Attachment is lazy and failure-tolerant: executables that can't render
optimized HLO (the AOT-deserialized restart path) simply leave the gauges
unpublished — the hot path never pays for, or fails on, the observatory.
"""

from __future__ import annotations

from typing import Optional

from ..metrics import REGISTRY
from .analyzer import CostReport, attribute_costs
from .device_db import DeviceSpec, device_spec

__all__ = ["CostWatch"]


class CostWatch:
    """Analytical cost model of one executable + gauge publisher."""

    def __init__(self, component: str,
                 spec: Optional[DeviceSpec] = None):
        self.component = component
        self.spec = spec or device_spec()
        self.report: Optional[CostReport] = None
        # overlap verdict for the observed executable: fraction of its
        # priced comm seconds NOT covered by start->done window compute,
        # and how many async windows it has. Defaults (1.0, 0) = "all
        # exposed, no async machinery" — the conservative truth for a
        # report attached without HLO overlap analysis.
        self.overlap_fraction: float = 1.0
        self.overlap_async: int = 0
        self._exec_id: Optional[int] = None
        # per-executable report cache: a trainer alternating between two
        # bucketed batch shapes re-observes a different executable every
        # log boundary — the HLO must not re-parse each time
        self._reports: dict = {}

    # -- attachment ----------------------------------------------------------

    def observe_executable(self, compiled) -> bool:
        """Analyze ``compiled`` (anything with ``as_text()`` yielding
        optimized HLO). Re-observing the same object is a no-op; any
        failure leaves the watch unattached and returns False."""
        if compiled is None:
            return self.report is not None
        rid = id(compiled)
        if self._exec_id == rid and self.report is not None:
            return True
        cached = self._reports.get(rid)
        if cached is not None:
            (self.report, self.overlap_fraction,
             self.overlap_async) = cached
            self._exec_id = rid
            return True
        as_text = getattr(compiled, "as_text", None)
        if as_text is None:
            return False
        try:
            from ...analysis.hlo import parse_hlo
            mod = parse_hlo(as_text())
            self.report = attribute_costs(mod, spec=self.spec)
            # overlap split of the comm bucket (ISSUE 14); any analysis
            # failure (unpaired start, exotic lowering) falls back to
            # fully-exposed rather than silently crediting the overlap
            try:
                from ...analysis.overlap import overlap_report
                ov = overlap_report(mod, spec=self.spec)
                self.overlap_fraction = ov["exposed_comm_fraction"]
                self.overlap_async = ov["async_collectives"]
            except Exception:
                self.overlap_fraction, self.overlap_async = 1.0, 0
            self._exec_id = rid
            if len(self._reports) >= 8:     # bounded; ids are stable while
                self._reports.clear()       # the owner caches executables
            self._reports[rid] = (self.report, self.overlap_fraction,
                                  self.overlap_async)
            return True
        except Exception:
            return False

    @property
    def attached(self) -> bool:
        return self.report is not None

    # -- publication ---------------------------------------------------------

    def publish(self, measured_step_s: float, host_s: float = 0.0,
                steps_per_exec: int = 1) -> Optional[dict]:
        """Publish the gauges for one measured per-step time.

        ``steps_per_exec`` maps the analyzed executable onto step units
        (the K=4 superstep scan executes 4 optimizer steps per run), so a
        per-step measured time composes with a per-execution flop count.
        Returns the published dict (None when unattached/disabled)."""
        r = self.report
        if r is None or not REGISTRY.enabled or measured_step_s <= 0:
            return None
        k = max(1, int(steps_per_exec))
        exec_s = measured_step_s * k
        mfu = r.total_flops / (exec_s * self.spec.peak_flops)
        hbm = r.total_bytes / (exec_s * self.spec.hbm_bw)
        ratio = r.predicted_step_s / exec_s

        # breakdown (per step): analytical compute/comm, scaled to fit
        # inside the measured wall time net of host overhead; residual is
        # the stall bucket. Buckets sum EXACTLY to measured_step_s.
        host = min(max(host_s, 0.0), measured_step_s)
        compute = r.predicted_compute_s / k
        comm = r.predicted_comm_s / k
        avail = measured_step_s - host
        attributed = compute + comm
        scale = min(1.0, avail / attributed) if attributed > 0 else 0.0
        compute *= scale
        comm *= scale
        stall = max(0.0, measured_step_s - host - compute - comm)
        # split the scaled comm share by the overlap verdict — hidden
        # (start->done windows cover it with compute) vs exposed. The
        # split preserves the exact-sum invariant: hidden + exposed is
        # the comm share by construction.
        exposed = comm * min(max(self.overlap_fraction, 0.0), 1.0)
        hidden = comm - exposed

        lbl = {"component": self.component}
        g = REGISTRY.gauge(
            "pt_step_time_breakdown",
            "measured per-step wall time split into compute/collective/"
            "exposed_comm/host/stall (buckets sum to the measured step "
            "time; collective = comm hidden behind overlap-window "
            "compute, exposed_comm = the rest)", "s")
        g.set(compute, bucket="compute", **lbl)
        g.set(hidden, bucket="collective", **lbl)
        g.set(exposed, bucket="exposed_comm", **lbl)
        g.set(host, bucket="host", **lbl)
        g.set(stall, bucket="stall", **lbl)
        if self.overlap_async > 0:
            # sync-lowered backends (CPU CI) are structurally 100%
            # exposed; publishing that would page the sentry's ratio
            # band on a non-event, so the fraction gauge exists only
            # where overlap machinery is actually in play
            REGISTRY.gauge(
                "pt_exposed_comm_fraction",
                "exposed / total priced comm seconds of the executable "
                "on the clock (only published when it has async "
                "collective windows)").set(self.overlap_fraction, **lbl)
        REGISTRY.gauge(
            "pt_model_flops_utilization",
            "HLO-attributed flops / (measured time x device peak) — the "
            "one analytical MFU definition (shared with bench "
            "mfu_analytical and graph_lint's flop floor)").set(mfu, **lbl)
        REGISTRY.gauge(
            "pt_hbm_bw_utilization",
            "HLO-attributed HBM bytes / (measured time x HBM "
            "bandwidth)").set(hbm, **lbl)
        REGISTRY.gauge(
            "pt_step_time_predicted_over_measured",
            "roofline-predicted / measured step time — cost-model drift "
            "as a monitored signal").set(ratio, **lbl)
        return {"mfu": mfu, "hbm_bw_utilization": hbm,
                "predicted_over_measured": ratio,
                "exposed_comm_fraction": self.overlap_fraction,
                "breakdown": {"compute": compute, "collective": hidden,
                              "exposed_comm": exposed,
                              "host": host, "stall": stall}}
