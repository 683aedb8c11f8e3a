"""paddle_tpu.observability.sentry — declarative SLOs over the metrics
plane and correlated incident capture.

The closing third of the observability loop (ISSUE 10): PR 4's registry
records, PR 9's cost observatory attributes, this package *watches*.

Quickstart::

    from paddle_tpu.observability import sentry as sn

    rules = sn.trainer_rules() + sn.serving_rules(itl_p99_ceiling_s=0.2)
    sn.install(sn.SloSentry(rules, incident_log="incidents.jsonl",
                            flight_dump=True, min_interval_s=1.0))
    trainer.fit(...)          # ticks at log boundaries
    engine.run()              # ticks at drain boundaries
    for inc in sn.active().incidents:
        print(inc.rule, inc.severity, inc.context["goodput"])
"""

from __future__ import annotations

from .rules import (EwmaSpike, RatioBand, SloRule, Staleness, Threshold,
                    default_rules, elastic_rules, fabric_rules,
                    frontdoor_rules, moe_rules, serving_rules,
                    trainer_rules)
from .sentry import (Incident, SloSentry, active, install, maybe_tick,
                     uninstall)

__all__ = [
    "SloRule", "Threshold", "EwmaSpike", "RatioBand", "Staleness",
    "trainer_rules", "serving_rules", "fabric_rules", "frontdoor_rules",
    "elastic_rules", "moe_rules", "default_rules",
    "Incident", "SloSentry", "install", "uninstall", "active",
    "maybe_tick",
]
