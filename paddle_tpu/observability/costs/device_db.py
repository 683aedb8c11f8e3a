"""Device capability tables for the cost observatory: peak FLOP/s, HBM
bandwidth, inter-chip link bandwidth.

One definition per number: bf16 peak FLOP/s and the v5e HBM come from the
trainer's ``PEAK_FLOPS``/``PEAK_HBM`` tables (the MFU denominator every
throughput report already uses), the v5p HBM + ICI constants from
``parallel/projection.py`` (cited public specs, asserted by
tests/test_projection) — this module only ADDS the device kinds those
tables don't carry, each with its source in a comment. A device kind no
table holds is a LookupError: a prediction divided by a guessed peak is
worse than none. The ``cpu`` rows are NOMINAL — they keep the observatory
testable on hosts with no accelerator, where absolute predictions are
meaningless but the RATIOS the acceptance tests pin (K=1 vs K=4 step
time, comm ∝ bytes) survive any constant scaling.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

__all__ = ["DeviceSpec", "device_spec", "current_device_kind"]

# bytes/s; v5e + v5p imported from projection.py (cited), the rest from
# the same public per-generation spec sheets (cloud.google.com/tpu/docs)
_HBM_BW_EXTRA = {
    "tpu v4": 1228e9,        # v4: 32 GB @ 1228 GB/s
    "tpu v6 lite": 1640e9,   # v6e (trillium): 32 GB @ 1640 GB/s
    "cpu": 50e9,             # NOMINAL DRAM tier (CPU tests only)
}

# bytes/s per chip, aggregate over ICI links (approximate: link count x
# per-link rate from the launch specs; the planner only needs an
# order-of-magnitude prior until tools/op_cost_probe.py measures)
_LINK_BW_EXTRA = {
    "tpu v4": 300e9,         # 6 links x 50 GB/s
    "tpu v5 lite": 200e9,    # v5e: 1600 Gbit/s aggregate
    "tpu v5e": 200e9,
    "tpu v6 lite": 400e9,    # v6e: 3200 Gbit/s aggregate
    "cpu": 10e9,             # NOMINAL host-interconnect tier (CPU tests only)
}


@dataclass(frozen=True)
class DeviceSpec:
    kind: str
    peak_flops: float        # bf16 FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    link_bw: float           # bytes/s per chip over one mesh axis

    def as_dict(self) -> Dict[str, float]:
        return {"kind": self.kind, "peak_flops": self.peak_flops,
                "hbm_bw": self.hbm_bw, "link_bw": self.link_bw}


def _peak_table() -> Dict[str, float]:
    from ...trainer.trainer import PEAK_FLOPS
    return dict(PEAK_FLOPS)


def _hbm_table() -> Dict[str, float]:
    from ...parallel.projection import HBM_BW
    from ...trainer.trainer import PEAK_HBM
    out = dict(_HBM_BW_EXTRA)
    out.update({k: v["bytes_per_s"] for k, v in PEAK_HBM.items()})
    out["tpu v5e"] = out["tpu v5 lite"]
    out["tpu v5"] = out["tpu v5p"] = HBM_BW["v5p"]
    return out


def _link_table() -> Dict[str, float]:
    from ...parallel.projection import ICI_AGG
    out = dict(_LINK_BW_EXTRA)
    out["tpu v5"] = out["tpu v5p"] = ICI_AGG["v5p"]
    return out


def current_device_kind(default: str = "cpu") -> str:
    # ONE device-kind probe: delegate to the autotune helper the TuneDB
    # keys already use, so DB keys and spec lookups can never disagree
    try:
        from ...ops.pallas.autotune import _device_kind
        return _device_kind(default=default)
    except Exception:
        return default


def device_spec(kind: Optional[str] = None) -> DeviceSpec:
    """Spec for ``kind`` (defaults to the current jax device);
    LookupError when a table does not hold it."""
    from ...trainer.trainer import peak_lookup
    kind = kind or current_device_kind()
    return DeviceSpec(
        kind=kind,
        peak_flops=peak_lookup(_peak_table(), kind),
        hbm_bw=peak_lookup(_hbm_table(), kind),
        link_bw=peak_lookup(_link_table(), kind),
    )
