"""paddle_tpu.trainer — training loop + MFU accounting (reference analogue:
hapi Model.fit, python/paddle/hapi/model.py:1054)."""

from .trainer import (Trainer, TrainMetrics, device_peak_flops, PEAK_FLOPS,
                      PEAK_HBM, peak_lookup)
