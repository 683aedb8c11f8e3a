"""Pallas TPU kernels (flash attention, fused norms). Importing registers
the TPU-backend kernels with the op registry."""

from . import flash_attention  # noqa: F401
from . import fused_norm  # noqa: F401
from . import fused_vocab_ce  # noqa: F401
from . import grouped_matmul  # noqa: F401
from . import paged_attention  # noqa: F401
