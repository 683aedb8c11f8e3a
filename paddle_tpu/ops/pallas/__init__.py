"""Pallas TPU kernels (flash attention, fused norms). Importing registers
the TPU-backend kernels with the op registry."""

from . import flash_attention  # noqa: F401
from . import fused_norm  # noqa: F401
from . import fused_vocab_ce  # noqa: F401
from . import gated_delta  # noqa: F401
from . import latent_attention  # noqa: F401
from . import paged_attention  # noqa: F401
from . import power_retention  # noqa: F401
from . import selective_ssm  # noqa: F401
from . import ssm  # noqa: F401

# every ``pl.pallas_call`` here passes one of these as ``name=``: jax puts
# it on the call's name stack, and the TPU compiler names the custom call
# after it (``%flash_attention_fwd.3``, ``%jvp_flash_attention_fwd_.1``,
# ``%transpose_jvp_fused_vocab_ce_bwd_dw__.2``), which is the text of the
# kernel's event on the device trace's ``XLA Ops`` line. The benchmark's
# ``*_share`` metrics find kernels by these names (PERF.md section 3).
KERNEL_NAMES = (
    "flash_attention_fwd", "flash_attention_bwd", "flash_attention_bwd_dq",
    "flash_attention_bwd_dkv", "fused_vocab_ce_fwd", "fused_vocab_ce_bwd_dh",
    "fused_vocab_ce_bwd_dw", "paged_attention_decode", "fused_rmsnorm_fwd",
    "fused_rmsnorm_bwd", "fused_rope", "int8_matmul",
    "latent_attention_decode", "ssm_state_update",
    "selective_state_update", "selective_scan", "conv_window_step",
    "power_state_update", "power_retention_chunked",
    "gated_delta_state_update",
)
