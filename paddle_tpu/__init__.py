"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new framework with the capabilities of PaddlePaddle (reference mounted
at /root/reference — see SURVEY.md), built on JAX/XLA/Pallas/pjit idioms:
functional core, GSPMD parallelism, Pallas hot kernels. The top-level
namespace mirrors ``paddle.*``: tensor functions live here, layers under
``nn``, optimizers under ``optimizer``, parallelism under ``distributed``.
"""

from .core import dtype as _dtype_ns
from .core.dtype import (bool_, uint8, int8, int16, int32, int64, float16,
                         bfloat16, float32, float64, complex64, complex128,
                         dtype, finfo, iinfo)
from .core.dtype import bool_ as bool  # noqa: A001 — paddle exports `bool`
from .core.flags import set_flags, get_flags
from .core.rng import seed

from . import amp
from . import autograd
from . import distributed
from . import io
from . import nn
from . import optimizer
from . import ops
from . import tensor
from .linalg import eigvalsh, eigvals, eig  # top-level parity

# paddle-style: every tensor function is also a top-level symbol
from .tensor import *  # noqa: F401,F403

# paddle-style Tensor METHODS on the runtime array type (x.numpy(),
# x.cast(...), x.unsqueeze(...), clear backward() migration error, ...)
from .tensor import methods as _tensor_methods
_tensor_methods.install()
from .tensor import Tensor

from .nn.layer import set_default_dtype, get_default_dtype

from .framework import save, load, set_device, get_device, is_compiled_with_cuda, \
    is_compiled_with_tpu, device_count, no_grad
from .device import (is_compiled_with_rocm, is_compiled_with_xpu,  # noqa: E402
                     is_compiled_with_ipu, is_compiled_with_custom_device)
from .base import (CPUPlace, CUDAPlace, CUDAPinnedPlace, TPUPlace, XPUPlace,
                   IPUPlace, ParamAttr, LazyGuard, DataParallel,
                   in_dynamic_mode, in_dynamic_or_pir_mode, enable_static,
                   disable_static, enable_grad, set_grad_enabled,
                   is_grad_enabled, disable_signal_handler, set_printoptions,
                   get_rng_state, set_rng_state, get_cuda_rng_state,
                   set_cuda_rng_state, create_parameter, create_global_var,
                   check_shape)
from .autograd import grad
from .hapi.summary import flops
from . import jit
from . import static
from . import metric
from . import device
from . import fft
from . import sparse
from . import distribution
from . import vision
from . import quantization
from . import incubate
from . import decomposition
from . import dataset
from . import version
from . import inference
from . import serving_fabric
from . import linalg
from . import resilience
from . import text
from . import audio
from . import geometric
from . import utils
from . import profiler
from . import onnx
from . import reader
from . import regularizer
from . import signal
from . import sysconfig
from . import callbacks
from . import hub
from .reader import batch
from . import hapi
from .hapi import Model
from .hapi.summary import summary

__version__ = version.full_version
