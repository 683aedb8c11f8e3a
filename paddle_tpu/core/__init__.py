"""paddle_tpu.core — flags, dtypes, RNG, compile cache."""

from . import compile_cache, dtype, flags, rng
from .compile_cache import configure_compilation_cache
from .flags import set_flags, get_flags, define_flag
from .rng import seed, rng_tracker
