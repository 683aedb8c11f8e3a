"""Minimal kernel dispatch registry.

The reference dispatches every op through KernelFactory on
(backend, layout, dtype) — paddle/phi/core/kernel_factory.h:314. On TPU, XLA
owns device/dtype dispatch, so the registry keeps only the residual decision:
per-op choice between a hand-written Pallas kernel and the XLA composition
fallback, overridable via FLAGS_use_pallas_kernels (core/flags.py).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax

from ..core.flags import flag

_KERNELS: Dict[Tuple[str, str], Callable] = {}


def device_is_tpu(d) -> bool:
    """True if a jax Device is a TPU. The single source of truth for
    is-this-a-TPU; framework.is_compiled_with_tpu uses it too."""
    return getattr(d, "platform", "") == "tpu"


def require_tpu():
    """The first jax device, or RuntimeError when it is not a TPU — for
    entry points whose numbers mean nothing anywhere else (chip_smoke.py,
    the tools/ that measure). Never falls back."""
    d = jax.devices()[0]
    if not device_is_tpu(d):
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is {d.platform!r} "
            f"({getattr(d, 'device_kind', '?')}); this program runs on the "
            f"chip only")
    return d


@functools.lru_cache(maxsize=None)
def backend_kind() -> str:
    """'tpu' | 'gpu' | 'cpu': the default jax backend."""
    return jax.default_backend()


def pallas_disabled() -> bool:
    """Global Pallas kill-switch (PT_DISABLE_PALLAS): one predicate shared
    by every kernel-family support gate, so one switch turns all of them
    off."""
    import os
    return bool(os.environ.get("PT_DISABLE_PALLAS"))


class pallas_disabled_scope:
    """Context manager flipping the kill-switch for a region: ops trace as
    their jnp/lax composite bodies instead of fused kernels (used by
    paddle_tpu.decomposition.decompose to expose primitive jaxprs)."""

    def __enter__(self):
        import os
        self._prev = os.environ.get("PT_DISABLE_PALLAS")
        os.environ["PT_DISABLE_PALLAS"] = "1"
        return self

    def __exit__(self, *exc):
        import os
        if self._prev is None:
            os.environ.pop("PT_DISABLE_PALLAS", None)
        else:
            os.environ["PT_DISABLE_PALLAS"] = self._prev
        return False


def register_kernel(op: str, backend: str):
    """Register an implementation for op on backend ('tpu'|'cpu'|'any')."""
    def deco(fn):
        _KERNELS[(op, backend)] = fn
        return fn
    return deco


def dispatch(op: str) -> Callable:
    """Pick the best registered impl: pallas/tpu first when enabled."""
    if flag("use_pallas_kernels"):
        k = _KERNELS.get((op, backend_kind()))
        if k is not None:
            return k
    k = _KERNELS.get((op, "any"))
    if k is None:
        raise KeyError(f"No kernel registered for op {op!r}")
    return k
