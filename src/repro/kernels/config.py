"""Kernel dispatch switch.

Inside ``with use_pallas(True):`` the model hot-spots (attention, WKV6,
RG-LRU scan) trace through the Pallas TPU kernels; outside it they trace
the pure-XLA path (the one the dry-run lowers).  The switch is read while
a function is *traced*, so it has to be held around the first call of a
jitted step, not only around ``jax.jit``.  Each entry point holds its own
setting for its whole run (``launch/serve.py``, ``launch/train.py``), so
nothing one run chose leaks into the next run in the same process.

On CPU backends the kernels run in interpret mode (tests).
"""
import contextlib
import contextvars
import logging

import jax

from repro.obs import metrics

_USE_PALLAS = contextvars.ContextVar("repro_use_pallas", default=False)

log = logging.getLogger(__name__)


@contextlib.contextmanager
def use_pallas(on: bool):
    """Trace the model hot-spots through the Pallas kernels inside the block."""
    token = _USE_PALLAS.set(on)
    try:
        yield
    finally:
        _USE_PALLAS.reset(token)


def pallas_enabled() -> bool:
    return _USE_PALLAS.get()


def interpret_mode() -> bool:
    return jax.default_backend() == "cpu"


def fallback(kernel: str, reason: str) -> None:
    """An enabled kernel cannot take this shape: say so, and count it as
    ``kernels.fallback.<kernel>``, before the caller traces the reference."""
    metrics.inc(f"kernels.fallback.{kernel}")
    log.warning("Pallas %s kernel cannot take %s; tracing the reference path",
                kernel, reason)
