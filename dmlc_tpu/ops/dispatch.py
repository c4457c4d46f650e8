"""Kernel-or-reference dispatch: the one place that decides.

Every Pallas kernel in the repo (flash attention forward/backward, the
ring step, paged decode attention over K/V or latent rows, the decode
state steps ``kda_state_step`` and ``ssm_state_step``) has a pure-lax
twin that is its parity reference.  Which of the two a traced program gets is decided
HERE and nowhere else: compiled by Mosaic on a TPU backend, the lax
reference on every other backend, and — only when a test says so —
the kernel under the Pallas interpreter.  The lax paths exist so the
CPU test suite has something to compare against; a chip run that got
one is a bug, so every decision is counted (stage ``kernels``:
``mosaic_traces`` / ``interpret_traces`` / ``lax_traces``, once per
trace, not per call) and ``chip_smoke.py`` fails on any ``lax`` or
``interpret`` trace.
"""

from __future__ import annotations

import contextlib
from typing import Optional

MOSAIC = "mosaic"        # the kernel, compiled for the chip
INTERPRET = "interpret"  # the kernel, under the Pallas interpreter
LAX = "lax"              # the pure-lax reference

_forced: Optional[str] = None


def kernel_mode() -> str:
    """How a supported kernel runs in this process right now."""
    if _forced is not None:
        return _forced
    import jax

    return MOSAIC if jax.default_backend() == "tpu" else LAX


@contextlib.contextmanager
def force_kernel_mode(mode: str):
    """Test hook: make every dispatch inside the block take ``mode``.

    The choice is read while JAX traces, so build (or re-jit) the
    program inside the block; an already-traced program keeps whatever
    it was traced with."""
    global _forced
    if mode not in (MOSAIC, INTERPRET, LAX):
        raise ValueError(f"unknown kernel mode {mode!r}")
    prev, _forced = _forced, mode
    try:
        yield
    finally:
        _forced = prev


def choose(supported: bool, impl: str = "auto") -> str:
    """The mode for one kernel call site, counted.

    ``supported`` is the kernel's own shape gate; ``impl`` is the
    site's explicit override: ``"auto"`` follows :func:`kernel_mode`,
    ``"lax"`` takes the reference, anything else demands the kernel
    (interpreted when the process has no chip to compile it for)."""
    from .. import telemetry

    if impl == "lax" or (impl == "auto" and not supported):
        mode = LAX
    else:
        mode = kernel_mode()
        if mode == LAX and impl != "auto":
            mode = INTERPRET
    if mode == MOSAIC:
        telemetry.inc("kernels", "mosaic_traces")
    elif mode == INTERPRET:
        telemetry.inc("kernels", "interpret_traces")
    else:
        telemetry.inc("kernels", "lax_traces")
    return mode
