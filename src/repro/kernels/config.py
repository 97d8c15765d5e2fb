"""Pallas execution mode.

Every kernel wrapper takes ``interpret=None`` and resolves it here, outside
its jit: ``interpret`` is a static argument, so the resolved boolean (not
``None``) is what keys the jit cache.  ``None`` follows the backend: Mosaic
compiles the kernel on a TPU, and the Pallas interpreter runs it on the CPU
(``JAX_PLATFORMS=cpu``, the tests).  An explicit ``interpret=`` wins.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """True unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)
