"""Interpret-or-compile choice shared by every Pallas kernel module.

A kernel runs in interpret mode exactly when the default backend is not a
TPU: CPU test runs interpret, a chip run compiles through Mosaic. Kernel
modules default their public ``interpret`` argument to ``None`` and resolve
it here while tracing, so the choice follows the process's platform and
never a flag read at import. An explicit ``interpret=`` still wins, which
lets ahead-of-time compile tests steer a kernel onto Mosaic from a CPU
process.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` if given, else True unless the default backend is TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
