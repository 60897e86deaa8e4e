"""Where Pallas kernels run: the one interpret-mode decision.

Kernel bodies run in the Pallas interpreter exactly when JAX's default
backend is the CPU (which has no Mosaic lowering); on a TPU they lower
to Mosaic.  Every ``interpret=None`` default in the kernel modules and
in ``ops`` resolves here, so no switch can leave a chip run silently in
the interpreter.  This lives in its own tiny module so the raw kernel
modules can resolve their defaults without importing ``ops`` — which
imports them.
"""
from __future__ import annotations

import jax


def interpret_default() -> bool:
    """True exactly when the default backend is the CPU."""
    return jax.default_backend() == "cpu"


def resolve(interpret) -> bool:
    """An explicit ``interpret`` flag, or the backend's default."""
    return interpret_default() if interpret is None else bool(interpret)
