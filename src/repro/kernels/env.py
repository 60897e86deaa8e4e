"""Where Pallas kernels run: the one interpret-mode decision, and the
VMEM a kernel may hold there.

Kernel bodies run in the Pallas interpreter exactly when JAX's default
backend is the CPU (which has no Mosaic lowering); on a TPU they lower
to Mosaic.  Every ``interpret=None`` default in the kernel modules and
in ``ops`` resolves here, so no switch can leave a chip run silently in
the interpreter.  This lives in its own tiny module so the raw kernel
modules can resolve their defaults without importing ``ops`` — which
imports them.
"""
from __future__ import annotations

import math

import jax
from jax.experimental.pallas import tpu as pltpu

# Of the 128 MiB of VMEM of a v5e TensorCore, what one MA-Echo kernel may
# hold.  Mosaic's own scoped limit, which a kernel gets unless it asks
# for more, is 16 MiB.
VMEM_BUDGET = 64 * 2 ** 20
VMEM_SCOPED = 16 * 2 ** 20


def interpret_default() -> bool:
    """True exactly when the default backend is the CPU."""
    return jax.default_backend() == "cpu"


def resolve(interpret) -> bool:
    """An explicit ``interpret`` flag, or the backend's default."""
    return interpret_default() if interpret is None else bool(interpret)


def _vmem_size(shape) -> int:
    """Bytes of an f32 buffer of ``shape`` in VMEM, whose last two axes
    are laid out in (8, 128) tiles."""
    *major, rows, cols = (1,) * (2 - len(shape)) + tuple(shape)
    return 4 * math.prod(major) * (-(-rows // 8) * 8) * (-(-cols // 128)
                                                         * 128)


def vmem_bytes(blocks, scratch=(), values=()) -> int:
    """VMEM a kernel holds: each pipelined f32 block in ``blocks``
    (inputs and outputs) twice, for the double buffer, and each
    ``scratch`` buffer and each block-sized value the body forms
    (``values``) once.  Mosaic's own count, found by compiling for a
    described v5e under a falling limit, came out 1-32 % below this at
    the MA-Echo kernels' shapes."""
    return (2 * sum(map(_vmem_size, blocks))
            + sum(map(_vmem_size, scratch)) + sum(map(_vmem_size, values)))


def compiler_params(nbytes: int):
    """Mosaic parameters for a kernel that holds ``nbytes`` of VMEM:
    past the default scoped limit, ``nbytes`` rounded up to a MiB as
    its own (None, the default, otherwise).  ``core.plan`` keeps
    ``nbytes`` within :data:`VMEM_BUDGET`; a kernel claims no more than
    it holds, since XLA keeps the VMEM no kernel claims for its own
    buffers (claiming the whole budget moved 35 MiB of them into HBM
    in a compile of the mesh cell's aggregate)."""
    if nbytes <= VMEM_SCOPED:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=-(-nbytes // 2 ** 20)
                                * 2 ** 20)
