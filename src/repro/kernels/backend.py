"""Where the Pallas kernels run: compiled on an accelerator, interpreted on
the CPU.

Every kernel wrapper takes `interpret=None` and resolves it here, so the
mode follows the backend JAX runs on and no entry point carries a default
that would silently interpret on a chip.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode for this process.

    None (the default everywhere) means "from the backend": interpret
    exactly when `jax.default_backend() == "cpu"`. An explicit False
    compiles with Mosaic on any backend (compiling for a described TPU from
    a CPU host needs it); an explicit True on an accelerator backend is
    refused, because an interpreted kernel there would silently stand in
    for the compiled one.
    """
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend != "cpu":
        raise ValueError(
            f"Pallas interpret mode was requested on the {backend!r} "
            f"backend; kernels run compiled on accelerators (pass "
            f"interpret=None)")
    return bool(interpret)
