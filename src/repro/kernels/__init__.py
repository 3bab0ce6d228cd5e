"""Pallas TPU kernels (one module each), their jnp oracles (``ref``) and
batched wrappers (``ops``)."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one rule for a kernel's ``interpret`` argument.

    ``None`` compiles the kernel on a TPU backend and runs the Pallas
    interpreter on the CPU backend (tests, CPU rehearsals); any other
    backend has no path and is an error.  ``True``/``False`` force a mode.
    """
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")
