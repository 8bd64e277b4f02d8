"""Where the Pallas kernels run: the one platform decision of the repo.

The kernels compile for TPU only.  Model and app code ask
:func:`compiled_kernels` whether to take a kernel or its jnp reference
path; the kernel wrappers resolve their ``interpret`` argument through
:func:`resolve_interpret`, so a backend without a compiled path raises
instead of silently running the Pallas interpreter.  Tests that check a
kernel on the CPU pass ``interpret`` explicitly.
"""

from __future__ import annotations

from typing import Optional

import jax


def compiled_kernels() -> bool:
    """True where the Pallas kernels compile (a TPU backend)."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; otherwise compiled on TPU, and an
    error on any backend that has no compiled path."""
    if interpret is not None:
        return interpret
    if compiled_kernels():
        return False
    raise RuntimeError(
        f"the Pallas kernels compile only for TPU, and the backend here is "
        f"{jax.default_backend()!r}; use the jnp reference path, or pass "
        "interpret explicitly to run a kernel in the Pallas interpreter"
    )
