"""Where Pallas kernels run: compiled on a TPU, interpreted elsewhere."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The ``interpret`` flag a kernel wrapper passes to ``pallas_call``.

    ``None`` (what every serving call site passes) means: compile the
    kernel when JAX's default backend is a TPU, run the Pallas
    interpreter on any other backend.  An explicit bool is returned
    unchanged, so a test can still force either mode."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
