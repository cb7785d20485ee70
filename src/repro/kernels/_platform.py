"""Where Pallas kernels run: compiled by Mosaic on a TPU, interpreted
everywhere else."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """A kernel's ``interpret`` flag: an explicit value wins, ``None``
    means interpret unless the default backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
