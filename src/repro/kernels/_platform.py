"""Where Pallas kernels run: compiled by Mosaic on a TPU, interpreted
everywhere else."""
from __future__ import annotations

import contextlib

import jax

_TARGET: str | None = None


def target_platform() -> str:
    """The platform programs are traced for: the default backend, or the
    one :func:`compiling_for` names."""
    return _TARGET or jax.default_backend()


@contextlib.contextmanager
def compiling_for(platform: str):
    """Trace for ``platform`` (e.g. a described TPU that this process
    compiles for but has not attached): kernels take that platform's
    choices."""
    global _TARGET
    prev, _TARGET = _TARGET, platform
    try:
        yield
    finally:
        _TARGET = prev


def resolve_interpret(interpret: bool | None = None) -> bool:
    """A kernel's ``interpret`` flag: an explicit value wins, ``None``
    means interpret unless the target platform is a TPU."""
    if interpret is None:
        return target_platform() != "tpu"
    return interpret
