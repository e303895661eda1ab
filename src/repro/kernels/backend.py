"""Kernel backend selection.

'pallas'    — compiled Pallas TPU kernels (real hardware target)
'interpret' — Pallas kernels in interpret mode (CPU correctness runs)
'jnp'       — pure-jnp reference path, identical math & packed storage
              (used for full-model CPU smoke tests and the dry-run lowering;
              roofline byte counts still reflect packed weights)

Default: 'jnp' on CPU hosts, 'pallas' when a TPU is present.  The
``REPRO_BACKEND`` env var overrides the default (validated against the
same set), so CI legs and launchers can pick the backend without code
edits; an explicit ``set_backend``/``use_backend`` still wins over both.
"""

from __future__ import annotations

import contextlib
import os

import jax

_BACKEND: str | None = None
_VALID = ("pallas", "interpret", "jnp")
_ENV_VAR = "REPRO_BACKEND"


def default_backend() -> str:
    env = os.environ.get(_ENV_VAR)
    if env:
        if env not in _VALID:
            raise ValueError(
                f"{_ENV_VAR}={env!r} is not a valid backend; "
                f"expected one of {_VALID}")
        return env
    # a backend that fails to initialise raises here: silently serving the
    # jnp reference would hide the device from every measurement
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def get_backend() -> str:
    global _BACKEND
    if _BACKEND is None:
        _BACKEND = default_backend()
    return _BACKEND


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {name!r}")
    _BACKEND = name


@contextlib.contextmanager
def use_backend(name: str):
    prev = get_backend()
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)
