"""Where the Pallas kernels run: compiled for the TPU, interpreted elsewhere.

Every public kernel wrapper (and every autotuner) takes `interpret=None` by
default and resolves it here, so a run on the chip always executes the
compiled kernels and a CPU run always executes the Pallas interpreter.  An
explicit `interpret=True` / `False` from the caller still wins.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """`interpret` when given, else True exactly when the default backend is
    not a TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
