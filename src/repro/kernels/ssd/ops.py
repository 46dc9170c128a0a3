"""jit wrapper for the SSD intra-chunk kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from ..backend import interpret_mode
from .kernel import ssd_chunk_pallas
from .ref import ssd_chunk_ref


def ssd_chunk(x_dt, B, C, seg, *, interpret: Optional[bool] = None,
              use_pallas: bool = True):
    """Intra-chunk SSD: returns (Y_diag, chunk_states).

    Shapes: x_dt (bh, nc, Q, P); B, C (bh, nc, Q, N); seg (bh, nc, Q).
    The inter-chunk recurrence (associative scan over nc) remains the
    caller's job (models/ssm.py) — it is latency-bound, not MXU work.
    """
    return _ssd_jit(x_dt, B, C, seg, interpret=interpret_mode(interpret),
                    use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("interpret", "use_pallas"))
def _ssd_jit(x_dt, B, C, seg, *, interpret: bool, use_pallas: bool):
    if not use_pallas:
        return ssd_chunk_ref(x_dt, B, C, seg)
    return tuple(ssd_chunk_pallas(x_dt, B, C, seg, interpret=interpret))
