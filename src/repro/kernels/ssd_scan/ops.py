"""jit'd wrapper for the SSD Pallas kernel, signature-compatible with
repro.nn.ssd.ssd_chunked (models pass ssd_fn=ssd_scan)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=128, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    y = ssd_scan_pallas(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    return y, None  # state handled by the oracle path (prefill)
