"""jit'd wrapper for the fused contention-solve kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.contention.kernel import contention_rates_pallas


@partial(jax.jit, static_argnames=("rounds", "interpret"))
def contention_rates(threads, act, onpath, tpt, bw, floor=None, cap=None, *,
                     rounds=0, interpret=None):
    """(S, F, 3) per-flow contention rates, the whole per-substep solve
    fused in one kernel. The ``backend="pallas"`` paths of
    ``repro.core.fleet`` (E=1 embedding, rounds=0) and
    ``repro.core.topology`` (real routing matrix, rounds=F) route here.

    threads (F, 3); act (S, F) activity mask per substep; onpath (S, F, E)
    routing matrix per substep; tpt/bw (S, E, 3) per-link schedule window.
    ``floor``/``cap``: optional (F,) per-flow rate floor/cap (None = the
    objective-free solve, a structurally smaller kernel). ``rounds``:
    static water-fill spill rounds (0 = no redistribution — fleet
    semantics). ``interpret`` defaults to compiled on a TPU and the
    interpreter on the CPU (``repro.kernels.default_interpret``)."""
    if interpret is None:
        interpret = default_interpret()
    F = threads.shape[0]
    with_objectives = floor is not None or cap is not None
    floor = jnp.zeros((F,), jnp.float32) if floor is None else floor
    cap = jnp.full((F,), jnp.inf, jnp.float32) if cap is None else cap
    floor3 = jnp.broadcast_to(floor[:, None].astype(jnp.float32), (F, 3))
    cap3 = jnp.broadcast_to(cap[:, None].astype(jnp.float32), (F, 3))
    return contention_rates_pallas(threads, act, onpath, tpt, bw,
                                   floor3, cap3,
                                   with_objectives=with_objectives,
                                   rounds=rounds, interpret=interpret)
