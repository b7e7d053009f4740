"""jit'd wrapper for the batched simulator-interval kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.sim_step.kernel import sim_step_pallas, sim_interval_pallas


def _pick_blk(E):
    """Env block: a lane-aligned divisor of E, else all of E — a TPU block's
    minor dim must be a multiple of 128 or span the whole array."""
    for cand in (256, 128):
        if E % cand == 0:
            return cand
    return E


@partial(jax.jit, static_argnames=("substeps", "duration", "interpret"))
def sim_step_batch(bufs, rate, cap, *, substeps=50, duration=1.0,
                   interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return sim_step_pallas(bufs, rate, cap, substeps=substeps,
                           duration=duration, blk=_pick_blk(bufs.shape[0]),
                           interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def sim_interval_batch(bufs, rates_dt, cap, *, interpret=None):
    """Schedule-aware interval: per-substep rates (E,S,3), pre-scaled by dt.
    The ``backend="pallas"`` path of repro.core.simulator.sim_interval routes
    here (per-env under vmap — the pallas batching rule folds the env batch
    into the grid)."""
    if interpret is None:
        interpret = default_interpret()
    return sim_interval_pallas(bufs, rates_dt, cap,
                               blk=_pick_blk(bufs.shape[0]),
                               interpret=interpret)
