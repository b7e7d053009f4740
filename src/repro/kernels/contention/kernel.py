"""Fused contention solve across the substep grid.

At fleet scale the contention solve — not the buffer integration — is the
episode hot spot: every substep builds (F, E, 3) share/demand/floor tensors
and reduces them over the flow axis several times. This kernel fuses the
whole per-substep solve (caps, proportionally scaled floors, the
thread-proportional residual split, the F-round water-fill redistribution,
and the min-over-path-links combine) into one VMEM-resident program per
substep: one HBM read of the window inputs and one (F, 3) write back, no
intermediate (S, F, E, 3) tensors ever materialized in HBM.

The grid iterates the S substeps; flows and links live entirely in VMEM.
Inside the kernel every tensor is stage-major, (3, E, F): flows ride the
128 lanes, links the 8 sublanes, and the stage is an untiled leading axis.
Every reduction keeps its axis, so no value ever moves between lanes and
sublanes — the TPU compiler refuses such shape casts. The wrapper re-lays
the operands into that form and the (3, 1, F) result back to (F, 3).
The schedule gathers (table bins -> per-substep tpt/bw, activity windows ->
act, route bins -> onpath) happen OUTSIDE the kernel: they are cheap
order-preserving gathers and keeping them out makes the kernel a pure
function of dense per-substep operands — exactly what the jnp reference in
``ref.py`` computes, which is what the parity tests pin.

``rounds`` is static: 0 is the single-bottleneck fleet model (no
redistribution — capacity a capped flow cannot use is stranded, matching
``_fleet_substep_rates``), > 0 runs that many water-fill spill rounds
(topology semantics: F rounds reach the fixed point).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _contention_kernel(threads_ref, act_ref, onpath_ref, tpt_ref, bw_ref,
                       floor_ref, cap_ref, out_ref, *, with_objectives,
                       rounds):
    threads = threads_ref[...]                         # (3, 1, F)
    act = act_ref[0]                                   # (1, 1, F)
    onpath = onpath_ref[0]                             # (1, E, F)
    tpt = tpt_ref[0]                                   # (3, E, 1)
    bw = bw_ref[0]                                     # (3, E, 1)
    # effective threads of flow f ON link e (0 off-path / inactive)
    eff = threads * act * onpath                       # (3, E, F)
    total = jnp.maximum(eff.sum(axis=-1, keepdims=True), 1e-9)  # (3, E, 1)
    share = eff / total
    if not with_objectives:
        link_rate = jnp.minimum(eff * tpt, share * bw)
    else:
        floor = floor_ref[...]                         # (3, 1, F)
        cap = cap_ref[...]                             # (3, 1, F)
        demand = jnp.minimum(eff * tpt, cap)           # (3, E, F)
        guaranteed = jnp.minimum(floor, demand)
        g_tot = guaranteed.sum(axis=-1, keepdims=True)  # (3, E, 1)
        guaranteed = guaranteed * jnp.minimum(
            1.0, bw / jnp.maximum(g_tot, 1e-9))
        residual = jnp.maximum(
            bw - guaranteed.sum(axis=-1, keepdims=True), 0.0)
        alloc = share * residual
        headroom = cap - guaranteed                    # inf when uncapped
        if rounds:
            def body(_, alloc):
                spill = jnp.maximum(alloc - headroom, 0.0).sum(
                    axis=-1, keepdims=True)
                alloc = jnp.minimum(alloc, headroom)
                w = eff * (alloc < headroom)
                w_tot = jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-9)
                return alloc + (w / w_tot) * spill

            alloc = jax.lax.fori_loop(0, rounds, body, alloc)
            alloc = jnp.minimum(alloc, headroom)
        link_rate = jnp.minimum(demand, guaranteed + alloc)
    # end-to-end rate: min over the flow's links (off-path never
    # constrains), empty paths and inactive flows move exactly nothing
    constraining = jnp.where(onpath > 0, link_rate, jnp.inf)
    rate = jnp.min(constraining, axis=1, keepdims=True)  # (3, 1, F)
    has_path = onpath.sum(axis=1, keepdims=True) > 0     # (1, 1, F)
    out_ref[0] = jnp.where(has_path, rate, 0.0) * act


def contention_rates_pallas(threads, act, onpath, tpt, bw, floor, cap, *,
                            with_objectives, rounds=0, interpret=True):
    """threads (F, 3); act (S, F); onpath (S, F, E); tpt/bw (S, E, 3);
    floor/cap (F, 3). Returns (S, F, 3) per-flow per-stage rates."""
    S, F = act.shape
    E = onpath.shape[-1]
    kernel = functools.partial(_contention_kernel,
                               with_objectives=with_objectives,
                               rounds=rounds)

    def per_flow(x):                                   # (F, 3) -> (3, 1, F)
        return x.astype(jnp.float32).T[:, None, :]

    def per_link(x):                                   # (S, E, 3) -> (S, 3, E, 1)
        return jnp.swapaxes(x.astype(jnp.float32), 1, 2)[..., None]

    out = pl.pallas_call(
        kernel,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((3, 1, F), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, 1, 1, F), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, 1, E, F), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, 3, E, 1), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, 3, E, 1), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 1, F), lambda i: (0, 0, 0)),
            pl.BlockSpec((3, 1, F), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 3, 1, F), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, 3, 1, F), jnp.float32),
        interpret=interpret,
        name="contention_solve",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(per_flow(threads), act.astype(jnp.float32)[:, None, None, :],
      jnp.swapaxes(onpath.astype(jnp.float32), 1, 2)[:, None],
      per_link(tpt), per_link(bw), per_flow(floor), per_flow(cap))
    return jnp.transpose(out[:, :, 0, :], (0, 2, 1))   # (S, F, 3)
