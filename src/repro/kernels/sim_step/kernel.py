"""AutoMDT dense-simulator interval across a batch of environments.

The paper's own compute hot spot is the simulator inner loop (it is what
makes offline PPO training fast). The vectorized trainer steps thousands of
envs in parallel; this kernel runs the whole ``substeps`` sub-interval loop
for a tile of environments entirely in VMEM — one HBM read of the env state
and one write back per simulated second, instead of ``substeps`` round trips.

Env tiles of 128 lanes x 8 sublanes map directly onto the VPU; everything is
elementwise f32, so the loop is bound by VMEM latency — i.e. effectively free
next to the PPO network's MXU work.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sim_kernel(bufs_ref, rate_ref, cap_ref, out_bufs_ref, moved_ref, *,
                substeps, dt):
    s = bufs_ref[:, 0]
    r = bufs_ref[:, 1]
    rate_r = rate_ref[:, 0] * dt
    rate_n = rate_ref[:, 1] * dt
    rate_w = rate_ref[:, 2] * dt
    cap_s = cap_ref[:, 0]
    cap_r = cap_ref[:, 1]

    def body(i, carry):
        s, r, mr, mn, mw = carry
        read = jnp.maximum(jnp.minimum(rate_r, cap_s - s), 0.0)
        s_mid = s + read
        net = jnp.maximum(jnp.minimum(jnp.minimum(rate_n, s_mid), cap_r - r),
                          0.0)
        r_mid = r + net
        wr = jnp.maximum(jnp.minimum(rate_w, r_mid), 0.0)
        return (s_mid - net, r_mid - wr, mr + read, mn + net, mw + wr)

    zero = jnp.zeros_like(s)
    s, r, mr, mn, mw = jax.lax.fori_loop(0, substeps, body,
                                         (s, r, zero, zero, zero))
    out_bufs_ref[:, 0] = s
    out_bufs_ref[:, 1] = r
    moved_ref[:, 0] = mr
    moved_ref[:, 1] = mn
    moved_ref[:, 2] = mw


def _sim_sched_kernel(bufs_ref, rates_ref, cap_ref, out_bufs_ref, moved_ref,
                      *, substeps):
    """Schedule-aware variant: per-substep rates (already scaled by dt) are
    resident in VMEM as an (S, 3, blk) block and read one substep per loop
    iteration — this is what lets the unified schedule-native simulator
    route its inner substep scan through Pallas unchanged. Environments
    ride the 128 lanes and the stages the sublanes, so a block costs
    S * 8 * blk words of VMEM; envs on the sublanes would pad the 3 stages
    out to 128 lanes, 16x that."""
    s = bufs_ref[0:1, :]                               # (1, blk)
    r = bufs_ref[1:2, :]
    cap_s = cap_ref[0:1, :]
    cap_r = cap_ref[1:2, :]

    def body(i, carry):
        s, r, mr, mn, mw = carry
        rate = rates_ref[i]                            # (3, blk)
        read = jnp.maximum(jnp.minimum(rate[0:1], cap_s - s), 0.0)
        s_mid = s + read
        net = jnp.maximum(jnp.minimum(jnp.minimum(rate[1:2], s_mid),
                                      cap_r - r), 0.0)
        r_mid = r + net
        wr = jnp.maximum(jnp.minimum(rate[2:3], r_mid), 0.0)
        return (s_mid - net, r_mid - wr, mr + read, mn + net, mw + wr)

    zero = jnp.zeros_like(s)
    s, r, mr, mn, mw = jax.lax.fori_loop(0, substeps, body,
                                         (s, r, zero, zero, zero))
    out_bufs_ref[0:1, :] = s
    out_bufs_ref[1:2, :] = r
    moved_ref[0:1, :] = mr
    moved_ref[1:2, :] = mn
    moved_ref[2:3, :] = mw


def sim_interval_pallas(bufs, rates_dt, cap, *, blk=256, interpret=True):
    """bufs: (E,2); rates_dt: (E,S,3) aggregate per-stage rates PER SUBSTEP,
    pre-multiplied by dt (already min(n*TPT, B) under the schedule); cap:
    (E,2). Returns (new_bufs (E,2), moved (E,3)). The kernel sees every
    operand env-minor: (2, E), (S, 3, E)."""
    E, S = rates_dt.shape[0], rates_dt.shape[1]
    blk = min(blk, E)
    assert E % blk == 0, (E, blk)
    kernel = functools.partial(_sim_sched_kernel, substeps=S)
    new_bufs, moved = pl.pallas_call(
        kernel,
        grid=(E // blk,),
        in_specs=[
            pl.BlockSpec((2, blk), lambda i: (0, i)),
            pl.BlockSpec((S, 3, blk), lambda i: (0, 0, i)),
            pl.BlockSpec((2, blk), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((2, blk), lambda i: (0, i)),
            pl.BlockSpec((3, blk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((2, E), jnp.float32),
            jax.ShapeDtypeStruct((3, E), jnp.float32),
        ],
        interpret=interpret,
        name="sim_step_sched",
    )(bufs.astype(jnp.float32).T,
      jnp.transpose(rates_dt.astype(jnp.float32), (1, 2, 0)),
      cap.astype(jnp.float32).T)
    return new_bufs.T, moved.T


def sim_step_pallas(bufs, rate, cap, *, substeps=50, duration=1.0,
                    blk=256, interpret=True):
    """bufs: (E,2); rate: (E,3) aggregate per-stage rates (already
    min(n*TPT, B)); cap: (E,2). Returns (new_bufs (E,2), moved (E,3))."""
    E = bufs.shape[0]
    blk = min(blk, E)
    assert E % blk == 0, (E, blk)
    dt = duration / substeps
    kernel = functools.partial(_sim_kernel, substeps=substeps, dt=dt)
    return pl.pallas_call(
        kernel,
        grid=(E // blk,),
        in_specs=[
            pl.BlockSpec((blk, 2), lambda i: (i, 0)),
            pl.BlockSpec((blk, 3), lambda i: (i, 0)),
            pl.BlockSpec((blk, 2), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk, 2), lambda i: (i, 0)),
            pl.BlockSpec((blk, 3), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((E, 2), jnp.float32),
            jax.ShapeDtypeStruct((E, 3), jnp.float32),
        ],
        interpret=interpret,
        name="sim_step",
    )(bufs.astype(jnp.float32), rate.astype(jnp.float32),
      cap.astype(jnp.float32))
