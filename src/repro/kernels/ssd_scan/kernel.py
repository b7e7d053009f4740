"""Mamba2 SSD chunked scan for TPU.

State-space duality splits the sequence into chunks of length Q: the
intra-chunk term is a masked (C B^T ⊙ L) x quadratic form — two MXU matmuls —
and the inter-chunk term is a tiny (P, N) state recurrence. Grid:
(B, H, n_chunks) with the chunk dimension innermost (sequential), carrying
the running state h (P, N) in VMEM scratch across chunks; h is re-zeroed when
a new (batch, head) pair starts (chunk index 0).

Per-block VMEM working set at the mamba2-1.3b config (Q=128, P=64, N=128):
x (128x64) + B/C (128x128) + L (128x128) + h (64x128) in fp32 ≈ 0.3 MB.

B/C groups broadcast over heads through the index_map (g = h // (H // G)), so
grouped B/C tiles are fetched once per group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *, Q):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (Q,)
    A = a_ref[0].astype(jnp.float32)                 # scalar (this head)
    Bm = b_ref[0, :, 0, :].astype(jnp.float32)       # (Q, N)
    Cm = c_ref[0, :, 0, :].astype(jnp.float32)       # (Q, N)

    dA = dt * A                                      # (Q,) negative
    dA_cum = jnp.cumsum(dA)                          # (Q,)

    # intra-chunk: (C B^T ⊙ L) @ (x * dt)
    li = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    seg = dA_cum[:, None] - dA_cum[None, :]          # sum over (j, i]
    L = jnp.where(li >= lj, jnp.exp(seg), 0.0)       # (Q, Q)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    xdt = x * dt[:, None]
    y_diag = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    h = h_ref[...]                                   # (P, N)
    y_off = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(dA_cum)[:, None]         # (Q, P)

    y_ref[0, :, 0, :] = (y_diag + y_off).astype(y_ref.dtype)

    # update state: h' = h * exp(sum dA) + sum_l decay_l dt_l x_l B_l^T
    decay = jnp.exp(dA_cum[Q - 1] - dA_cum) * dt     # (Q,)
    state_upd = jax.lax.dot_general(x, Bm * decay[:, None],
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    h_ref[...] = h * jnp.exp(dA_cum[Q - 1]) + state_upd


def ssd_scan_pallas(x, dt, A, B, C, *, chunk=128, interpret=True):
    """x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,g,n) -> y:(b,s,h,p).
    (Final state is not returned by the kernel path; the training forward
    doesn't need it — prefill uses the jnp oracle which does return it.)"""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    Q = min(chunk, s)
    assert s % Q == 0, (s, Q)
    nc = s // Q
    group = h // g

    kernel = functools.partial(_ssd_kernel, Q=Q)
    grid = (b, h, nc)
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, Q, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, Q, 1, n),
                         lambda bi, hi, ci, group=group: (bi, ci, hi // group, 0)),
            pl.BlockSpec((1, Q, 1, n),
                         lambda bi, hi, ci, group=group: (bi, ci, hi // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=cparams,
        name="ssd_scan",
    )(x, dt.astype(jnp.float32), A.astype(jnp.float32), B, C)
