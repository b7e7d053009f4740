"""Blocked online-softmax (flash) attention for TPU.

Grid: (B, Hq, n_q_blocks, n_kv_blocks); the kv-block dimension is the
innermost (sequential on TPU — "arbitrary" semantics), carrying the running
max / normalizer / accumulator in VMEM scratch. Q/K/V tiles are VMEM blocks
via BlockSpec; scores run on the MXU in fp32; fully-masked kv blocks are
skipped (causal => ~2x fewer MXU flops; sliding window => O(S*W) instead of
O(S^2)).

GQA is handled in the K/V index_map (kv_head = q_head // group), so KV tiles
are fetched once per group without materializing repeated heads in HBM.

Layouts: q (B, Hq, S, D); k/v (B, Hkv, S, D); D and the block sizes should be
multiples of 128 (MXU tiles) on real hardware — interpret mode (CPU tests)
accepts anything.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               blk_q, blk_k, n_kv, causal, window, scale):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * blk_q
    k_start = ki * blk_k
    # block-level skip: causal => kv block must start at/before q block end;
    # sliding window => kv block must end after q block start - window
    live = jnp.bool_(True)
    if causal:
        live = k_start <= q_start + blk_q - 1
    if window is not None:
        live = jnp.logical_and(live,
                               k_start + blk_k - 1 >= q_start - (window - 1))

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)      # (blk_q, D)
        k = k_ref[0, 0].astype(jnp.float32)      # (blk_k, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        ok = jnp.bool_(True)
        if causal:
            ok = qp >= kp
        if window is not None:
            ok = jnp.logical_and(ok, qp - kp < window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        # rows with no live key yet: keep everything at the init state
        p = jnp.where((m_new[:, None] <= NEG_INF / 2), 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=None,
                         blk_q=512, blk_k=512, interpret=True):
    """q: (B, Hq, S, D); k/v: (B, Hkv, Skv, D). Returns (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, Skv)
    assert S % blk_q == 0 and Skv % blk_k == 0, (S, Skv, blk_q, blk_k)
    n_q, n_kv = S // blk_q, Skv // blk_k
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_fa_kernel, blk_q=blk_q, blk_k=blk_k,
                               n_kv=n_kv, causal=causal, window=window,
                               scale=scale)
    grid = (B, Hq, n_q, n_kv)
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running max
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running normalizer
            pltpu.VMEM((blk_q, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        compiler_params=cparams,
        name="flash_attention",
    )(q, k, v)
