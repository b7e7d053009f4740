"""jit'd wrapper around the flash-attention Pallas kernel: model-layout
(B, S, H, D) in/out, padding to block multiples, GQA via head-group
index-mapping, interpret mode on the CPU."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.flash_attention.kernel import flash_attention_bhsd


@partial(jax.jit, static_argnames=("causal", "window", "blk_q", "blk_k",
                                   "interpret"))
def flash_attention(q, k, v, q_pos=None, k_pos=None, *, causal=True,
                    window=None, blk_q=512, blk_k=512, interpret=None):
    """q: (B,S,Hq,D); k/v: (B,Skv,Hkv,D). Positions are assumed contiguous
    from 0 (training/prefill path); decode uses the cache path instead."""
    if interpret is None:
        interpret = default_interpret()
    B, S, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, Skv)
    pad_q = (-S) % blk_q
    pad_k = (-Skv) % blk_k
    qt = jnp.moveaxis(q, 2, 1)  # (B,Hq,S,D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded keys sit at positions > every query: causal-masked out
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               blk_q=blk_q, blk_k=blk_k, interpret=interpret)
    if pad_q:
        out = out[:, :, :S]
    return jnp.moveaxis(out, 1, 2)
