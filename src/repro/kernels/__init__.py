"""Pallas TPU kernels for the framework's compute hot spots.

  flash_attention/  blocked online-softmax attention (causal + sliding
                    window + GQA), VMEM-tiled, MXU-aligned
  ssd_scan/         Mamba2 SSD chunked scan (intra-chunk quadratic on the
                    MXU + inter-chunk state recurrence in VMEM scratch)
  sim_step/         AutoMDT dense-simulator sub-stepping across an env batch
                    (the paper's own hot loop: offline PPO training)

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper; compiled on TPU, interpreted on CPU) and ref.py (pure-jnp oracle).
"""

from __future__ import annotations

import jax


def default_interpret() -> bool:
    """The kernel wrappers' ``interpret=None`` default: compiled Mosaic on a
    TPU, the Pallas interpreter on the CPU (tests, CPU rehearsals). Any
    other platform has no path here and raises rather than silently
    interpreting on an accelerator."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels here compile for TPU or interpret "
                       f"on CPU; no path for platform {platform!r}")


def compiled_kernel_in(hlo_text: str, name: str) -> bool:
    """Whether a compiled program's text holds kernel ``name`` as a Mosaic
    custom call, i.e. compiled for the TPU. An interpreted kernel leaves its
    name only in the op metadata of ordinary XLA ops."""
    return any(name in line and 'custom_call_target="tpu_custom_call"' in line
               for line in hlo_text.splitlines())
