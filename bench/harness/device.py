"""The chip the run is on, its compile cache, and its memory reading."""

from __future__ import annotations

import os
import sys

from .spec import ROOT

PLATFORM = "tpu"
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def require_chips(n):
    """The first ``n`` JAX devices, which must be TPUs; raises ``NoChip``
    otherwise (there is no CPU fallback)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def enable_compile_cache():
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``, a fixed path. Every program is kept,
    however fast it compiled, so a cell's second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compilations, so a run can say how many fell inside
    its measured window."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def describe(devices, extra_bytes=0):
    """The result line's ``device`` object. ``memory_peak_bytes`` is the
    allocator's peak on the fullest chip plus ``extra_bytes``: the compiled
    temporaries of the window's largest program, which the allocator's
    counter does not include on this backend."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(max(peaks) + extra_bytes)}


def warn(msg):
    print(msg, file=sys.stderr, flush=True)
