"""Host spans of the benchmark, and the reduction of a profiler trace to
device busy time, idle gaps and per-program durations.

The benchmark opens a span around each call it makes into a layer of the
program (``Spans``); in a traced run each span is a
``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so it lands in
the trace on the device's clock. ``DeviceTrace`` reads the ``.xplane.pb``
with ``jax.profiler.ProfileData``: device planes give the "XLA Ops" and
"XLA Modules" lines, the host plane gives the ``bench.*`` spans, and the
``bench.window`` span bounds everything that is counted.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
from collections import defaultdict

WINDOW = "window"


class Spans:
    """Named host spans around the benchmark's calls into the program: in a
    traced run each is a ``jax.profiler.TraceAnnotation`` named
    ``bench.<name>``; otherwise they cost nothing."""

    def __init__(self, annotate=False):
        self.annotate = annotate

    def span(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        return TraceAnnotation("bench." + name)


class Tracer:
    """The profiler around a traced run's window, which lasts at most
    ``limit_s`` (a traffic's ``trace_seconds``) so the trace stays small
    enough to read within the run's time."""

    def __init__(self, log_dir, limit_s=None):
        self.log_dir = str(log_dir)
        self.limit_s = limit_s

    def seconds(self, seconds):
        return min(seconds, self.limit_s) if self.limit_s else seconds

    def start(self):
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def annotation(self):
        """An open ``bench.window`` annotation; the caller exits it."""
        from jax.profiler import TraceAnnotation
        ann = TraceAnnotation("bench." + WINDOW)
        ann.__enter__()
        return ann

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def read(self):
        try:
            return DeviceTrace(self.log_dir)
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


class DeviceTrace:
    """One traced window, in seconds on the trace's clock."""

    def __init__(self, log_dir):
        from jax.profiler import ProfileData
        files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        pd = ProfileData.from_file(files[-1])
        self.devices = []   # per device: {"ops": [...], "modules": [...]}
        host = []
        for plane in pd.planes:
            name = plane.name
            if name.startswith("/device:") and "CPU" not in name:
                dev = {"name": name, "ops": [], "modules": []}
                for line in plane.lines:
                    key = ("ops" if line.name == "XLA Ops" else
                           "modules" if line.name == "XLA Modules" else None)
                    if key is None:
                        continue
                    dev[key] = [(ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9,
                                 ev.name) for ev in line.events]
                if dev["ops"] or dev["modules"]:
                    self.devices.append(dev)
            elif name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            host.append((ev.start_ns * 1e-9,
                                         (ev.start_ns + ev.duration_ns)
                                         * 1e-9, ev.name[len("bench."):]))
        windows = [h for h in host if h[2] == WINDOW]
        if not windows:
            raise ValueError("the trace holds no bench.window span")
        self.lo, self.hi = windows[0][0], windows[0][1]
        self.host = [h for h in host if h[2] != WINDOW]
        for dev in self.devices:
            dev["ops"] = _clip(dev["ops"], self.lo, self.hi)
            dev["modules"] = _clip(dev["modules"], self.lo, self.hi)
            dev["busy"] = _union([(s, e) for s, e, _ in
                                  (dev["ops"] or dev["modules"])])

    @property
    def window_s(self):
        return self.hi - self.lo

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the devices
        that ran any."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in d["busy"])
                   for d in self.devices) / len(self.devices)

    def _gaps(self, dev, lo, hi):
        out, t = [], lo
        for s, e in dev["busy"]:
            if e <= lo or s >= hi:
                continue
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def modules(self, substr):
        """(start, end) of every run of a compiled program whose module
        name contains ``substr``, on the first device that ran one."""
        for dev in self.devices:
            mods = sorted((s, e) for s, e, n in dev["modules"] if substr in n)
            if mods:
                return mods, dev
        return [], None

    def idle_between(self, substr):
        """Device-idle seconds between each pair of consecutive runs of the
        program ``substr``."""
        mods, dev = self.modules(substr)
        return [sum(e - s for s, e in self._gaps(dev, a[1], b[0]))
                for a, b in zip(mods, mods[1:])]

    def host_span_at(self, t):
        """The innermost benchmark span open at ``t``, or "other"."""
        best = None
        for s, e, name in self.host:
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "other"

    def breakdown(self, top=10):
        """The device ops that took most time, and the longest idle gaps
        named by what the host was doing, on the busiest device."""
        if not self.devices:
            return {"device_ops": [], "idle_gaps": []}
        dev = max(self.devices, key=lambda d: sum(e - s for s, e in d["busy"]))
        per_op = defaultdict(float)
        for s, e, n in dev["ops"]:
            # an op's event name is its HLO text; its name is what precedes
            # " = "
            per_op[n.split(" = ", 1)[0].lstrip("%")[:64]] += e - s
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self._gaps(dev, self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[self.host_span_at(0.5 * (a + b)), b - a]
                              for a, b in gaps]}

    def describe(self):
        """One line per device plane: its name and event counts."""
        return [f"{d['name']}: {len(d['ops'])} ops, {len(d['modules'])} "
                f"module runs in the window" for d in self.devices]
