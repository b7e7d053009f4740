"""The trainer's own host spans laid on a traced window's clock, and the
device-idle time between episode programs split among them.

The program records its spans (``repro.core.tracing``) on
``time.perf_counter_ns``; the trace keeps its own clock. The window is
stamped on both: ``ctx.window`` on ``perf_counter`` and the
``bench.window`` span (``ctx.trace.lo``/``hi``) on the trace's. The two
edges give the offset between the clocks. Each idle interval between two
consecutive runs of the episode program (the intervals
``DeviceTrace.idle_between`` sums) is split by the innermost program span
open at each instant, by exact overlap.

Where the program records no spans (a build without them), or the two
window lengths differ by more than ``MAX_SKEW_S``, nothing is read.
"""

from __future__ import annotations

MAX_SKEW_S = 1e-3

# the trainer's phase -> the part of the host gap it is counted in; any
# other span, or none, is "other"
PARTS = {"ppo.dispatch": "dispatch", "ppo.rewards": "rewards",
         "ppo.select": "select", "ppo.best_copy": "select"}
NAMES = ("dispatch", "rewards", "select", "other")


def on_trace_clock(ctx):
    """The program's spans that overlap the window, as (start, end, name)
    in seconds on the trace's clock; None where there are none to map."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    ring = tracing.spans()
    ws, we = ctx.window
    lo, hi = ctx.trace.lo, ctx.trace.hi
    if not ring or abs((we - ws) - (hi - lo)) > MAX_SKEW_S:
        return None
    off = 0.5 * ((lo - ws) + (hi - we))
    out = []
    for name, s, e, _ in ring:
        s, e = s * 1e-9 + off, e * 1e-9 + off
        if e > lo and s < hi:
            out.append((s, e, name))
    return out


def split(spans, a, b):
    """Seconds of [a, b) under each innermost span name (None where no
    span is open). Spans on one thread nest, so the innermost open span
    is the shortest that holds the instant."""
    inside = [sp for sp in spans if sp[1] > a and sp[0] < b]
    edges = sorted({a, b, *(t for s, e, _ in inside for t in (s, e)
                            if a < t < b)})
    out = {}
    for t0, t1 in zip(edges, edges[1:]):
        held = [sp for sp in inside if sp[0] <= t0 and sp[1] >= t1]
        name = min(held, key=lambda sp: sp[1] - sp[0])[2] if held else None
        out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


def gap_parts(ctx):
    """Mean device-idle seconds per gap between consecutive runs of the
    episode program, by part of the round (``NAMES``); they sum to the
    mean of ``ctx.trace.idle_between(ctx.program)``. None where the spans
    cannot be mapped or there is no gap."""
    spans = on_trace_clock(ctx)
    mods, dev = ctx.trace.modules(ctx.program)
    if spans is None or len(mods) < 2:
        return None
    total = dict.fromkeys(NAMES, 0.0)
    for a, b in zip(mods, mods[1:]):
        for s, e in ctx.trace._gaps(dev, a[1], b[0]):
            for name, secs in split(spans, s, e).items():
                total[PARTS.get(name, "other")] += secs
    return {k: v / (len(mods) - 1) for k, v in total.items()}


def gap_ms(ctx, part):
    parts = gap_parts(ctx)
    return None if parts is None else 1e3 * parts[part]
