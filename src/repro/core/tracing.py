"""Host spans of the trainer's phases.

``span(name, **args)`` marks one phase of host work. It opens a
``jax.profiler.TraceAnnotation``, so a profiler trace holds the span on
the same clock as the device's ops (TensorBoard, Perfetto), and it records
``(name, start_ns, end_ns, parent)`` on ``time.perf_counter_ns`` in a
bounded in-memory ring, so a reader in the same process can lay the phases
against a trace or a timer of its own. ``parent`` is the name of the span
open on the same thread when this one opened (None at the top). A block
that raises still closes and records its span. ``mark(name)`` records an
instant the same way, as a span of zero length.

There is no switch: with no profiler running a span costs a few
microseconds, so spans mark phases that run once per training round, never
a per-step or per-episode loop.
"""

from __future__ import annotations

import collections
import threading
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

# the most recent spans kept; at about six spans and marks a round this is
# some ten thousand rounds
RING_SIZE = 1 << 16

_ring = collections.deque(maxlen=RING_SIZE)
_open = threading.local()


class span:
    """Mark a block as the phase ``name``: ``with span("ppo.rewards"):``.
    With ``step_num`` the span is a ``StepTraceAnnotation``: a trace
    viewer's step view splits the trace at each one."""

    __slots__ = ("name", "_ann", "_parent", "_start")

    def __init__(self, name, *, step_num=None, **args):
        self.name = name
        self._ann = (TraceAnnotation(name, **args) if step_num is None else
                     StepTraceAnnotation(name, step_num=step_num, **args))

    def __enter__(self):
        stack = _stack()
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _ring.append((self.name, self._start, end, self._parent))
        _stack().pop()
        return False


def mark(name):
    """An instant: the event ``name`` happened now. Recorded in the ring
    as a zero-length entry ``(name, t, t, parent)`` and emitted as a
    zero-length ``TraceAnnotation``; being zero-length, it is never the
    innermost span open over an interval."""
    stack = _stack()
    parent = stack[-1] if stack else None
    with TraceAnnotation(name):
        t = time.perf_counter_ns()
    _ring.append((name, t, t, parent))


def _stack():
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def spans():
    """A copy of the ring, oldest first: ``(name, start_ns, end_ns,
    parent)`` per closed span."""
    return list(_ring)
