"""Share of the window's rounds whose episode program was dispatched while
the round before was still running on the device: the trainer's
``ppo.dispatch_ahead`` marks (``repro.core.tracing.mark``) whose time lies
in the window, over the window's rounds. Read from the trainer's ring on
``perf_counter``; it needs no trace. Nothing is read where the program
makes no marks."""


def read(ctx):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "mark") or not ctx.rounds:
        return None
    ws, we = ctx.window
    n = sum(1 for name, t, _, _ in tracing.spans()
            if name == "ppo.dispatch_ahead" and ws <= t * 1e-9 <= we)
    return 100.0 * n / ctx.rounds
