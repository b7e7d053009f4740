"""Mean device-idle time per gap between episode programs while the
trainer's innermost open span is ``ppo.select`` or ``ppo.best_copy``: the
Python loop over the round's rewards, the history, the convergence test
and the copies of the best parameters. From the trace and the trainer's
own spans (``harness.program_spans``)."""

from harness import program_spans


def read(ctx):
    return program_spans.gap_ms(ctx, "select")
