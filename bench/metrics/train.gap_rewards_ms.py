"""Mean device-idle time per gap between episode programs while the
trainer's innermost open span is ``ppo.rewards``: the copy of the round's
episode rewards to the host. From the trace and the trainer's own spans
(``harness.program_spans``)."""

from harness import program_spans


def read(ctx):
    return program_spans.gap_ms(ctx, "rewards")
