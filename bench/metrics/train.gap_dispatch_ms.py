"""Mean device-idle time per gap between episode programs while the
trainer's innermost open span is ``ppo.dispatch``: the key split and the
call that dispatches the episode program. From the trace and the
trainer's own spans (``harness.program_spans``)."""

from harness import program_spans


def read(ctx):
    return program_spans.gap_ms(ctx, "dispatch")
