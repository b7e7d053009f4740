"""Mean device-idle time per gap between episode programs under no other
part of the round: ``ppo.round``'s own time (the resample,
``Workload.compiled()``) and time outside every trainer span. From the
trace and the trainer's own spans (``harness.program_spans``)."""

from harness import program_spans


def read(ctx):
    return program_spans.gap_ms(ctx, "other")
