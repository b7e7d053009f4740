"""Mean device-idle time between consecutive runs of the episode program:
what the trainer's host loop (reward copy, best-param copies, the draw,
``Workload.compiled()``) costs the device each round. From the trace."""


def read(ctx):
    gaps = ctx.trace.idle_between(ctx.program)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
