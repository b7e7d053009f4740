"""Mean device duration of one run of the episode program (rollout plus
PPO update), from its module events in the trace."""


def read(ctx):
    mods, _ = ctx.trace.modules(ctx.program)
    return 1e3 * sum(e - s for s, e in mods) / len(mods) if mods else None
