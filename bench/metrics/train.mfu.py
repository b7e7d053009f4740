"""The whole round's share of the chip's peak: actor-critic matmul FLOPs
per round (``harness.flops``, from shapes) times the rounds of the traced
window, over the window's host-clock length and the bf16 peak of the
device kind (``peaks.json``)."""


def read(ctx):
    if not ctx.rounds or not ctx.peaks:
        return None
    seconds = ctx.window[1] - ctx.window[0]
    return (100.0 * ctx.flops_per_round * ctx.rounds
            / (seconds * ctx.peaks["flops_bf16"]))
