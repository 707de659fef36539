"""step.mfu: model FLOP utilization of the whole step, in % of the
chips' peak: the FLOPs the forward and backward passes need per token
(``flops/<family>.py``, recomputation not counted) times tokens per
second over the traced window, over chips times the bf16 peak."""


def read(ctx):
    if ctx.peaks is None:
        return None
    chips = len(ctx.trace.devices)
    return 100.0 * ctx.flops_per_token * ctx.tokens_per_s / (
        chips * ctx.peaks["bf16_flops_per_s"])
