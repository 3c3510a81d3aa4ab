"""Model flop utilization: the benchmark's own flop count per token
(3 x forward, recompute not counted) x tokens/s per chip over the chip's
bf16 peak. From the end-to-end rate: the whole window, stalls included."""


def read(ctx):
    if ctx.peaks is None or 'tokens_per_s_chip' not in ctx.raw:
        return None
    return ctx.counts.mfu_percent(ctx.config, ctx.raw['seq'],
                                  ctx.raw['tokens_per_s_chip'],
                                  ctx.peaks['bf16_flops_per_s'])
