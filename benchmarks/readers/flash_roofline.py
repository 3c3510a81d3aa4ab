"""The flash forward+backward kernels against their roofline: the least
time for the causal flops and the bytes they must move (counted by
`benchmarks/counts.py`) over the kernels' device time per step. Reports
nothing where the traced steps ran no flash kernel."""
import re

from benchmarks import xtrace

_FLASH = re.compile(r'flash', re.I)


def read(ctx):
    tr, raw = ctx.trace, ctx.raw
    if tr is None or ctx.peaks is None or 'batch' not in raw:
        return None
    steps = raw.get('traced_steps')
    flash = [e for e in tr['events0'] if xtrace.is_custom_call(e)
             and _FLASH.search(xtrace.kernel_name(e))]
    if not flash or not steps:
        return None
    per_step = sum(e[2] for e in flash) / steps
    # device 0 runs its share of the batch and of the heads
    share = 1.0 / ctx.chips
    pct, _ = ctx.counts.roofline_percent(
        ctx.counts.flash_train_flops(ctx.config, raw['batch'], raw['seq']) * share,
        ctx.counts.flash_train_bytes(ctx.config, raw['batch'], raw['seq']) * share,
        per_step, ctx.peaks['bf16_flops_per_s'],
        ctx.peaks['hbm_bytes_per_s'])
    return pct
