"""The decode sub-step against its memory roofline: the least time for
the bytes the algorithm needs (every weight once plus the REAL rows of
the cache in the cache's dtype — padding rows are not needed bytes) over
the device time of one sub-step."""
from benchmarks import xtrace


def read(ctx, match):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t, n = xtrace.module_time(ctx.trace, match)
    if not n:
        return None
    substep_s = t / n / ctx.raw['decode_block']
    need = ctx.counts.decode_substep_bytes(ctx.config,
                                           ctx.raw['real_rows_mean'])
    return 100.0 * need / ctx.peaks['hbm_bytes_per_s'] / substep_s
