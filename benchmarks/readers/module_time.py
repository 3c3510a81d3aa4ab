"""Device time of the compiled programs whose name matches, from the
'XLA Modules' line of device 0: per call over `decode_block`
(`call_over_block`, milliseconds) or as a share of busy time
(`busy_share`, percent)."""
from benchmarks import xtrace


def read(ctx, match, per):
    if ctx.trace is None:
        return None
    t, n = xtrace.module_time(ctx.trace, match)
    if not n:
        return None
    if per == 'busy_share':
        return 100.0 * t / ctx.trace['busy_s0']
    return 1e3 * t / n / ctx.raw['decode_block']
