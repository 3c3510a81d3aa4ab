"""Share of device busy time in one family of operations (device 0)."""
from benchmarks import xtrace

_PRED = {'custom_call': xtrace.is_custom_call}


def read(ctx, match):
    if ctx.trace is None:
        return None
    t, _ = xtrace.time_of(ctx.trace['events0'], _PRED[match])
    return 100.0 * t / ctx.trace['busy_s0']
