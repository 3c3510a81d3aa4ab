"""A percentile of a list of samples the driver kept."""
from benchmarks import window


def read(ctx, key, q, scale=1.0):
    vals = ctx.raw.get(key)
    if not vals:
        return None
    return window.percentile(vals, q) * scale
