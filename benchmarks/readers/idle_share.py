"""1 - union of device-op intervals over the traced window, device 0."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace['idle_share0']
