"""A number the driver measured, as it is (times an optional scale)."""


def read(ctx, key, scale=1.0):
    v = ctx.raw.get(key)
    return None if v is None else v * scale
