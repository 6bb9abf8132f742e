"""Union of device-operation intervals in the traced slice over the
requests completed in it."""


def read(ctx):
    t = ctx["trace"]
    return 1e3 * t["busy_s"] / t["requests"] if t else None
