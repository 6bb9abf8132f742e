"""Process start to the window's opening: device, data, load, count
back, first queries, the first read of /metrics."""


def read(ctx):
    return ctx["setup_s"]
