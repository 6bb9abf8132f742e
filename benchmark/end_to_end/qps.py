"""All requests completed in the window over the window's whole length."""


def read(ctx):
    return len(ctx["log"]) / ctx["elapsed"]
