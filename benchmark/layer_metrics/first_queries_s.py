"""Seconds of step 6: the first requests of every entry of the mix,
which build the resident state and load or compile every program."""


def read(ctx):
    return ctx["phases"]["first_queries_s"]
