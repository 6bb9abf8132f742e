"""Rows acknowledged by /v1/arrow/write over the seconds of the load."""


def read(ctx):
    return ctx["rows_acked"] / ctx["phases"]["load_s"]
