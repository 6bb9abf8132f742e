"""Median client-side latency, send to last byte, of all requests of the
window."""

import statistics


def read(ctx):
    return 1e3 * statistics.median(r["latency"] for r in ctx["log"])
