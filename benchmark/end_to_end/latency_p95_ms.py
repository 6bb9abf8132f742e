"""95th percentile (nearest rank) of the client-side latency of all
requests of the window."""

import math


def read(ctx):
    lat = sorted(r["latency"] for r in ctx["log"])
    return 1e3 * lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
