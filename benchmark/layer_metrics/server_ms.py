"""Mean seconds inside the server's handler for the window's requests:
delta sum over delta count of greptime_http_request_duration_seconds for
the routes the window used, /metrics before and after."""

HIST = "greptime_http_request_duration_seconds"


def window_seconds(ctx):
    """(seconds, requests) the server observed in the window."""
    routes = {r["req"]["route"] for r in ctx["log"]}
    total = count = 0.0
    for route in routes:
        lab = f'{{path="{route}"}}'
        for part, sign in ((ctx["metrics_after"], 1), (ctx["metrics_before"], -1)):
            total += sign * part.get(f"{HIST}_sum{lab}", 0.0)
            count += sign * part.get(f"{HIST}_count{lab}", 0.0)
    return total, count


def read(ctx):
    total, count = window_seconds(ctx)
    return 1e3 * total / count if count else None
