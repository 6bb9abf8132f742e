"""Slab cells the PromQL window programs gathered, a request: delta of
``greptime_promql_window_rows_total`` (promql/engine.py, counted at
dispatch: padded matched series x slab width, host arithmetic on static
shapes) between the run's two ``GET /metrics``, over the window's
requests.  512 x 512 = 262,144 in ``node64.cpu_rate``; 512 x 4,096 would
mean every request fell to the cap (the whole series row).  A program
without the counter gives None."""

COUNTER = "greptime_promql_window_rows_total"


def read(ctx):
    if COUNTER not in ctx["metrics_after"] or not ctx["log"]:
        return None
    return (ctx["metrics_after"][COUNTER]
            - ctx["metrics_before"].get(COUNTER, 0.0)) / len(ctx["log"])
