"""Series the label matchers kept, a request: delta of
``greptime_promql_selected_series_total`` (counted where
``greptime_promql_window_rows_total`` is, at the dispatch of a window
program: a host integer, the length of the selection) between the run's
two ``GET /metrics``, over the window's requests.  The configuration's
file says what to expect (``series.matched``: 63,000 in
``k8s100k.namespace_cpu``).  A program without the counter gives None."""

COUNTER = "greptime_promql_selected_series_total"


def read(ctx):
    if COUNTER not in ctx["metrics_after"] or not ctx["log"]:
        return None
    return (ctx["metrics_after"][COUNTER]
            - ctx["metrics_before"].get(COUNTER, 0.0)) / len(ctx["log"])
