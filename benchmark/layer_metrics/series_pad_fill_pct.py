"""Share of the window programs' series slots that held a series: 100 x
delta of ``greptime_promql_selected_series_total`` over delta of
``greptime_promql_padded_series_total`` (the selection padded to the
program's static size, a power of two) inside the window.  63,000 of
65,536 = 96.1 in ``k8s100k.namespace_cpu``; the rest is work on slots
that own no rows.  A program without the counters, or a window that
dispatched no program, gives None."""

SELECTED = "greptime_promql_selected_series_total"
PADDED = "greptime_promql_padded_series_total"


def read(ctx):
    after, before = ctx["metrics_after"], ctx["metrics_before"]
    if SELECTED not in after or PADDED not in after:
        return None
    padded = after[PADDED] - before.get(PADDED, 0.0)
    if padded <= 0:
        return None
    return 100.0 * (after[SELECTED] - before.get(SELECTED, 0.0)) / padded
