"""Share of the columns the window programs' [S, T, .] passes ran over
that the slab's width ``W`` asked for: 100 x delta of
``greptime_promql_window_rows_total`` (padded series x ``W``) over delta
of ``greptime_promql_swept_columns_total`` (padded series x the columns
every compare-select pass sweeps; both counted at a program's dispatch,
host integers off static shapes) inside the window.  100 where the
gathered chunks are folded to the columns a window can read; under 100
is sweeping sentinels: ``W`` under one 128-row chunk, or the searched
form past 8,192 columns, which keeps its extra chunk.  A program that
sweeps the gathered ``W`` + 128 would read 50 in
``k8s100k.namespace_cpu`` (``W`` 128) and 80 in ``node64.cpu_rate``
(``W`` 512).  A program without the counter, or a window that
dispatched no program, gives None."""

ROWS = "greptime_promql_window_rows_total"
SWEPT = "greptime_promql_swept_columns_total"


def read(ctx):
    after, before = ctx["metrics_after"], ctx["metrics_before"]
    if ROWS not in after or SWEPT not in after:
        return None
    swept = after[SWEPT] - before.get(SWEPT, 0.0)
    if swept <= 0:
        return None
    return 100.0 * (after[ROWS] - before.get(ROWS, 0.0)) / swept
