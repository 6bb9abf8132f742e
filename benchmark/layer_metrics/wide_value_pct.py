"""Share of the window programs' slab cells that were read as two-word
values: 100 x delta of ``greptime_promql_wide_rows_total`` (padded series
x ``W`` of every window program dispatched on a WIDE sort layout: a
DOUBLE column past 2^24 kept as two f32 words, so that a counter's
increase is exact) over delta of ``greptime_promql_window_rows_total``
(the same product of every window program dispatched), inside the
window; both are host integers off static shapes, counted at a program's
dispatch.  100 in a cell whose counters pass 2^24 (bytes); under 100
there is a silent fall to the f32 column, whose answers this cell's
limits refuse.  A program without the counter, or a window that
dispatched no program, gives None."""

ROWS = "greptime_promql_window_rows_total"
WIDE = "greptime_promql_wide_rows_total"


def read(ctx):
    after, before = ctx["metrics_after"], ctx["metrics_before"]
    if ROWS not in after or WIDE not in after:
        return None
    rows = after[ROWS] - before.get(ROWS, 0.0)
    if rows <= 0:
        return None
    return 100.0 * (after[WIDE] - before.get(WIDE, 0.0)) / rows
