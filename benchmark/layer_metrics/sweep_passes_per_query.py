"""Traversals of [series, steps, swept width] that the window programs of
one request emit: delta of ``greptime_promql_sweep_passes_total`` (counted
at a program's dispatch beside ``greptime_promql_window_rows_total``: a
host integer off the program's static class, promql/engine.py
``sweep_passes``, the helper the program's own source is emitted from)
between the run's two ``GET /metrics``, over the window's requests.  A
window program that picks everything it reads at a window edge in one
traversal, the edge's count with it, reads 2 for ``rate`` (4 for min/max:
the two masked reduces besides; 0 where the slab is searched, past 8,192
columns); one that sweeps once for every array it reads would read 8 (two
edge counts and six picks).  A program without the counter, or a window
that dispatched no program, gives None."""

PASSES = "greptime_promql_sweep_passes_total"
ROWS = "greptime_promql_window_rows_total"


def read(ctx):
    after, before = ctx["metrics_after"], ctx["metrics_before"]
    if PASSES not in after or not ctx["log"]:
        return None
    if after.get(ROWS, 0.0) - before.get(ROWS, 0.0) <= 0:
        return None
    return (after[PASSES] - before.get(PASSES, 0.0)) / len(ctx["log"])
