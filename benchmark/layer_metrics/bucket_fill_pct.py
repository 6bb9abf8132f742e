"""Share of the (group, bucket) slots that histogram_quantile programs
folded which held a bucket series: 100 x delta of
``greptime_promql_histogram_filled_buckets_total`` over delta of
``greptime_promql_histogram_buckets_total`` (G x K of every program
dispatched, K the selection's distinct bounds), inside the window; host
integers off the program's numbering.  100 where every group has every
bound; under 100 is padding from uneven bucket sets.  A program without
the counters, or a window that folded nothing, gives None."""

FILLED = "greptime_promql_histogram_filled_buckets_total"
SLOTS = "greptime_promql_histogram_buckets_total"


def read(ctx):
    after, before = ctx["metrics_after"], ctx["metrics_before"]
    if FILLED not in after or SLOTS not in after:
        return None
    slots = after[SLOTS] - before.get(SLOTS, 0.0)
    if slots <= 0:
        return None
    return 100.0 * (after[FILLED] - before.get(FILLED, 0.0)) / slots
