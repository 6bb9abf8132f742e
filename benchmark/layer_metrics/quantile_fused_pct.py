"""Share of the window's histogram_quantile series that the fused
program computed: 100 x delta of
``greptime_promql_histogram_quantiles_total{path="fused"}`` over the
delta of all its children (``path="unfused"``: the argument evaluated,
then folded apart), counted where a program is dispatched.  100 where
every ``histogram_quantile(q, sum by (le, ...)(rate(...)))`` ran as one
program; under 100 is a silent fall back.  A program without the
counter, or a window that computed no quantile, gives None."""

COUNTER = "greptime_promql_histogram_quantiles_total{"


def read(ctx):
    def counts(m):
        every = {k: v for k, v in m.items() if k.startswith(COUNTER)}
        return (sum(v for k, v in every.items() if 'path="fused"' in k),
                sum(every.values()))

    fused_after, all_after = counts(ctx["metrics_after"])
    fused_before, all_before = counts(ctx["metrics_before"])
    if all_after == all_before:
        return None
    return 100.0 * (fused_after - fused_before) / (all_after - all_before)
