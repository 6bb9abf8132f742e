"""Share of the window's PromQL reply bodies that the native encoder
wrote from the result's arrays: 100 x delta of
``greptime_http_reply_encoded_total{encoder="columns"}`` over the delta
of all its children, on the routes under ``/v1/prometheus/``
(servers/http.py ``_prom_reply``, counted where the body is built).
Expected 100 in a cell whose every request is a ``query_range``; under
100 is a silent fall back to ``json.dumps`` over a point-by-point list
(no library, a ``result`` something read or wrote through, an instant
query).  A program that does not count its PromQL replies gives None."""

COUNTER = "greptime_http_reply_encoded_total{"
ROUTES = 'route="/v1/prometheus/'


def read(ctx):
    def counts(m):
        every = {k: v for k, v in m.items()
                 if k.startswith(COUNTER) and ROUTES in k}
        return (sum(v for k, v in every.items()
                    if 'encoder="columns"' in k), sum(every.values()))

    cols_after, all_after = counts(ctx["metrics_after"])
    cols_before, all_before = counts(ctx["metrics_before"])
    if all_after == all_before:
        return None
    return 100.0 * (cols_after - cols_before) / (all_after - all_before)
