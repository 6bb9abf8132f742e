"""Share of the window's reply bodies that the native encoder wrote from
whole columns: 100 x delta of
``greptime_http_reply_encoded_total{encoder="columns"}`` over the delta
of all its children (servers/http.py ``_json_reply``, counted where the
body is built).  Expected 100 in a cell whose every reply is a result
with rows; under 100 is a silent fall back to ``json.dumps`` over rows
(no library, a column of a kind the encoder does not know).  A program
without the counter gives None."""

COUNTER = "greptime_http_reply_encoded_total{"


def read(ctx):
    def counts(m):
        every = {k: v for k, v in m.items() if k.startswith(COUNTER)}
        return (sum(v for k, v in every.items()
                    if 'encoder="columns"' in k), sum(every.values()))

    cols_after, all_after = counts(ctx["metrics_after"])
    cols_before, all_before = counts(ctx["metrics_before"])
    if all_after == all_before:
        return None
    return 100.0 * (cols_after - cols_before) / (all_after - all_before)
