"""Result rows on the host a request: stages ``materialize`` (SQL:
query/engine.py execute_select, the executor's finish and _shape) +
``format`` (PromQL: servers/http.py _h_prom, range_payload)."""

from stage_metrics import per_request_ms, window_seconds


def read(ctx):
    return per_request_ms(ctx, window_seconds(ctx, ("materialize", "format")))
