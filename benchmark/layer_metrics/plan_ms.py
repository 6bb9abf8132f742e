"""Parse / plan: stages ``parse`` + ``optimize`` + ``plan`` a request
(SQL: serving/scheduler.py _make_sql_entry, query/engine.py
execute_select; PromQL: servers/http.py _eval_promql)."""

from stage_metrics import per_request_ms, window_seconds

STAGES = ("parse", "optimize", "plan")


def read(ctx):
    return per_request_ms(ctx, window_seconds(ctx, STAGES))
