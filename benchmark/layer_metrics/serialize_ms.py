"""The reply's JSON a request: stage ``serialize``, _result_to_json and
web.json_response, which runs json.dumps (servers/http.py)."""

from stage_metrics import per_request_ms, window_seconds


def read(ctx):
    return per_request_ms(ctx, window_seconds(ctx, ("serialize",)))
