"""The host's wait for the device a request: stage ``device_wait``, the
one place a result leaves the device (query/physical.py fetch_host;
servers/http.py _eval_promql, on the worker thread)."""

from stage_metrics import per_request_ms, window_seconds


def read(ctx):
    return per_request_ms(ctx, window_seconds(ctx, ("device_wait",)))
