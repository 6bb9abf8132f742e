"""What the stages do not see of the handler's time, a request:
``server_ms`` (greptime_http_request_duration_seconds of the window's
routes) less the six stage metrics.  ``gc_pause_ms`` is not taken off:
it lies inside them."""

PARTS = ("plan_ms", "queue_wait_ms", "engine_host_ms", "device_wait_ms",
         "materialize_ms", "serialize_ms")


def read(ctx):
    values = [ctx["read"](name) for name in ("server_ms",) + PARTS]
    if any(v is None for v in values):
        return None
    return values[0] - sum(values[1:])
