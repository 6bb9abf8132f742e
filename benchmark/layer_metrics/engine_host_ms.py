"""The engines' host side of a request, look-ups and enqueue: stages
``scan_cache`` + ``execute`` (query/engine.py execute_select) and
``selection`` + ``sort_layout`` + ``window_kernel`` + ``fused_kernel`` +
``group_agg`` (promql/engine.py, compile/fused.py).  None of them waits
for the device in a warm request: that is ``device_wait_ms``."""

from stage_metrics import per_request_ms, window_seconds

STAGES = ("scan_cache", "selection", "sort_layout", "execute",
          "window_kernel", "fused_kernel", "group_agg")


def read(ctx):
    return per_request_ms(ctx, window_seconds(ctx, STAGES))
