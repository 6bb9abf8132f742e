"""The scheduler's queue a request, submit to claim, all priorities:
``greptime_scheduler_wait_seconds`` (serving/scheduler.py)."""

from stage_metrics import family_seconds, per_request_ms

HIST = "greptime_scheduler_wait_seconds"


def read(ctx):
    return per_request_ms(ctx, family_seconds(ctx["metrics_after"], HIST)
                          - family_seconds(ctx["metrics_before"], HIST))
