"""Full (generation 2) Python garbage collections in the server's
process, a request: ``greptime_gc_pause_seconds{generation="2"}``
(utils/tracing.py GC_PAUSE).  A pause falls inside whatever stage was
running, so this is a part of the stage metrics, not a term beside them."""

from stage_metrics import family_seconds, has_stages, per_request_ms

HIST = "greptime_gc_pause_seconds"


def read(ctx):
    if not has_stages(ctx["metrics_after"]):
        return None
    return per_request_ms(ctx, family_seconds(ctx["metrics_after"], HIST)
                          - family_seconds(ctx["metrics_before"], HIST))
