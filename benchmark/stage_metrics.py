"""What the readers of the program's stage histogram share.

The program observes every stage of its query and ingest paths into
``greptime_query_stage_seconds{stage}`` (greptimedb_tpu/utils/tracing.py
``Tracer.stage``), tracer on or off.  A reader takes ``_sum`` of the
stages it names out of ``GET /metrics`` as ``run.py`` parsed it.  A
program without the histogram (a commit before it) gives ``None``, so
the metric is left out of the line; a stage that never ran reads 0.
"""

STAGE = "greptime_query_stage_seconds"


def has_stages(metrics: dict) -> bool:
    return any(k.startswith(STAGE) for k in metrics)


def family_seconds(metrics: dict, family: str) -> float:
    """``_sum`` of every child of one histogram."""
    prefix = f"{family}_sum"
    return sum(v for k, v in metrics.items()
               if k == prefix or k.startswith(prefix + "{"))


def stage_seconds(metrics: dict, stages) -> float:
    return sum(metrics.get(f'{STAGE}_sum{{stage="{s}"}}', 0.0)
               for s in stages)


def setup_seconds(ctx: dict, stages):
    """Seconds of ``stages`` up to the window's opening: load, count
    back and first queries (``metrics_before`` is taken after them)."""
    if not has_stages(ctx["metrics_before"]):
        return None
    return stage_seconds(ctx["metrics_before"], stages)


def window_seconds(ctx: dict, stages):
    """Seconds of ``stages`` inside the window."""
    if not has_stages(ctx["metrics_after"]):
        return None
    return (stage_seconds(ctx["metrics_after"], stages)
            - stage_seconds(ctx["metrics_before"], stages))


def per_request_ms(ctx: dict, seconds):
    """Seconds of the window as milliseconds a request of the window."""
    if seconds is None or not ctx["log"]:
        return None
    return 1e3 * seconds / len(ctx["log"])
