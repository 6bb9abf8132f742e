"""Set-up: seconds of column coercion, tag encoding and the duplicate
check, stage ``ingest_encode`` (storage/region.py _write_locked)."""

from stage_metrics import setup_seconds


def read(ctx):
    return setup_seconds(ctx, ("ingest_encode",))
