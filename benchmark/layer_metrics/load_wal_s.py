"""Set-up: seconds appending the load to the write-ahead log, stage
``ingest_wal`` (storage/region.py _write_locked)."""

from stage_metrics import setup_seconds


def read(ctx):
    return setup_seconds(ctx, ("ingest_wal",))
