"""Set-up: seconds appending the load to the memtable and to the
resident grid's tail, stages ``ingest_memtable`` (storage/region.py
_write_locked) + ``ingest_grid_tail`` (storage/cache.py)."""

from stage_metrics import setup_seconds


def read(ctx):
    return setup_seconds(ctx, ("ingest_memtable", "ingest_grid_tail"))
