"""Seconds of flush and compaction inside the window: stages ``flush`` +
``compaction`` (storage/region.py _flush_locked, compact_files).
Expected 0: a window with background work in it is not a steady one."""

from stage_metrics import window_seconds


def read(ctx):
    return window_seconds(ctx, ("flush", "compaction"))
