"""Set-up: seconds decoding the load's wire bodies, stage
``ingest_parse`` (servers/protocols.py)."""

from stage_metrics import setup_seconds


def read(ctx):
    return setup_seconds(ctx, ("ingest_parse",))
