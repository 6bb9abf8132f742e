"""The readers of the program's stage histogram on a small written pair
of ``/metrics`` dicts: sums by hand, a stage that never ran (0, not an
error), a window without requests (``None``), a program without the
histogram (``None``: the metric is left out of the line)."""

import pytest
import run

STAGE = "greptime_query_stage_seconds"
ROUTE = "/v1/sql"
READERS = ["plan_ms", "queue_wait_ms", "engine_host_ms", "device_wait_ms",
           "materialize_ms", "serialize_ms", "gc_pause_ms",
           "server_unattributed_ms", "window_background_s", "load_parse_s",
           "load_encode_s", "load_wal_s", "load_memtable_s"]


def stages(**seconds):
    return {f'{STAGE}_sum{{stage="{k}"}}': v for k, v in seconds.items()}


def make_ctx(before, after, requests=4):
    log = [{"req": {"route": ROUTE}} for _ in range(requests)]
    ctx = {"metrics_before": before, "metrics_after": after, "log": log,
           "phases": {}}
    ctx["read"] = lambda name: run.load_module("layer_metrics", name).read(ctx)
    return ctx


BEFORE = {
    **stages(parse=1.0, optimize=0.5, plan=0.25, execute=2.0, device_wait=3.0,
             materialize=4.0, serialize=5.0, ingest_parse=10.0,
             ingest_encode=20.0, ingest_wal=30.0, ingest_memtable=40.0,
             ingest_grid_tail=2.0, flush=7.0),
    'greptime_scheduler_wait_seconds_sum{priority="interactive"}': 0.5,
    'greptime_scheduler_wait_seconds_sum{priority="normal"}': 0.25,
    'greptime_gc_pause_seconds_sum{generation="2"}': 1.0,
    f'greptime_http_request_duration_seconds_sum{{path="{ROUTE}"}}': 100.0,
    f'greptime_http_request_duration_seconds_count{{path="{ROUTE}"}}': 10.0,
}
# the window: 4 requests; per request parse 1 ms, optimize 2, plan 3,
# queue 4, execute 10 + scan_cache 5 (new in the window), device 20,
# materialize 30 + format 0 (never ran), serialize 40, handler 125
AFTER = {
    **stages(parse=1.004, optimize=0.508, plan=0.262, execute=2.040,
             scan_cache=0.020, device_wait=3.080, materialize=4.120,
             serialize=5.160, ingest_parse=10.0, ingest_encode=20.0,
             ingest_wal=30.0, ingest_memtable=40.0, ingest_grid_tail=2.0,
             flush=7.0),
    'greptime_scheduler_wait_seconds_sum{priority="interactive"}': 0.512,
    'greptime_scheduler_wait_seconds_sum{priority="normal"}': 0.254,
    'greptime_gc_pause_seconds_sum{generation="2"}': 1.1,
    f'greptime_http_request_duration_seconds_sum{{path="{ROUTE}"}}': 100.5,
    f'greptime_http_request_duration_seconds_count{{path="{ROUTE}"}}': 14.0,
}
BY_HAND = {
    "plan_ms": 6.0, "queue_wait_ms": 4.0, "engine_host_ms": 15.0,
    "device_wait_ms": 20.0, "materialize_ms": 30.0, "serialize_ms": 40.0,
    "gc_pause_ms": 25.0,
    "server_unattributed_ms": 125.0 - (6 + 4 + 15 + 20 + 30 + 40),
    "window_background_s": 0.0, "load_parse_s": 10.0, "load_encode_s": 20.0,
    "load_wal_s": 30.0, "load_memtable_s": 42.0,
}


def test_every_reader_has_an_entry_and_a_file():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    named = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert named[name]["source"] == "program_counter"
        assert "workloads" not in named[name]    # every cell reports them
        assert callable(run.load_module("layer_metrics", name).read)


@pytest.mark.parametrize("name", READERS)
def test_by_hand(name):
    assert make_ctx(BEFORE, AFTER).get("read")(name) == pytest.approx(
        BY_HAND[name], abs=1e-9)


def test_background_work_in_the_window_is_seen():
    after = {**AFTER, **stages(flush=7.5, compaction=1.25)}
    assert make_ctx(BEFORE, after)["read"]("window_background_s") == \
        pytest.approx(1.75)


@pytest.mark.parametrize("name", READERS)
def test_a_stage_that_never_ran_reads_zero(name):
    only = stages(http_request=1.0)    # the histogram is there, no more
    ctx = make_ctx(only, dict(only))
    if name == "server_unattributed_ms":
        assert ctx["read"](name) is None    # no handler histogram: no base
    else:
        assert ctx["read"](name) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_no_request_in_the_window(name):
    value = make_ctx(BEFORE, AFTER, requests=0)["read"](name)
    if name.endswith("_ms"):
        assert value is None
    else:                       # seconds of set-up or of the window
        assert value == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_histogram_reports_nothing(name):
    """The parent commit: the reader returns ``None`` and does not raise,
    so the line leaves the metric out.  ``queue_wait_ms`` reads a
    histogram the parent has."""
    old = {k: v for k, v in AFTER.items() if not k.startswith(STAGE)
           and "gc_pause" not in k}
    value = make_ctx(dict(old), old)["read"](name)
    assert value == 0.0 if name == "queue_wait_ms" else value is None
