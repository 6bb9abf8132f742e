"""The one stage boundary (utils/tracing.py ``Tracer.stage``) over the two
served paths: ``/v1/sql`` and the Prometheus ``query_range``, through
``HttpServer`` on the CPU.  Every stage feeds the always-on histogram
``greptime_query_stage_seconds{stage}``, a profiler annotation and, with
the tracer on, a span; the stages a per-layer reader sums are siblings.
"""

import gc
import glob
import json
import re
import time
import urllib.parse
import urllib.request

import jax
import pytest

from greptimedb_tpu.servers import HttpServer
from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.utils.telemetry import REGISTRY
from greptimedb_tpu.utils.tracing import TRACER

STAGE = "greptime_query_stage_seconds"
HANDLER = "greptime_http_request_duration_seconds"
RANGE_ROUTE = "/v1/prometheus/api/v1/query_range"
SQL = ("SELECT h, date_bin(INTERVAL '10 seconds', ts) AS b, avg(v) "
       "FROM cpu GROUP BY h, b")
PROMQL = "sum by (h) (rate(cpu[20s]))"
# what the benchmark's readers sum for a request (benchmark/layer_metrics)
SQL_STAGES = ("parse", "optimize", "plan", "scan_cache", "execute",
              "device_wait", "materialize", "serialize")
PROM_STAGES = ("parse", "selection", "sort_layout", "fused_kernel",
               "group_agg", "device_wait", "format", "serialize")


@pytest.fixture(scope="module")
def served():
    db = GreptimeDB()
    db.sql("CREATE TABLE cpu (h STRING, ts TIMESTAMP(3) TIME INDEX, "
           "v DOUBLE, PRIMARY KEY (h))")
    db.sql("INSERT INTO cpu VALUES " + ",".join(
        f"('h{i % 4}', {1000 * i}, {float(i)})" for i in range(200)))
    srv = HttpServer(db, port=0)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"

    def sql():
        body = urllib.parse.urlencode({"sql": SQL}).encode()
        with urllib.request.urlopen(base + "/v1/sql", data=body) as r:
            out = json.loads(r.read())
        assert out["code"] == 0 and out["output"][0]["records"]["rows"]

    def query_range():
        q = urllib.parse.urlencode(
            {"query": PROMQL, "start": "20", "end": "180", "step": "10"})
        with urllib.request.urlopen(f"{base}{RANGE_ROUTE}?{q}") as r:
            out = json.loads(r.read())
        assert out["status"] == "success" and out["data"]["result"]

    def tql():
        assert db.sql(f"TQL EVAL (20, 180, '10s') {PROMQL}").rows

    sql()           # warm: every program built, every cache filled
    query_range()
    try:
        yield {"sql": sql, "query_range": query_range, "tql": tql}
    finally:
        srv.stop()
        db.close()


def counts(stages):
    return {s: REGISTRY.value(STAGE, (s,)) for s in stages}


def hist_sum(name, labels):
    for mname, _kind, _ln, key, child in REGISTRY.snapshot():
        if mname == name and key == tuple(labels):
            return child.sum
    return 0.0


def test_disabled_stage_observes_and_never_spans(monkeypatch):
    assert not TRACER.enabled

    def boom(*a, **k):
        raise AssertionError("span() called with the tracer disabled")

    monkeypatch.setattr(TRACER, "span", boom)
    n0 = REGISTRY.value(STAGE, ("probe_disabled",))
    s0 = hist_sum(STAGE, ("probe_disabled",))
    with TRACER.stage("probe_disabled", rows=3) as st:
        time.sleep(0.002)
    assert REGISTRY.value(STAGE, ("probe_disabled",)) == n0 + 1
    assert hist_sum(STAGE, ("probe_disabled",)) - s0 == st.seconds >= 0.002
    assert TRACER._spans == []


@pytest.mark.parametrize("request_of, stages", [
    ("sql", ("http_request",) + SQL_STAGES),
    ("query_range", ("http_request",) + PROM_STAGES),
])
def test_served_request_raises_every_summed_stage(served, request_of, stages):
    before = counts(stages)
    served[request_of]()
    after = counts(stages)
    assert {s: after[s] - before[s] for s in stages} == {
        s: 1.0 for s in stages}


@pytest.mark.parametrize("request_of, stages", [
    ("sql", SQL_STAGES), ("query_range", PROM_STAGES)])
def test_summed_stages_are_siblings_inside_http_request(
        served, request_of, stages):
    TRACER.configure(enabled=True)
    TRACER.drain()
    try:
        served[request_of]()
        spans = TRACER.drain()
    finally:
        TRACER.disable()
    root = [s for s in spans if s["name"] == "http_request"]
    assert len(root) == 1
    summed = sorted((s for s in spans if s["name"] in stages),
                    key=lambda s: s["start_ns"])
    assert {s["name"] for s in summed} == set(stages)
    slack = 50_000  # ns: the span's epoch start and its timer differ by this
    for a, b in zip(summed, summed[1:]):
        assert a["end_ns"] <= b["start_ns"] + slack, (a["name"], b["name"])
    assert root[0]["start_ns"] <= summed[0]["start_ns"] + slack
    assert max(s["end_ns"] for s in summed) <= root[0]["end_ns"] + slack
    total = sum(s["end_ns"] - s["start_ns"] for s in summed)
    assert total <= root[0]["end_ns"] - root[0]["start_ns"] + slack
    # one trace: the loop thread's stages and the worker's share the id
    assert len({s["trace_id"] for s in spans}) == 1, [
        (s["name"], s["trace_id"][-4:]) for s in spans]


def test_query_range_histogram_covers_the_payload(served, monkeypatch):
    from greptimedb_tpu.promql import format as prom_format

    real = prom_format.range_payload

    def slow(res, steps):
        time.sleep(0.05)
        return real(res, steps)

    monkeypatch.setattr(prom_format, "range_payload", slow)
    s0 = hist_sum(HANDLER, (RANGE_ROUTE,))
    f0 = hist_sum(STAGE, ("format",))
    served["query_range"]()
    assert hist_sum(HANDLER, (RANGE_ROUTE,)) - s0 >= 0.05
    assert hist_sum(STAGE, ("format",)) - f0 >= 0.05


def test_full_collection_is_observed(served):
    name = "greptime_gc_pause_seconds"
    n2 = REGISTRY.value(name, ("2",))
    gc.collect()
    assert REGISTRY.value(name, ("2",)) == n2 + 1
    gc.collect(0)
    gc.collect(1)
    assert REGISTRY.value(name, ("2",)) == n2 + 1
    assert REGISTRY.value(name, ("0",)) == REGISTRY.value(name, ("1",)) == 0


def host_events(trace_dir) -> set[str]:
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    return {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}


def test_stages_and_program_names_on_the_profiler_host_plane(
        served, tmp_path):
    """While a profiler session is open a stage is an event of the host
    plane, by its name, on the device trace's clock; and every program
    the two warm requests ran carries a family name."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TRACER.stage("probe_mirrored"):
            served["sql"]()
            served["query_range"]()
    finally:
        jax.profiler.stop_trace()
    events = host_events(tmp_path)
    assert {"probe_mirrored", "http_request", "device_wait",
            "serialize"} <= events
    programs = {m.group(1) for m in (
        re.fullmatch(r"PjitFunction\((.+)\)", e) for e in events) if m}
    assert programs
    for name in programs:
        assert re.fullmatch(r"(sql|promql)_[a-z0-9_]+", name), name


def test_tracer_adds_no_device_sync(served, monkeypatch):
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)

    def syncs(request_of):
        calls.clear()
        served[request_of]()
        return len(calls)

    paths = ("sql", "query_range", "tql")
    off = {r: syncs(r) for r in paths}
    TRACER.configure(enabled=True)
    try:
        on = {r: syncs(r) for r in paths}
    finally:
        TRACER.disable()
    assert on == off
