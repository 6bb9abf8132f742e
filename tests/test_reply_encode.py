"""A reply built from columns: the native encoder's /v1/sql body against
``json.dumps`` of the same result as rows, byte for byte; the roads that
must fall back to ``json.dumps`` and say so in the counter; a served
GROUP BY whose rows are never built; and the Prometheus payloads against
their old point-by-point form: a ``query_range`` body written from the
result's arrays, its fall back, and the hand-over to a list once something
reads through ``result``."""

import collections.abc
import copy
import importlib.util
import json
import math
import os
import threading
import time
import types
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu import native
from greptimedb_tpu.promql import format as prom_format
from greptimedb_tpu.query.engine import ColumnRows, QueryResult
from greptimedb_tpu.servers import http
from greptimedb_tpu.utils.telemetry import REGISTRY

ROUTE = "/v1/sql"
RANGE_ROUTE = "/v1/prometheus/api/v1/query_range"
COUNTER = "greptime_http_reply_encoded_total"


@pytest.fixture(scope="module")
def encoder():
    """The library with the symbol, built here where the checkout has
    none (it is git-ignored); the cases that need it skip without it."""
    if native.lib() is None and native.build():
        native._TRIED = False  # look again
    if native.lib() is None or getattr(native.lib(), "_gt_no_json", False):
        pytest.skip("native library without gt_json_rows (no toolchain, or "
                    "a libstdc++ without floating-point to_chars)")


def encoded(road: str, route: str = ROUTE) -> float:
    return REGISTRY.value(COUNTER, (route, road))


def reply_and_reference(columns, names=None) -> tuple[bytes, bytes]:
    """(what _json_reply sends, json.dumps of the same dict over rows)."""
    names = names or [f"c{i}" for i in range(len(columns))]
    res = QueryResult(names, column_types=["String"] * len(names),
                      columns=ColumnRows(columns))
    body = http._result_to_json(res, time.perf_counter())
    assert res.columns is not None  # building the dict built no rows
    reference = copy.deepcopy(body)
    reference["output"][0]["records"]["rows"] = ColumnRows(columns).to_rows()
    return (http._json_reply(body, ROUTE).body,
            json.dumps(reference).encode())


F64 = np.array([
    0.0, -0.0, 1.0, -1.0, 100.0, 0.1, 1 / 3, -2.5e-7, 123456789.125,
    1e-5, 9.999e-5, 1e-4, 0.00012345, 1e15, 9999999999999998.0, 1e16,
    1.5e16, 1e21, 1e22, 123456789012345680.0, 1e100, 1.5e-100,
    5e-324, 2.225073858507201e-308, 2.2250738585072014e-308,
    1.7976931348623157e308, -1.7976931348623157e308,
    float("nan"), float("inf"), float("-inf")])
I64 = np.array([np.iinfo(np.int64).min, -1, 0, 1, 10, 99, 1700000000000,
                np.iinfo(np.int64).max])
TEXT = np.array([
    "", "plain", 'quo"te', "back\\slash", "\n\r\t\b\f", "\x00\x01\x1f\x7f",
    "café € 中", "pair \U0001f600 \U0010ffff", None,
    "</script>", "a" * 300], dtype=object)
RNG = np.random.default_rng(28)
MIXED = [
    np.array([f"host_{i}" for i in range(97)], dtype=object),
    1700000000000 + 3600000 * np.arange(97, dtype=np.int64),
    (RNG.random(97) * 100).astype(np.float32),
    RNG.standard_normal(97) * 10.0 ** RNG.integers(-9, 20, 97),
    np.arange(97) % 3 == 0,
]
MIXED[3][::7] = np.nan

CASES = {
    "float64": [F64],
    "float32": [np.clip(F64, -3e38, 3e38).astype(np.float32)],
    "float32_usage": [(RNG.random(500) * 100).astype(np.float32)],
    "float64_random": [RNG.standard_normal(2000)
                       * 10.0 ** RNG.integers(-30, 30, 2000)],
    "float16": [np.array([0.5, 65504.0, np.nan], dtype=np.float16)],
    "int64": [I64],
    "int32": [np.array([np.iinfo(np.int32).min, 0, 7], dtype=np.int32)],
    "int8": [np.array([-128, 127], dtype=np.int8)],
    "uint64": [np.array([0, np.iinfo(np.uint64).max], dtype=np.uint64)],
    "uint8": [np.array([0, 255], dtype=np.uint8)],
    "bool": [np.array([True, False, True])],
    "text": [TEXT],
    "text_all_none": [np.array([None, None], dtype=object)],
    "numpy_str": [np.array(["x", "yy", "é"])],
    "numpy_str_in_object": [np.array([np.str_("x"), "y"], dtype=object)],
    "strided": [np.arange(20, dtype=np.float64)[::2],
                np.arange(40, dtype=np.int64)[::4]],
    "mixed": MIXED,
    "zero_rows": [np.array([], dtype=object), np.array([], dtype=np.float64)],
    "one_cell": [np.array([1.5])],
}


@pytest.mark.parametrize("case", CASES)
def test_native_body_is_json_dumps_byte_for_byte(encoder, case):
    before = encoded("columns"), encoded("rows")
    got, want = reply_and_reference(CASES[case])
    assert got == want
    assert (encoded("columns"), encoded("rows")) == (before[0] + 1, before[1])
    json.loads(got)


def test_non_ascii_column_names_ride_in_the_envelope(encoder):
    got, want = reply_and_reference(
        [np.array([1.0]), np.array(["v"], dtype=object)],
        names=['na"me é', "rows"])
    assert got == want


def no_symbol(monkeypatch):
    if native.lib() is not None:
        monkeypatch.setattr(native.lib(), "_gt_no_json", True, raising=False)


def no_library(monkeypatch):
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", True)


FALLBACKS = {
    # name: (columns, what to break first)
    "object_of_numbers": ([np.array([1, 2.5, None], dtype=object)], None),
    "object_text_and_number": ([np.array(["a", 1], dtype=object)], None),
    "object_text_and_bool": ([np.array(["a", True], dtype=object)], None),
    "lone_surrogate": ([np.array(["ok", "\ud800"], dtype=object)], None),
    "unknown_dtype": ([np.array([1.0, 2.0], dtype=np.longdouble)], None),
    "library_without_symbol": (MIXED, no_symbol),
    "no_library": (MIXED, no_library),
}


@pytest.mark.parametrize("case", FALLBACKS)
def test_what_the_encoder_does_not_know_takes_json_dumps(monkeypatch, case):
    columns, break_it = FALLBACKS[case]
    if break_it is not None:
        break_it(monkeypatch)
    assert native.json_rows(columns) is None
    if columns[0].dtype == np.longdouble:
        columns = [columns[0].astype(np.float64)]  # json.dumps has no such
        monkeypatch.setattr(native, "json_rows", lambda cols: None)
    before = encoded("columns"), encoded("rows")
    got, want = reply_and_reference(columns)
    assert got == want
    assert (encoded("columns"), encoded("rows")) == (before[0], before[1] + 1)


@pytest.mark.parametrize("res", [
    QueryResult([], [], affected_rows=3),
    QueryResult(["Tables"], [["t"], ["u"]]),
    QueryResult(["a"], []),
], ids=["affectedrows", "rows_given", "rows_given_empty"])
def test_a_result_without_columns_takes_json_dumps(res):
    before = encoded("columns"), encoded("rows")
    body = http._result_to_json(res, time.perf_counter())
    want = json.dumps(body).encode()
    assert http._json_reply(body, ROUTE).body == want
    assert (encoded("columns"), encoded("rows")) == (before[0], before[1] + 1)


def test_no_columns_is_none_for_the_wrapper():
    assert native.json_rows([]) is None


def test_a_list_put_in_the_views_place_reaches_the_client(encoder):
    """benchmark/tests/test_faults.py alters a reply so: deepcopy, row 0
    through list(), the rest through [1:]."""
    res = QueryResult(["h", "v"], columns=ColumnRows(
        [np.array(["a", "b", "c"], dtype=object), np.array([1.0, 2.0, 3.0])]))
    body = copy.deepcopy(http._result_to_json(res, time.perf_counter()))
    records = body["output"][0]["records"]
    assert records["rows"]
    row = list(records["rows"][0])
    row[-1] = row[-1] * 1.001 + 1e-3
    records["rows"] = [row] + list(records["rows"][1:])
    before = encoded("rows")
    sent = json.loads(http._json_reply(body, ROUTE).body)
    assert sent["output"][0]["records"]["rows"] == [
        ["a", 1.0 * 1.001 + 1e-3], ["b", 2.0], ["c", 3.0]]
    assert encoded("rows") == before + 1


# ---- QueryResult: rows on first read, columns until then -----------------

def column_result():
    return QueryResult(["h", "n", "v"], column_types=["String"] * 3,
                       columns=ColumnRows([
                           np.array(["a", None, np.str_("c")], dtype=object),
                           np.array([1, 2, 3]),
                           np.array([1.5, np.nan, 3.0], dtype=np.float32)]))


def test_rows_are_built_on_first_read_and_kept():
    res = column_result()
    assert res.num_rows == 3 and len(res.columns) == 3
    assert repr(res) == "QueryResult[3 rows x 3 cols]"
    assert res.columns is not None
    rows = res.rows
    assert rows == [["a", 1, 1.5], [None, 2, None], ["c", 3, 3.0]]
    assert [type(v) for v in rows[2]] == [str, int, float]
    assert res.rows is rows and res.columns is None and res.num_rows == 3
    rows[:] = rows[:1]
    assert res.num_rows == 1


@pytest.mark.parametrize("make", [
    lambda: QueryResult(["a"], [[1], [2]]),
    lambda: QueryResult(["a"], rows=[[1], [2]], affected_rows=0,
                        column_types=["Int64"]),
    lambda: QueryResult(["a"], columns=ColumnRows([np.array([1, 2])])),
], ids=["positional", "keywords", "columns"])
def test_every_way_to_make_a_result_reads_the_same(make):
    res = make()
    assert res.num_rows == 2 and res.rows == [[1], [2]]
    assert res.to_pydict() == {"a": [1, 2]}
    assert res == QueryResult(["a"], [[1], [2]],
                              column_types=res.column_types)
    res.rows = [[9]]
    assert res.rows == [[9]] and res.num_rows == 1 and res.columns is None
    assert QueryResult([]).rows == [] and QueryResult([], None).num_rows == 0


def test_column_rows_reads_as_the_list_of_rows():
    view = column_result().columns
    rows = view.to_rows()
    assert view and len(view) == 3 and list(view) == rows
    assert [view[i] for i in (0, 1, 2, -1)] == [rows[0], rows[1], rows[2],
                                                rows[-1]]
    assert list(view[1:]) == rows[1:] and isinstance(view[1:], ColumnRows)
    assert list(copy.deepcopy(view)) == rows
    with pytest.raises(IndexError):
        view[3]
    assert not ColumnRows([]) and ColumnRows([]).to_rows() == []
    assert not ColumnRows([np.array([])])


# ---- the served path ------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from greptimedb_tpu.standalone import GreptimeDB

    db = GreptimeDB(str(tmp_path_factory.mktemp("reply")))
    db.sql("CREATE TABLE m (host STRING, ts TIMESTAMP TIME INDEX, "
           "v DOUBLE, n BIGINT, PRIMARY KEY(host))")
    db.sql("INSERT INTO m VALUES ('a', 1000, 1.5, 1), ('a', 2000, 2.5, 2), "
           "('b\"é', 1000, NULL, 3), ('b\"é', 2000, 8.0, NULL)")
    srv = http.HttpServer(db, host="127.0.0.1", port=0)
    srv.start()

    def sql(q):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/sql",
                urllib.parse.urlencode({"sql": q}).encode()) as r:
            return r.headers, r.read()

    yield types.SimpleNamespace(db=db, sql=sql)
    srv.stop()
    db.close()


GROUP_BY = ("SELECT host, avg(v), max(n), count(*) FROM m GROUP BY host "
            "ORDER BY host")
GROUP_BY_ROWS = [["a", 2.0, 2, 2], ["b\"é", 8.0, 3, 2]]


def test_served_group_by_never_builds_rows(encoder, served, monkeypatch):
    seen = []
    real = http._result_to_json

    def watch(res, t0):
        seen.append(res)
        return real(res, t0)

    monkeypatch.setattr(http, "_result_to_json", watch)
    before = encoded("columns"), encoded("rows")
    headers, reply = served.sql(GROUP_BY)
    assert (encoded("columns"), encoded("rows")) == (before[0] + 1, before[1])
    assert len(seen) == 1 and seen[0].columns is not None  # rows never built
    assert headers["Content-Type"] == "application/json; charset=utf-8"
    records = json.loads(reply)["output"][0]["records"]
    assert records["rows"] == GROUP_BY_ROWS and records["total_rows"] == 2
    # and the bytes are json.dumps' own
    body = real(seen[0], time.perf_counter())
    body["execution_time_ms"] = json.loads(reply)["execution_time_ms"]
    body["output"][0]["records"]["rows"] = seen[0].rows
    assert reply == json.dumps(body).encode()


@pytest.mark.parametrize("q, rows", [
    (GROUP_BY, GROUP_BY_ROWS),
    ("SELECT host, ts, v, n FROM m ORDER BY host, ts",
     [["a", 1000, 1.5, 1], ["a", 2000, 2.5, 2],
      ["b\"é", 1000, None, 3], ["b\"é", 2000, 8.0, 0]]),
    ("SELECT DISTINCT host FROM m ORDER BY host DESC LIMIT 1",
     [["b\"é"]]),
    ("SELECT host, count(*) FROM m GROUP BY host HAVING count(*) > 5", []),
    ("SELECT count(*), 'lit' FROM m", [[4, "lit"]]),
], ids=["group_by", "raw_rows", "distinct_limit", "having_none", "literal"])
def test_rows_of_a_select_are_what_they_were(served, q, rows):
    res = served.db.sql(q)
    assert res.columns is not None and res.num_rows == len(rows)
    assert res.rows == rows
    for got, want in zip(res.rows, rows):
        assert [type(v) for v in got] == [type(v) for v in want]
    assert json.loads(served.sql(q)[1])["output"][0]["records"]["rows"] == rows


# ---- the Prometheus payloads ---------------------------------------------

def old_fmt_val(v):
    if np.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    return repr(float(v))


def old_instant_payload(res, steps):
    """promql/format.py before PR 28: a numpy scalar a point."""
    vals = np.asarray(res.values, dtype=np.float64)
    result = []
    for s, lab in enumerate(res.labels):
        v = vals[s, -1]
        if not np.isnan(v):
            result.append({
                "metric": {k: str(x) for k, x in lab.items()},
                "value": [steps[-1] / 1000.0, old_fmt_val(v)],
            })
    return {"status": "success",
            "data": {"resultType": "vector", "result": result}}


def old_range_payload(res, steps):
    vals = np.asarray(res.values, dtype=np.float64)
    result = []
    for s, lab in enumerate(res.labels):
        pts = [
            [steps[t] / 1000.0, old_fmt_val(vals[s, t])]
            for t in range(len(steps))
            if not np.isnan(vals[s, t])
        ]
        if pts:
            result.append({"metric": {k: str(v) for k, v in lab.items()},
                           "values": pts})
    return {"status": "success",
            "data": {"resultType": "matrix", "result": result}}


def matrix(dtype):
    vals = (np.random.default_rng(5).random((6, 61)) * 3).astype(dtype)
    vals[0, 3] = np.nan
    vals[1, :] = np.nan               # a series with no points at all
    vals[2, 0], vals[2, -1] = np.inf, -np.inf
    vals[3, -1] = np.nan              # no sample at the instant
    vals[4, -1] = np.inf
    labels = [{"instance": f"node{i}", "cpu": i} for i in range(6)]
    steps = 1700000000123 + 60000 * np.arange(61, dtype=np.int64)
    return types.SimpleNamespace(values=vals, labels=labels), steps


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("name, old", [
    ("range_payload", old_range_payload),
    ("instant_payload", old_instant_payload)])
def test_payload_equals_the_point_by_point_form(name, old, dtype):
    res, steps = matrix(dtype)
    build, want = getattr(prom_format, name), old(res, steps)
    assert prom_format.payload_body(build(res, steps))[0] == json.dumps(
        want).encode()
    got = build(res, steps)
    assert got == want
    assert prom_format.payload_body(got) == (json.dumps(want).encode(), "rows")
    assert len(got["data"]["result"]) == (5 if name == "range_payload" else 4)
    # a list of steps, and values wider than labels x steps (padding)
    wide = types.SimpleNamespace(
        values=np.pad(res.values, ((0, 2), (0, 0)), constant_values=1.0),
        labels=res.labels)
    assert build(wide, steps.tolist()) == want


def series(values, labels=None, steps=None):
    values = np.atleast_2d(np.asarray(values))
    if labels is None:
        labels = [{"instance": f"node{i}"} for i in range(len(values))]
    if steps is None:
        steps = 1700000000000 + 60000 * np.arange(values.shape[1],
                                                  dtype=np.int64)
    return types.SimpleNamespace(values=values, labels=labels), steps


EDGES = [-0.0, 5e-324, 1e16, 1e-5, 3.0, 9999999999999998.0, 0.1, 1 / 3]
MATRICES = {
    "float32": lambda: matrix(np.float32),
    "float64": lambda: matrix(np.float64),
    "nan_gaps": lambda: series([[np.nan, 1.5, np.nan, 2.5, np.nan],
                                [0.5, np.nan, np.nan, np.nan, 1.0]]),
    "all_nan_series_dropped": lambda: series(
        [[1.0, 2.0], [np.nan, np.nan], [3.0, 4.0], [np.nan, np.nan]]),
    "every_series_all_nan": lambda: series(np.full((3, 4), np.nan)),
    "infinities": lambda: series([[np.inf, -np.inf, 1.0],
                                  [np.nan, np.inf, np.nan]]),
    "negative_zero": lambda: series([[-0.0, 0.0]]),
    "subnormal": lambda: series([[5e-324, 2.2250738585072014e-308]]),
    "exponent_forms": lambda: series([[1e16, 1e-5, 9.999e-5, 1e-4, 1e15,
                                       1e22, 1.5e-100, -1.7976931348623157e308]]),
    "whole_value": lambda: series([[3.0, 100.0, -7.0]]),
    "edges_float32": lambda: series(np.array([EDGES], dtype=np.float32)),
    "random_float64": lambda: series(
        np.random.default_rng(33).standard_normal((7, 40))
        * 10.0 ** np.random.default_rng(34).integers(-30, 30, (7, 40))),
    "fractional_second_steps": lambda: series(
        [[1.0, 2.0, 3.0]], steps=np.array([1700000000001, 1700000000250,
                                           1700000000999])),
    "steps_before_1970_and_zero": lambda: series(
        [[1.0, 2.0, 3.0]], steps=[-1500, 0, 10]),
    "steps_as_a_list": lambda: series(
        [[1.0, 2.0]], steps=[1700000000000, 1700000060000]),
    "wide": lambda: (lambda res, steps: (types.SimpleNamespace(
        values=np.pad(res.values, ((0, 3), (0, 5)), constant_values=1.0),
        labels=res.labels), steps))(*matrix(np.float32)),
    "strided_columns": lambda: series(
        np.arange(24, dtype=np.float64).reshape(3, 8)[:, ::2]),
    "transposed": lambda: series(
        np.arange(12, dtype=np.float64).reshape(4, 3).T),
    "no_series": lambda: series(np.zeros((0, 3))),
    "one_step": lambda: series([[1.5], [np.nan], [2.5]]),
    "one_series_one_point": lambda: series([[0.25]]),
    "label_with_a_quote": lambda: series(
        [[1.0]], labels=[{"path": 'say "hi"', 'k"ey': "v"}]),
    "label_with_a_backslash": lambda: series(
        [[1.0]], labels=[{"path": "C:\\temp\\n", "tab": "a\tb\n"}]),
    "label_non_ascii": lambda: series(
        [[1.0], [2.0]], labels=[{"city": "Zürich 中 \U0001f600"},
                                {"city": "café"}]),
    "integer_label_value": lambda: series(
        [[1.0], [2.0]], labels=[{"cpu": 3, "up": True}, {"cpu": None}]),
    "no_labels": lambda: series([[1.0]], labels=[{}]),
    "many_labels": lambda: series(
        [[1.0, 2.0]], labels=[{f"l{i}": "x" * i for i in range(12)}]),
}


@pytest.mark.parametrize("case", MATRICES)
def test_native_matrix_body_is_json_dumps_byte_for_byte(encoder, case):
    res, steps = MATRICES[case]()
    want = json.dumps(old_range_payload(res, steps)).encode()
    payload = prom_format.range_payload(res, steps)
    assert prom_format.payload_body(payload) == (want, "columns")
    # encoding read nothing through: the holder is as it was, and reads
    # as the point-by-point list
    result = payload["data"]["result"]
    assert result.values is not None and result.encode() is not None
    assert list(result) == old_range_payload(res, steps)["data"]["result"]
    assert prom_format.payload_body(payload) == (want, "rows")
    json.loads(want)


def test_matrix_series_becomes_its_list_at_the_first_read():
    res, steps = matrix(np.float64)
    want = old_range_payload(res, steps)["data"]["result"]
    result = prom_format.range_payload(res, steps)["data"]["result"]
    assert result.to_list() == want and result.values is not None
    held = copy.deepcopy(result)
    assert held.values is not result.values and held.values is not None
    assert len(result) == 5 and result.values is None  # read: now the list
    assert result.encode() is None
    assert result[0] is result[0] and result[-1] == want[-1]
    assert result[1:3] == want[1:3] and result == want and want == result
    assert [s["metric"] for s in result] == [s["metric"] for s in want]
    result[0]["values"][0] = "kept"
    assert result[0]["values"][0] == "kept" and list(held) == want
    assert held == copy.deepcopy(held) != result
    with pytest.raises(IndexError):
        result[5]
    with pytest.raises(TypeError):
        hash(result)
    none = prom_format.range_payload(*series(np.zeros((0, 2))))
    assert not none["data"]["result"] and list(none["data"]["result"]) == []


class LazyLabels(collections.abc.Sequence):
    """Labels as promql/engine.py hands them over in a fused aggregation:
    decoded a series on demand, over state that cannot be copied."""

    def __init__(self, labels):
        self.labels, self.device_state = labels, threading.Lock()

    def __len__(self):
        return len(self.labels)

    def __getitem__(self, i):
        return dict(self.labels[i])


def test_the_holder_survives_deepcopy_over_lazy_labels(encoder):
    res, steps = matrix(np.float32)
    want = json.dumps(old_range_payload(res, steps)).encode()
    res.labels = LazyLabels(res.labels)
    with pytest.raises(TypeError):
        copy.deepcopy(res.labels)
    payload = copy.deepcopy(prom_format.range_payload(res, steps))
    assert prom_format.payload_body(payload) == (want, "columns")
    assert prom_format.payload_body(
        prom_format.instant_payload(res, steps))[1] == "rows"


def no_matrix_symbol(monkeypatch):
    if native.lib() is not None:
        monkeypatch.setattr(native.lib(), "_gt_no_matrix", True, raising=False)


def read_through(payload):
    assert payload["data"]["result"][0]["values"]


def not_a_holder(payload):
    payload["data"]["result"] = list(payload["data"]["result"])


def nan_step(payload):
    payload["data"]["result"].step_seconds[0] = np.nan  # json.dumps: NaN


PROM_FALLBACKS = {
    # name: (payload builder, what to do first: to the world, to the payload)
    "library_without_symbol": ("range_payload", no_matrix_symbol, None),
    "no_library": ("range_payload", no_library, None),
    "result_read_through": ("range_payload", None, read_through),
    "result_is_a_list": ("range_payload", None, not_a_holder),
    "step_not_finite": ("range_payload", None, nan_step),
    "instant_vector": ("instant_payload", None, None),
}


@pytest.mark.parametrize("case", PROM_FALLBACKS)
def test_what_the_matrix_encoder_does_not_take_is_json_dumps(
        monkeypatch, case):
    name, break_it, touch = PROM_FALLBACKS[case]
    res, steps = matrix(np.float32)
    payload = getattr(prom_format, name)(res, steps)
    if break_it is not None:
        break_it(monkeypatch)
    if touch is not None:
        touch(payload)
    reference = copy.deepcopy(payload)
    reference["data"]["result"] = list(reference["data"]["result"])
    if case != "step_not_finite":
        assert reference == globals()[f"old_{name}"](res, steps)
    before = encoded("columns", RANGE_ROUTE), encoded("rows", RANGE_ROUTE)
    resp = http._prom_reply(payload, RANGE_ROUTE, {"x-greptime-trace-id": "t"})
    assert resp.body == json.dumps(reference).encode()
    assert resp.headers["x-greptime-trace-id"] == "t"
    assert resp.headers["Content-Type"] == "application/json; charset=utf-8"
    assert (encoded("columns", RANGE_ROUTE), encoded("rows", RANGE_ROUTE)
            ) == (before[0], before[1] + 1)


def test_an_error_payload_and_an_altered_envelope(encoder):
    error = {"status": "error", "errorType": "bad_data", "error": "é"}
    assert prom_format.payload_body(error) == (json.dumps(error).encode(),
                                               "rows")
    res, steps = matrix(np.float32)
    payload = prom_format.range_payload(res, steps)
    payload["warnings"] = ['a "w"']  # the envelope is json.dumps' own
    want = dict(old_range_payload(res, steps), warnings=['a "w"'])
    assert prom_format.payload_body(payload) == (json.dumps(want).encode(),
                                                 "columns")
    # the slot's text inside the payload a second time: json.dumps
    payload = prom_format.range_payload(res, steps)
    payload["warnings"] = [native._SLOT]
    want["warnings"] = [native._SLOT]
    assert prom_format.payload_body(payload) == (json.dumps(want).encode(),
                                                 "rows")


def test_a_point_written_through_the_holder_reaches_the_client(encoder):
    """benchmark/tests/test_faults.py alters a reply so: deepcopy, then a
    write into the first series' first point."""
    res, steps = matrix(np.float32)
    body = copy.deepcopy(prom_format.range_payload(res, steps))
    result = body.get("data", {}).get("result")
    assert result
    t, v = result[0]["values"][0]
    result[0]["values"][0] = [t, repr(float(v) * 1.001 + 1e-3)]
    before = encoded("columns", RANGE_ROUTE), encoded("rows", RANGE_ROUTE)
    sent = json.loads(http._prom_reply(body, RANGE_ROUTE).body)
    want = old_range_payload(res, steps)
    assert sent["data"]["result"][0]["values"][0] == [
        t, repr(float(want["data"]["result"][0]["values"][0][1]) * 1.001
                + 1e-3)]
    assert sent["data"]["result"][0]["values"][1:] == (
        want["data"]["result"][0]["values"][1:])
    assert sent["data"]["result"][1:] == want["data"]["result"][1:]
    assert (encoded("columns", RANGE_ROUTE), encoded("rows", RANGE_ROUTE)
            ) == (before[0], before[1] + 1)


@pytest.fixture(scope="module")
def served_prom():
    """No data home, as tests/test_stage_boundary.py serves PromQL: a
    program first built through a home's artifact store keeps another
    name in this process, which that file's profiler test reads."""
    from greptimedb_tpu.standalone import GreptimeDB

    db = GreptimeDB()
    db.sql("CREATE TABLE cpu (h STRING, ts TIMESTAMP(3) TIME INDEX, "
           "v DOUBLE, PRIMARY KEY (h))")
    db.sql("INSERT INTO cpu VALUES " + ",".join(
        f"('h\"{i % 4}é', {1000 * i}, {float(i)})" for i in range(200)))
    srv = http.HttpServer(db, host="127.0.0.1", port=0)
    srv.start()

    def query_range(query="sum by (h) (rate(cpu[20s]))"):
        q = urllib.parse.urlencode(
            {"query": query, "start": "20", "end": "180", "step": "10"})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}{RANGE_ROUTE}?{q}") as r:
            return r.headers, r.read()

    yield types.SimpleNamespace(db=db, query_range=query_range, port=srv.port)
    srv.stop()
    db.close()


def test_served_query_range_is_written_from_arrays(encoder, served_prom,
                                                   monkeypatch):
    seen = []
    real = prom_format.range_payload

    def watch(res, steps):
        seen.append(real(res, steps))
        return seen[-1]

    monkeypatch.setattr(prom_format, "range_payload", watch)
    before = encoded("columns", RANGE_ROUTE), encoded("rows", RANGE_ROUTE)
    headers, reply = served_prom.query_range()
    assert (encoded("columns", RANGE_ROUTE), encoded("rows", RANGE_ROUTE)
            ) == (before[0] + 1, before[1])
    assert len(seen) == 1
    result = seen[0]["data"]["result"]
    assert result.values is not None  # no point was built
    assert headers["Content-Type"] == "application/json; charset=utf-8"
    # the bytes are json.dumps' own over the point-by-point form
    seen[0]["data"]["result"] = result.to_list()
    assert reply == json.dumps(seen[0]).encode()
    sent = json.loads(reply)["data"]["result"]
    assert len(sent) == 4 and len(sent[0]["values"]) == 17
    assert sorted(s["metric"]["h"] for s in sent) == [
        f'h"{i}é' for i in range(4)]
    # the same request without the symbol: the same bytes, by json.dumps
    no_matrix_symbol(monkeypatch)
    assert served_prom.query_range()[1] == reply
    assert (encoded("columns", RANGE_ROUTE), encoded("rows", RANGE_ROUTE)
            ) == (before[0] + 1, before[1] + 1)


def test_served_instant_query_takes_json_dumps(served_prom):
    route = "/v1/prometheus/api/v1/query"
    before = encoded("columns", route), encoded("rows", route)
    q = urllib.parse.urlencode({"query": "cpu", "time": "100"})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{served_prom.port}{route}?{q}") as r:
        out = json.loads(r.read())
    assert out["data"]["resultType"] == "vector" and len(
        out["data"]["result"]) == 4
    assert (encoded("columns", route), encoded("rows", route)) == (
        before[0], before[1] + 1)


def test_the_grpc_gateway_answers_the_same_bytes(encoder, served_prom):
    import pyarrow.flight as fl

    from greptimedb_tpu.rpc.promgateway import PromGatewayServer

    query = "sum by (h) (rate(cpu[20s]))"
    srv = PromGatewayServer(served_prom.db)
    threading.Thread(target=srv.serve, daemon=True).start()
    client = fl.connect(f"grpc://{srv.address}")
    try:
        def ask(**req):
            (out,) = client.do_action(
                fl.Action("prom_query", json.dumps(req).encode()))
            return out.body.to_pybytes()

        assert ask(query=query, start=20, end=180, step=10) == (
            served_prom.query_range(query)[1])
        vector = json.loads(ask(query="cpu", time=100))
        assert vector["data"]["resultType"] == "vector"
        assert json.loads(ask(query="cpu{{{"))["status"] == "error"
    finally:
        client.close()
        srv.shutdown()


def test_fmt_val():
    assert [prom_format.fmt_val(v) for v in (math.inf, -math.inf, 0.5, 1e16)
            ] == ["+Inf", "-Inf", "0.5", "1e+16"]


# ---- the benchmark's reader of the counter --------------------------------

def layer_reader(name):
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def key(route, road):
    return f'{COUNTER}{{route="{route}",encoder="{road}"}}'


def test_reply_columnar_pct_reads_the_counter():
    reader = layer_reader("reply_columnar_pct")
    before = {key(ROUTE, "columns"): 2.0, key(ROUTE, "rows"): 5.0, "x": 1.0}
    after = {key(ROUTE, "columns"): 8.0, key(ROUTE, "rows"): 7.0,
             key("/v1/logs", "rows"): 0.0, "x": 9.0}
    assert reader.read({"metrics_before": before,
                        "metrics_after": after}) == 75.0
    all_columns = dict(after, **{key(ROUTE, "columns"): 10.0,
                                 key(ROUTE, "rows"): 5.0})
    assert reader.read({"metrics_before": before,
                        "metrics_after": all_columns}) == 100.0
    # no reply in the window, and a program without the counter
    assert reader.read({"metrics_before": after,
                        "metrics_after": after}) is None
    assert reader.read({"metrics_before": {"x": 1.0},
                        "metrics_after": {"x": 2.0}}) is None


def test_prom_reply_native_pct_reads_the_promql_routes():
    reader = layer_reader("prom_reply_native_pct")
    instant = "/v1/prometheus/api/v1/query"
    before = {key(RANGE_ROUTE, "columns"): 4.0, key(RANGE_ROUTE, "rows"): 8.0,
              key(ROUTE, "columns"): 1.0, "x": 1.0}
    after = {key(RANGE_ROUTE, "columns"): 10.0, key(RANGE_ROUTE, "rows"): 9.0,
             key(instant, "rows"): 1.0, key(ROUTE, "columns"): 50.0,
             key(ROUTE, "rows"): 7.0, "x": 9.0}
    assert reader.read({"metrics_before": before,
                        "metrics_after": after}) == 75.0
    every = dict(before, **{key(RANGE_ROUTE, "columns"): 3004.0})
    assert reader.read({"metrics_before": before,
                        "metrics_after": every}) == 100.0
    # only /v1/sql moved; no PromQL reply in the window; and a program
    # that does not count its PromQL replies (the parent of PR 33)
    sql_only = dict(before, **{key(ROUTE, "columns"): 9.0})
    assert reader.read({"metrics_before": before,
                        "metrics_after": sql_only}) is None
    assert reader.read({"metrics_before": after,
                        "metrics_after": after}) is None
    assert reader.read({"metrics_before": {key(ROUTE, "rows"): 1.0},
                        "metrics_after": {key(ROUTE, "rows"): 5.0}}) is None


def test_prom_reply_native_pct_is_declared_for_the_promql_cell():
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "prom_reply_native_pct")
    assert entry == {"name": "prom_reply_native_pct", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "wire + server", "moves": "qps",
                     "workloads": ["node64.cpu_rate"]}
    assert callable(layer_reader(entry["name"]).read)
