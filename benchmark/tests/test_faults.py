"""Drives a whole run (all but the look for a chip) at rehearsal size
with the timed path broken underneath, and sees ``correct`` come out
false; the same run unbroken comes out true.  The faults these cells can
have: an answer altered where it is produced, and an acknowledged write
that is not there."""

import copy

import pytest
import run

CELLS = [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]]


def _run(workload):
    return run.run_cell(workload, seed=7, seconds=1.0, trace=False,
                        rehearse=True, need_tpu=False)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = _run(workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def _alter_sql(body: dict) -> None:
    """The last value of the first row, moved by a thousandth."""
    records = body.get("output", [{}])[0].get("records")
    if records and records["rows"]:
        row = list(records["rows"][0])
        row[-1] = row[-1] * 1.001 + 1e-3
        records["rows"] = [row] + list(records["rows"][1:])


def _alter_prom(body: dict) -> None:
    """The first point of the first series, moved by a thousandth."""
    result = body.get("data", {}).get("result")
    if result:
        t, v = result[0]["values"][0]
        result[0]["values"][0] = [t, repr(float(v) * 1.001 + 1e-3)]


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(workload, monkeypatch):
    """Every reply after the warm-up carries one value moved by 0.1 %,
    altered where the server builds the reply."""
    from greptimedb_tpu.promql import format as prom_format
    from greptimedb_tpu.servers import http

    calls = {"n": 0}
    warm = 5  # step 6 sends 3 or 8 requests: break inside it or just after

    def altered(real, alter):
        def build(*a, **kw):
            out = copy.deepcopy(real(*a, **kw))
            calls["n"] += 1
            if calls["n"] > warm:
                alter(out)
            return out
        return build

    monkeypatch.setattr(http, "_result_to_json",
                        altered(http._result_to_json, _alter_sql))
    monkeypatch.setattr(prom_format, "range_payload",
                        altered(prom_format.range_payload, _alter_prom))
    res = _run(workload)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
    over = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert any(k.startswith("max_err.") for k in over), res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_lost_write_is_not_correct(workload, monkeypatch):
    """The server acknowledges a body's rows and writes none of them."""
    from greptimedb_tpu.servers import http

    def lossy(db, table, cols, *a, **kw):
        return len(cols["ts"])

    monkeypatch.setattr(http, "_ingest_columns", lossy)
    res = _run(workload)
    assert not res["correct"], res["checks"]
    assert res["checks"]["rows_not_counted_back"]["value"] > 0
