"""``search_rounds_per_query`` on written pairs of ``/metrics`` dicts: the
quotient by hand, a program without the counter (the parent commit:
``None``, the line leaves the metric out), a window that dispatched no
window program and a window without requests (``None``)."""

import run

ROUNDS = "greptime_promql_search_rounds_total"
ROWS = "greptime_promql_window_rows_total"


def read(before, after, requests=4):
    ctx = {"metrics_before": before, "metrics_after": after,
           "log": [{"req": {}}] * requests}
    return run.load_module("layer_metrics", "search_rounds_per_query").read(
        ctx)


def test_quotient_by_hand():
    before = {ROUNDS: 10.0, ROWS: 1000.0}
    assert read(before, {ROUNDS: 30.0, ROWS: 5000.0}) == 5.0
    # every run under one chunk: the count alone, no scalar round: 0
    assert read(before, {ROUNDS: 10.0, ROWS: 5000.0}) == 0.0
    # a counter that first appears inside the window
    assert read({ROWS: 0.0}, {ROUNDS: 12.0, ROWS: 64.0}) == 3.0


def test_nothing_to_read():
    assert read({ROWS: 1.0}, {ROWS: 9.0}) is None           # no counter
    same = {ROUNDS: 8.0, ROWS: 9.0}
    assert read(same, dict(same)) is None                   # no dispatch
    assert read({}, {ROUNDS: 8.0, ROWS: 9.0}, requests=0) is None


def test_entry_names_the_promql_cells():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entry, = (m for m in bench["per_layer"]
              if m["name"] == "search_rounds_per_query")
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "kernels" and entry["moves"] == "qps"
    assert entry["workloads"] == ["node64.cpu_rate", "k8s100k.namespace_cpu",
                                  "k8snet120k.namespace_bandwidth"]
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
