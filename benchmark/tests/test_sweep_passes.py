"""``sweep_passes_per_query`` on written pairs of ``/metrics`` dicts: the
quotient by hand, a program without the counter (the parent commit:
``None``, the line leaves the metric out), a window that dispatched no
window program and a window without requests (``None``)."""

import run

PASSES = "greptime_promql_sweep_passes_total"
ROWS = "greptime_promql_window_rows_total"


def read(before, after, requests=4):
    ctx = {"metrics_before": before, "metrics_after": after,
           "log": [{"req": {}}] * requests}
    return run.load_module("layer_metrics", "sweep_passes_per_query").read(
        ctx)


def test_quotient_by_hand():
    before = {PASSES: 10.0, ROWS: 1000.0}
    assert read(before, {PASSES: 18.0, ROWS: 5000.0}) == 2.0
    # a searched slab dispatches and sweeps nothing: 0, not None
    assert read(before, {PASSES: 10.0, ROWS: 5000.0}) == 0.0
    # a counter that first appears inside the window
    assert read({ROWS: 0.0}, {PASSES: 12.0, ROWS: 64.0}) == 3.0


def test_nothing_to_read():
    assert read({ROWS: 1.0}, {ROWS: 9.0}) is None           # no counter
    same = {PASSES: 8.0, ROWS: 9.0}
    assert read(same, dict(same)) is None                   # no dispatch
    assert read({}, {PASSES: 8.0, ROWS: 9.0}, requests=0) is None


def test_entry_names_the_promql_cells():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = bench["per_layer"][-1]
    assert entry["name"] == "sweep_passes_per_query"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "kernels" and entry["moves"] == "qps"
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
