"""Scalar gather rounds of the first-sample search that the window programs
of one request emit: delta of ``greptime_promql_search_rounds_total``
(counted at a program's dispatch beside ``greptime_promql_window_rows_total``:
a host integer off the program's static class, promql/engine.py
``search_rounds``, the helper the program's own source takes its rounds
from) between the run's two ``GET /metrics``, over the window's requests.
Each round gathers one scalar a matched series from each of the
timestamps' two words; the search's last log2(128) rounds are one count
over two gathered 128-row chunks, so a layout whose runs are all under
128 samples reads 0, and 2,880 samples a series read 5 (12 bits less 7).
A search of every round would read the runs' bits (7 and 12).  A program
without the counter, or a window that dispatched no program, gives None."""

ROUNDS = "greptime_promql_search_rounds_total"
ROWS = "greptime_promql_window_rows_total"


def read(ctx):
    after, before = ctx["metrics_after"], ctx["metrics_before"]
    if ROUNDS not in after or not ctx["log"]:
        return None
    if after.get(ROWS, 0.0) - before.get(ROWS, 0.0) <= 0:
        return None
    return (after[ROUNDS] - before.get(ROUNDS, 0.0)) / len(ctx["log"])
