"""Prometheus API response payloads — the ONE formatting definition,
shared by the HTTP API (servers/http.py) and the gRPC PromQL gateway
(rpc/promgateway.py; reference src/servers/src/grpc/prom_query_gateway.rs
reuses the HTTP handlers' types the same way)."""

from __future__ import annotations

import math

import numpy as np


def fmt_val(v: float) -> str:
    """Prometheus' text of one sample, from a Python float."""
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    return repr(v)


# Both payloads work on whole arrays: one test over the matrix for what is
# not finite, one ``tolist()``, the step timestamps divided once.  A
# request of 64 series x 61 steps is 3,904 points, and a numpy scalar a
# point costs several times the point's text.

def instant_payload(res, steps) -> dict:
    last = np.asarray(res.values, dtype=np.float64)[:len(res.labels), -1]
    t = float(steps[-1]) / 1000.0
    result = [
        {"metric": {k: str(x) for k, x in lab.items()},
         "value": [t, fmt_val(v)]}
        for lab, v in zip(res.labels, last.tolist())
        if v == v  # NaN is no sample
    ]
    return {"status": "success",
            "data": {"resultType": "vector", "result": result}}


def range_payload(res, steps) -> dict:
    vals = np.asarray(res.values, dtype=np.float64)[
        :len(res.labels), :len(steps)]
    ts = (np.asarray(steps, dtype=np.float64) / 1000.0).tolist()
    odd = (~np.isfinite(vals)).any(axis=1).tolist()  # NaN or an infinity
    result = []
    for lab, row, has_odd in zip(res.labels, vals.tolist(), odd):
        if has_odd:
            pts = [[t, fmt_val(v)] for t, v in zip(ts, row) if v == v]
        else:
            pts = [[t, repr(v)] for t, v in zip(ts, row)]
        if pts:
            result.append({"metric": {k: str(v) for k, v in lab.items()},
                           "values": pts})
    return {"status": "success",
            "data": {"resultType": "matrix", "result": result}}


def evaluate(db, query: str, start_s: float, end_s: float,
             step_s: float, lookback_s: float | None = None) -> dict:
    """Parse + evaluate + format in one call (instant when start == end)."""
    from greptimedb_tpu.promql.engine import (
        DEFAULT_LOOKBACK_S, PromEvaluator,
    )
    from greptimedb_tpu.promql.parser import parse_promql

    expr = parse_promql(query)
    ev = PromEvaluator(db, start_s, end_s, step_s,
                       lookback_s or DEFAULT_LOOKBACK_S)
    res = ev.eval(expr)
    steps = ev.steps_ms()
    if start_s == end_s:
        return instant_payload(res, steps)
    return range_payload(res, steps)
