"""Prometheus API response payloads — the ONE formatting definition,
shared by the HTTP API (servers/http.py) and the gRPC PromQL gateway
(rpc/promgateway.py; reference src/servers/src/grpc/prom_query_gateway.rs
reuses the HTTP handlers' types the same way)."""

from __future__ import annotations

import json
import math
from collections.abc import Sequence

import numpy as np

from greptimedb_tpu import native


def _metric(labels: dict) -> dict:
    return {k: str(v) for k, v in labels.items()}


def fmt_val(v: float) -> str:
    """Prometheus' text of one sample, from a Python float."""
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    return repr(v)


# Both payloads work on whole arrays, the step timestamps divided once: a
# request of 64 series x 61 steps is 3,904 points, a numpy scalar a point
# costs several times the point's text, and a Python list a point several
# times what the native encoder takes for it (MatrixSeries).

def instant_payload(res, steps) -> dict:
    last = np.asarray(res.values, dtype=np.float64)[:len(res.labels), -1]
    t = float(steps[-1]) / 1000.0
    result = [
        {"metric": _metric(lab), "value": [t, fmt_val(v)]}
        for lab, v in zip(res.labels, last.tolist())
        if v == v  # NaN is no sample
    ]
    return {"status": "success",
            "data": {"resultType": "vector", "result": result}}


class MatrixSeries(Sequence):
    """A matrix reply's ``result`` as whole arrays, the PromQL counterpart
    of query/engine.py ``ColumnRows``: each series' ``metric`` dict (made
    here, once a series: the evaluator's labels may be a lazy view over
    device state, which no ``deepcopy`` survives), the evaluator's
    ``values`` (float64 [series, steps]) and the steps in seconds.  It
    builds nothing a point: ``encode`` writes the array's JSON text from
    the arrays in one native call (``payload_body``).  Read as a sequence
    it *becomes* the list of ``{"metric", "values"}`` dicts that
    ``to_list`` builds: built at the first read and kept, so that a write
    through it (its holder may change a point) stays, and from there on
    the list is the result and the arrays go, as with
    ``QueryResult.rows``."""

    __slots__ = ("metrics", "values", "step_seconds", "_list")

    def __init__(self, labels, values: np.ndarray, step_seconds: np.ndarray):
        self.metrics = [_metric(lab) for lab in labels]
        self.values = values
        self.step_seconds = step_seconds
        self._list = None

    def to_list(self) -> list[dict]:
        """Point by point, over whole arrays: one test over the matrix for
        what is not finite, one ``tolist()``.  NaN is no sample, and a
        series without a sample no series."""
        ts = self.step_seconds.tolist()
        odd = (~np.isfinite(self.values)).any(axis=1).tolist()
        result = []
        for metric, row, has_odd in zip(self.metrics, self.values.tolist(),
                                        odd):
            if has_odd:
                pts = [[t, fmt_val(v)] for t, v in zip(ts, row) if v == v]
            else:
                pts = [[t, repr(v)] for t, v in zip(ts, row)]
            if pts:
                result.append({"metric": metric, "values": pts})
        return result

    def encode(self) -> memoryview | None:
        """``json.dumps(self.to_list())`` as bytes, from the arrays; None
        once it was read through, or without the native encoder."""
        if self._list is not None:
            return None
        return native.json_matrix(
            self.values, self.step_seconds,
            [json.dumps(metric).encode() for metric in self.metrics])

    def _read(self) -> list[dict]:
        if self._list is None:
            self._list = self.to_list()
            self.metrics = self.values = self.step_seconds = None
        return self._list

    def __len__(self) -> int:
        return len(self._read())

    def __getitem__(self, i):
        return self._read()[i]

    def __iter__(self):
        return iter(self._read())

    def __eq__(self, other):
        return self._read() == other


def range_payload(res, steps) -> dict:
    vals = np.asarray(res.values, dtype=np.float64)[
        :len(res.labels), :len(steps)]
    ts = np.asarray(steps, dtype=np.float64) / 1000.0
    return {"status": "success",
            "data": {"resultType": "matrix",
                     "result": MatrixSeries(res.labels, vals, ts)}}


def payload_body(payload: dict) -> tuple[bytes, str]:
    """The JSON body of a payload and the encoder that wrote it.  Where
    ``result`` is a ``MatrixSeries`` that nothing has read through and
    the native encoder is there, the body is ``json.dumps`` of the
    envelope around the encoder's bytes (``"columns"``); anything else (an
    instant vector, an error, a result that became its list, no library)
    is ``json.dumps`` (``"rows"``).  Both give the same bytes."""
    data = payload.get("data")
    result = data.get("result") if isinstance(data, dict) else None
    if isinstance(result, MatrixSeries):
        encoded = result.encode()
        if encoded is not None:
            encoded = native.json_around(payload, data, "result", encoded)
        if encoded is not None:
            return encoded, "columns"
        data["result"] = result._read()
    return json.dumps(payload).encode(), "rows"


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
