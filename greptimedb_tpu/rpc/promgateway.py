"""PromQL-over-gRPC gateway (reference
src/servers/src/grpc/prom_query_gateway.rs: the frontend gRPC service
that evaluates PromQL and answers in the Prometheus API shape, for
clients that speak gRPC instead of HTTP).

Our gRPC substrate is Arrow Flight (rpc/), so the gateway is a Flight
action service: do_action("prom_query", {query, time | start+end+step,
lookback?}) → one Result holding the Prometheus JSON payload."""

from __future__ import annotations

import json
import time

import pyarrow.flight as fl

from greptimedb_tpu.promql.format import evaluate, payload_body


class PromGatewayServer(fl.FlightServerBase):
    def __init__(self, db, host: str = "127.0.0.1", port: int = 0):
        location = f"grpc://{host}:{port}"
        super().__init__(location)
        self.db = db
        self.host = host

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def do_action(self, context, action):
        if action.type != "prom_query":
            raise fl.FlightServerError(f"unknown action {action.type}")
        try:
            req = json.loads(action.body.to_pybytes().decode())
            query = req["query"]
            if "time" in req or ("start" not in req):
                t = float(req.get("time", time.time()))
                payload = evaluate(self.db, query, t, t, 1.0,
                                   req.get("lookback"))
            else:
                payload = evaluate(
                    self.db, query, float(req["start"]), float(req["end"]),
                    float(req.get("step", 60.0)), req.get("lookback"),
                )
        except fl.FlightServerError:
            raise
        except Exception as e:  # noqa: BLE001 — prom error envelope
            payload = {"status": "error", "errorType": "bad_data",
                       "error": str(e)}
        yield fl.Result(payload_body(payload)[0])


def prom_query(address: str, query: str, **params) -> dict:
    """Client helper: one PromQL evaluation over the gateway."""
    client = fl.connect(f"grpc://{address}")
    try:
        body = json.dumps({"query": query, **params}).encode()
        results = list(client.do_action(fl.Action("prom_query", body)))
        return json.loads(results[0].body.to_pybytes().decode())
    finally:
        client.close()
